"""The wire reader of trace files and the reduction of the engine's spans
and the model's scopes (``engine_trace.py``), on two short traces of the
paged cell recorded on a TPU v5e and on hand-made intervals.

``paged_v5e.xplane.pb`` (0.4 s, three decode steps) predates the spans and
scopes: the values ``trace.reduce`` gives on it are pinned here, so that
nothing the benchmark already reports moves with the new reduction.
``paged_spans_v5e.xplane.pb`` is one decode step of the paged cell's
engine (two live slots) with both, written by ``chip_spans.py --record``."""
from pathlib import Path

import pytest

import engine_trace as E
import trace as T
import xplane

DATA = Path(__file__).parent / "data"
OLD = DATA / "paged_v5e.xplane.pb"
NEW = DATA / "paged_spans_v5e.xplane.pb"
PROGRAMS = {"decode": "step", "admit": "admit"}
KERNELS = ("paged_decode_attention", "fused_mlp", "decode_attention")


@pytest.fixture(scope="module")
def old():
    pd = T.load(OLD)
    return pd, T.reduce(pd, PROGRAMS, KERNELS)


@pytest.fixture(scope="module")
def new():
    pd = T.load(NEW)
    return pd, E.reduce(pd, NEW, PROGRAMS, KERNELS)


def _device(path):
    return [p for p in xplane.read(path) if p.name == "/device:TPU:0"][0]


def test_wire_reader_gives_profile_data_events():
    pd = T.load(OLD)
    want = T.planes(pd)["device"][0][1]
    dev = _device(OLD)
    lines = {ln.name: ln for ln in dev.lines}
    for name in (T.OPS, T.MODULES):
        got = [(dev.event_metadata[m][0], s, e)
               for m, s, e in lines[name].events]
        assert [(n, s, e) for n, s, e, *_ in want[name]] == got
    assert len(lines[T.OPS].events) == 3882


def test_tf_op_is_the_scope_path():
    dev = _device(OLD)
    by_name = {T.op_name(n): stats
               for n, stats in dev.event_metadata.values()}
    stats = by_name["compare_reduce_fusion"]
    assert stats["tf_op"].rsplit(":", 1)[0] == "jit(step)/reduce_or"
    assert stats["source"].endswith("serve.py:117")


def test_existing_reduction_is_pinned(old):
    _pd, red = old
    assert red["window_s"] == pytest.approx(1.020951301, abs=1e-12)
    assert red["busy_s"] == pytest.approx(1.00856764, abs=1e-12)
    assert red["program_s"] == pytest.approx({"decode": 1.008571359},
                                              abs=1e-12)
    assert red["program_n"] == {"decode": 3.0}
    assert red["kernel_s"] == pytest.approx(
        {"paged_decode_attention": 0.968197296}, abs=1e-12)
    assert len(red["op_s"]) == 41
    assert [n for n, _s in red["device_ops"]] == [
        "paged_decode_attention", "fusion", "copy",
        "bitcast_dynamic-update-slice_fusion", "copy_bitcast_fusion",
        "dynamic-slice_bitcast_fusion", "constant_dynamic-slice_fusion",
        "copy-done", "dynamic-slice_reduce_fusion", "iota_reduce_fusion"]
    assert [s for _n, s in red["device_ops"]][:3] == pytest.approx(
        [0.968197296, 0.020879205, 0.009749944], abs=1e-12)
    assert len(red["idle_gaps"]) == 10
    assert {n for n, _s in red["idle_gaps"]} == {"bench.engine_step"}
    assert [s for _n, s in red["idle_gaps"]][:4] == pytest.approx(
        [0.004176518, 0.004053009, 0.002572476, 0.001580396], abs=1e-12)


def test_engine_reduction_keeps_every_existing_value(old):
    pd, red = old
    got = E.reduce(pd, OLD, PROGRAMS, KERNELS)
    for k, v in red.items():
        if k != "idle_gaps":
            assert got[k] == v, k
    # the same gaps; without serve.* spans the labels are the harness's
    assert got["idle_gaps"] == red["idle_gaps"]
    assert got["span_n"] == {} and got["engine_idle_s"] == 0.0
    # no scope in that program: all of it is unscoped decode time
    assert set(got["scope_s"]) == {("decode", None)}
    assert got["scope_s"][("decode", None)] <= red["program_s"]["decode"]
    # what a program without the spans and scopes gives: nothing to report
    assert {k: f(got) for k, f in E.METRICS.items()} == dict.fromkeys(
        E.METRICS)


def test_combine_keeps_and_sums(old):
    pd, red = old
    one = E.reduce(pd, OLD, PROGRAMS, KERNELS)
    both = E.combine([one, one])
    base = T.combine([red, red])
    for k, v in base.items():
        assert both[k] == v, k
    assert both["scope_s"][("decode", None)] == pytest.approx(
        2 * one["scope_s"][("decode", None)])


def test_scope_of_takes_the_innermost():
    assert E.scope_of("jit(step)/while/body/closed_call/attention/dot:") \
        == "attention"
    assert E.scope_of("jit(step)/while/body/mlp/mlp/router/gt:") == "router"
    assert E.scope_of("jit(step)/router/jit(_where)/select_n:") == "router"
    assert E.scope_of("jit(step)/sample/cond/branch_1_fun/mlp_x/add:") \
        == "sample"
    assert E.scope_of("jit(step)/reduce_or:") is None
    assert E.scope_of("") is None


def test_overlap_of_interval_lists():
    assert E.overlap([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert E.overlap([(0, 10)], [(10, 20)]) == 0
    assert E.overlap([], [(0, 1)]) == 0
    assert E.overlap([(0, 4), (6, 8), (9, 12)], [(1, 7), (7.5, 10)]) == \
        3 + 1 + 0.5 + 1


def test_scope_seconds_by_execution():
    mods = [("jit_step(1)", 100, 200, 1), ("jit_admit(2)", 300, 400, 2),
            ("jit_step(1)", 500, 600, 3),      # ends past the window
            ("jit_other(3)", 700, 800, 4)]
    ops = [("attention", 110, 150), ("mlp", 150, 190), (None, 190, 200),
           ("attention", 310, 320), ("mlp", 510, 520),
           ("sample", 710, 720), ("mlp", 250, 260)]   # between programs
    got = E.scope_seconds(ops, mods, PROGRAMS, 0, 550)
    assert dict(got) == pytest.approx({
        ("decode", "attention"): 40e-9, ("decode", "mlp"): 40e-9,
        ("decode", None): 10e-9, ("admit", "attention"): 10e-9})


def test_recorded_step_holds_its_phases(new):
    pd, _red = new
    host = T.planes(pd)["host"]
    spans = E.engine_spans(pd)
    steps = [sp for sp in spans if sp[0] == "serve.step"]
    assert len(steps) == 1 and len(host) == 1
    step = steps[0]
    assert host[0][1] <= step[1] and step[2] <= host[0][2]
    inner = [sp[0] for sp in spans if sp is not step
             and step[1] <= sp[1] and sp[2] <= step[2]]
    assert inner == ["serve.schedule", "serve.pages", "serve.upload",
                     "serve.decode", "serve.sync", "serve.emit"]


def test_recorded_clocks_agree(new):
    """The program's spans and the device share one clock: the decode
    program starts after its dispatch span opens, and the host's wait for
    its tokens ends no earlier than it does (less 0.1 ms)."""
    pd, _red = new
    spans = E.engine_spans(pd)
    mods = T.planes(pd)["device"][0][1][T.MODULES]
    runs = {}
    for n, s, e, run in mods:
        if T.program_of(n, PROGRAMS) == "decode":
            lo, hi = runs.get(run, (s, e))
            runs[run] = (min(lo, s), max(hi, e))
    assert runs
    for s, e in runs.values():
        dec = [sp for sp in spans if sp[0] == "serve.decode" and sp[1] <= s]
        assert dec
        d = max(dec, key=lambda sp: sp[1])
        sync = min((sp for sp in spans
                    if sp[0] == "serve.sync" and sp[1] >= d[2]),
                   key=lambda sp: sp[1])
        assert sync[2] >= e - 0.1e6


def test_recorded_scopes_split_the_decode(new):
    _pd, red = new
    scope = red["scope_s"]
    att, mlp = scope[("decode", "attention")], scope[("decode", "mlp")]
    assert att > 0 and mlp > 0
    assert att + mlp <= red["program_s"]["decode"]
    assert {k[1] for k in scope} == set(E.SCOPES) | {None}
    # the paged decode kernel is attention time, all of it
    assert red["kernel_s"]["paged_decode_attention"] <= att
    dev = _device(NEW)
    for name, stats in dev.event_metadata.values():
        if T.base_name(name) == "paged_decode_attention":
            assert E.scope_of(stats["tf_op"]) == "attention"


def test_recorded_readers(new):
    _pd, red = new
    got = {k: f(red) for k, f in E.METRICS.items()}
    assert got == pytest.approx({
        "decode_host_ms": 2.70042, "engine_idle_share": 1.18766097721,
        "decode_attention_ms": 325.179394, "decode_mlp_ms": 4.34309})
    idle = 100.0 * (1.0 - red["busy_s"] / red["window_s"])
    assert got["engine_idle_share"] <= idle
    step_ms = red["program_s"]["decode"] / red["program_n"]["decode"] * 1e3
    assert got["decode_attention_ms"] + got["decode_mlp_ms"] <= step_ms


def test_recorded_gaps_name_engine_phases(new):
    """Every long gap of the step falls inside ``serve.step``, and is put
    down to the engine's phase, not to the harness's span around it."""
    _pd, red = new
    assert len(red["idle_gaps"]) == 10
    assert all(n.startswith("serve.") and n != "serve.step"
               for n, _s in red["idle_gaps"])
    # the device waits while the host uploads the decode operands
    assert red["idle_gaps"][0][0] == "serve.upload"
    assert red["idle_gaps"][0][1] == pytest.approx(0.002873812)


def test_record_writes_an_admitting_and_a_decoding_step(tmp_path, capsys):
    """``chip_spans.py --record`` at a CPU size: the same two steps it
    records on the chip, each with the engine's spans."""
    import chip_spans
    from smoke import smoke_checkout
    out = tmp_path / "rec"
    chip_spans.main(["--record", str(out), "--workload",
                     "paged-int8-rag-backlog", "--seed", "3000000007",
                     "--seconds", "2"], require_tpu=False,
                    bench_dir=smoke_checkout(tmp_path),
                    overrides={"kernel_backend": "ref"})
    assert len(capsys.readouterr().out.splitlines()) == 2
    files = sorted(out.iterdir())
    assert [f.name for f in files] == ["0.xplane.pb", "1.xplane.pb"]
    admit, decode = ([sp[0] for sp in E.engine_spans(T.load(f))]
                     for f in files)
    assert admit.count("serve.admit") == 1
    assert admit.count("serve.admit.call") >= 1
    assert "serve.admit" not in decode
    assert admit.count("serve.decode") == decode.count("serve.decode") == 1
