"""The trace reduction, on a short trace recorded on a TPU v5e (0.4 s of
the paged cell) and on hand-made intervals."""
from pathlib import Path

import pytest

import trace as T

DATA = Path(__file__).parent / "data" / "paged_v5e.xplane.pb"
KERNELS = ("paged_decode_attention", "fused_mlp", "decode_attention")


@pytest.fixture(scope="module")
def recorded():
    pd = T.load(DATA)
    return pd, T.reduce(pd, {"decode": "step", "admit": "admit"}, KERNELS)


def test_union_merges_and_clips():
    got = T.union([(5, 9), (0, 2), (1, 3), (8, 12), (20, 30)], 1, 25)
    assert got == [[1, 3], [5, 12], [20, 25]]


def test_op_names():
    ev = ('%decode_attention.7 = bf16[16,28,1,128]{3,2,1,0} custom-call('
          's32[16]{0} %copy-done.33), custom_call_target="tpu_custom_call"')
    assert T.op_name(ev) == "decode_attention.7"
    assert T.base_name(ev) == "decode_attention"
    assert T.base_name("fusion.12.clone.3") == "fusion.12.clone"
    assert T.program_of("jit_step(123)", {"decode": "step"}) == "decode"
    assert T.program_of("jit_step", {"decode": "step"}) == "decode"
    assert T.program_of("jit_admit_x", {"admit": "admit"}) is None


def test_label_innermost_span():
    host = [("bench.engine_step", 0, 100), ("bench.submit", 40, 50)]
    assert T._label(host, 45) == "bench.submit"
    assert T._label(host, 10) == "bench.engine_step"
    assert T._label(host, 150) == "outside spans"


def test_recorded_busy_is_the_union(recorded):
    pd, red = recorded
    p = T.planes(pd)
    lo = p["host"][0][1]
    hi = max(e[2] for e in p["host"])
    ops = p["device"][0][1][T.OPS]
    # brute force: a nanosecond grid is too fine; check the union against
    # the sum of clipped, non-overlapping pieces instead
    merged = T.union([(s, e) for _n, s, e in ops], lo, hi)
    assert all(a[1] < b[0] for a, b in zip(merged, merged[1:]))
    assert red["busy_s"] == pytest.approx(
        sum(e - s for s, e in merged) / 1e9)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["window_s"] == pytest.approx((hi - lo) / 1e9)


def test_recorded_programs_and_kernels(recorded):
    _pd, red = recorded
    assert red["program_n"].get("decode", 0) >= 1
    assert red["program_s"]["decode"] > 0
    assert red["kernel_s"].get("paged_decode_attention", 0) > 0
    # a kernel runs inside its program
    assert red["kernel_s"]["paged_decode_attention"] < \
        red["program_s"]["decode"]
    assert "decode_attention" not in red["kernel_s"]


def test_recorded_gaps_are_labelled(recorded):
    _pd, red = recorded
    assert red["idle_gaps"]
    assert all(name.startswith("bench.") or name == "outside spans"
               for name, _s in red["idle_gaps"])
    secs = [s for _n, s in red["idle_gaps"]]
    assert secs == sorted(secs, reverse=True)
    assert sum(secs) <= red["window_s"] - red["busy_s"] + 1e-9


def test_combine_sums_stretches(recorded):
    _pd, red = recorded
    both = T.combine([red, red])
    assert both["stretches"] == 2
    assert both["window_s"] == pytest.approx(2 * red["window_s"])
    assert both["busy_s"] == pytest.approx(2 * red["busy_s"])
    assert both["program_n"]["decode"] == 2 * red["program_n"]["decode"]
    assert both["kernel_s"]["paged_decode_attention"] == pytest.approx(
        2 * red["kernel_s"]["paged_decode_attention"])
    top, secs = both["device_ops"][0]
    assert secs == pytest.approx(2 * red["op_s"][top])
    assert len(both["idle_gaps"]) == min(10, 2 * len(red["idle_gaps"]))
    assert both["idle_gaps"][0][1] == pytest.approx(red["idle_gaps"][0][1])
