"""What a profiler trace of the serving engine shows: host spans inside
``ServingEngine.step`` (``serve.*``, on the profiler's clock) and the
model's named scopes on every compiled operation. Tracing must change no
call, sync or compile of the engine."""
import math
import re
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import ElasticConfig, get_config, get_elastic
from repro.models import model_init, router_init
from repro.training import GenRequest, ServingEngine
from tests.conftest import f32

SCOPES = ("attention", "mlp", "router", "lm_head", "sample")
PAGE = 8
# paged serving takes a dense MLP (see ServingEngine._validate_paged)
DENSE_KW = dict(mlp_token_capacity=0.5, mha_token_capacity=0.5,
                mha_head_topk=2, lora_rank=1)
PROMPT_LENS = (5, 13, 17, 8)


def _engine(layout):
    key = jax.random.PRNGKey(0)
    cfg = f32(get_config("toy-lm", "smoke"))
    if layout == "paged":
        ecfg = ElasticConfig(**DENSE_KW)
        kw = dict(kv_layout="paged", page_size=PAGE)
    else:                        # moefied experts: the elastic decode path
        ecfg = get_elastic("toy-lm", cfg)
        kw = {}
    params = model_init(key, cfg, ecfg)
    rp = router_init(jax.random.fold_in(key, 1), cfg, ecfg)
    return ServingEngine(params, rp, cfg, ecfg, mode="infer", batch_size=2,
                         max_seq=48, **kw)


def _requests(vocab):
    """Prompts whose first tokens differ, so no page is a shared prefix."""
    rng = np.random.default_rng(0)
    out = []
    for i, n in enumerate(PROMPT_LENS):
        p = rng.integers(1, vocab, n, dtype=np.int32)
        p[0] = i + 1
        out.append(GenRequest(p, 3 + i % 2))
    return out


def _serve(engine):
    handles = [engine.submit(r) for r in _requests(engine.cfg.vocab_size)]
    while engine.has_work:
        engine.step()
    return handles


def _host_spans(log_dir: Path):
    """[(name, start, end, stats)] of the serve.* spans, by start."""
    path = sorted(log_dir.glob("**/*.xplane.pb"))[-1]
    out = []
    for pl in ProfileData.from_file(str(path)).planes:
        if not pl.name.startswith("/host:"):
            continue
        for ln in pl.lines:
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats)) for e in ln.events
                    if e.name.startswith("serve.")]
    return sorted(out, key=lambda s: s[1])


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.fixture(scope="module", params=["ring", "paged"])
def traced(request, tmp_path_factory):
    """(layout, engine, handles, spans) of a workload served under the
    profiler at its host tracer's level 1, as the benchmark records."""
    engine = _engine(request.param)
    log_dir = tmp_path_factory.mktemp(f"trace_{request.param}")
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(log_dir), profiler_options=opts):
        handles = _serve(engine)
    return request.param, engine, handles, _host_spans(log_dir)


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def test_admissions_lie_inside_steps_with_their_request_id(traced):
    _layout, _engine_, handles, spans = traced
    steps = _named(spans, "serve.step")
    admits = _named(spans, "serve.admit")
    assert steps and len(admits) == len(handles)
    for a in admits:
        assert sum(_inside(a, s) for s in steps) == 1
    assert sorted(a[3]["request_id"] for a in admits) == \
        sorted(h.id for h in handles)
    by_id = {h.id: h for h in handles}
    for a in admits:
        assert a[3]["prompt_len"] == len(by_id[a[3]["request_id"]].request
                                         .prompt)
        # the wait for the first token is inside its admission
        syncs = [s for s in _named(spans, "serve.admit.sync")
                 if _inside(s, a)]
        assert len(syncs) == 1
    # the scheduler pass opens every step
    for s in steps:
        first = min((x for x in spans if x is not s and _inside(x, s)),
                    key=lambda x: x[1])
        assert first[0] == "serve.schedule"


def test_one_admission_call_per_chunk(traced):
    layout, _engine_, _handles, spans = traced
    for a in _named(spans, "serve.admit"):
        calls = [c for c in _named(spans, "serve.admit.call")
                 if _inside(c, a)]
        plen = a[3]["prompt_len"]
        want = math.ceil(plen / PAGE) if layout == "paged" else 1
        assert [c[3]["chunk"] for c in calls] == list(range(want))
        prefix = [p for p in _named(spans, "serve.admit.prefix")
                  if _inside(p, a)]
        assert len(prefix) == (layout == "paged")


def test_one_decode_and_sync_per_step_with_live_slots(traced):
    layout, _engine_, handles, spans = traced
    n_decode = 0
    for s in _named(spans, "serve.step"):
        inner = [x for x in spans if _inside(x, s) and x is not s]
        dec = _named(inner, "serve.decode")
        sync = _named(inner, "serve.sync")
        assert len(dec) == len(sync) <= 1
        if dec:
            n_decode += 1
            assert dec[0][3]["live"] >= 1
            up = _named(inner, "serve.upload")
            assert len(up) == 1 and up[0][2] <= dec[0][1]
            assert dec[0][2] <= sync[0][1]
            emit = _named(inner, "serve.emit")
            assert len(emit) == 1 and sync[0][2] <= emit[0][1]
            pages = _named(inner, "serve.pages")
            assert len(pages) == (layout == "paged")
    # every token a decode emitted was emitted by a traced decode with the
    # slot counted live: one prefill token per request, the rest decoded
    decoded = sum(len(h.output) - 1 for h in handles)
    assert sum(d[3]["live"] for d in _named(spans, "serve.decode")) == \
        decoded
    assert n_decode >= max(len(h.output) for h in handles) - 1


def test_decode_span_counts_the_table_entries_the_kernel_visits(traced):
    """``pages`` of a paged decode: entries 0 .. t // page_size of every
    live slot's table row, t the position the step writes; 0 on the ring."""
    layout, _engine_, handles, spans = traced
    decs = _named(spans, "serve.decode")
    if layout == "ring":
        assert all(d[3]["pages"] == 0 for d in decs)
        return
    for d in decs:
        assert d[3]["live"] <= d[3]["pages"] <= d[3]["live"] * (48 // PAGE)
    want = sum((len(h.request.prompt) + j) // PAGE + 1
               for h in handles for j in range(len(h.output) - 1))
    assert sum(d[3]["pages"] for d in decs) == want


def test_tracing_changes_no_compile_and_no_token(traced):
    layout, engine, handles, _spans = traced
    plain = _engine(layout)
    want = _serve(plain)
    assert engine.compile_counts() == plain.compile_counts()
    for h, w in zip(handles, want):
        np.testing.assert_array_equal(h.output, w.output)


def _innermost(op_name: str):
    return next((p for p in reversed(op_name.split("/")) if p in SCOPES),
                None)


@pytest.mark.parametrize("entry", ["decode", "admit"])
@pytest.mark.parametrize("layout", ["ring", "paged"])
def test_scopes_in_compiled_serving_graphs(layout, entry):
    engine = _engine(layout)
    ep = engine.entry_points()[entry]
    text = ep.fn.lower(*ep.args, **ep.static).compile().as_text()
    found = {_innermost(n) for n in re.findall(r'op_name="([^"]*)"', text)}
    assert set(SCOPES) <= found
    if layout == "ring" and entry == "decode":
        # the moefied experts' weights, gathered per slot, are MLP time
        cfg = engine.cfg
        fe = cfg.d_ff // engine.spec.mlp_n_experts
        sizes = {f"{{1,{cfg.d_model},{fe}}}", f"{{1,{fe},{cfg.d_model}}}"}
        gathers = [(m.group(1), m.group(2)) for m in re.finditer(
            r' gather\(.*slice_sizes=(\{[\d,]+\}).*op_name="([^"]*)"',
            text)]
        experts = [n for s, n in gathers if s in sizes]
        assert len(experts) >= 2
        assert all(_innermost(n) == "mlp" for n in experts)

