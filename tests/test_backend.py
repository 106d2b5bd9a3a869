"""Kernel-backend dispatch + RoutingPlan reuse (ISSUE 4).

Covers the acceptance properties:
  * parity grid: forward outputs and router gradients agree across
    kernel_backend {ref, interpret} x routing_impl {ragged, gather,
    dense_mask} (the interpret backend runs the REAL Pallas kernel logic
    through the model hot path, with the jnp-reference backward);
  * the model forward under kernel_backend="interpret" actually calls the
    Pallas kernels (call-counter on the kernel modules' entry points);
  * exactly ONE RoutingPlan sort per block trace (no per-component
    re-sort), and ZERO sorts on the identity (full-budget) graph;
  * the ring-cache decode kernel bit-matches the jnp attn_decode twin on
    staggered per-slot positions.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.elasti_toy import toy_lm
from repro.core import routing as R
from repro.core.policy import ElasticPolicy, ElasticSpec, ragged_bucket
from repro.models import forward, model_init, router_init
from tests.conftest import f32

N_EXPERTS = 4


def _setup(key, s=24, *, experts=False, impl="ragged", backend="ref"):
    cfg = f32(toy_lm())
    spec = ElasticSpec(
        mha_token_routed=True, mlp_token_routed=True, mha_head_routed=True,
        mlp_n_experts=N_EXPERTS if experts else None, expert_routed=experts,
        lora_rank=1, routing_impl=impl, kernel_backend=backend)
    params = model_init(key, cfg, spec)
    rp = router_init(jax.random.fold_in(key, 1), cfg, spec)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (2, s), dtype=np.int32))}
    return cfg, spec, params, rp, batch


def _pol(budget, cfg, experts):
    return ElasticPolicy.uniform(
        budget, n_heads=cfg.n_heads,
        n_experts=N_EXPERTS if experts else None, static=True)


# ----------------------------- parity grid -----------------------------------

@pytest.mark.parametrize("experts", [False, True])
@pytest.mark.parametrize("impl", ["ragged", "gather", "dense_mask"])
@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_backend_impl_parity_grid(key, backend, impl, experts):
    """Forward outputs and router grads agree across every execution path
    x backend combination (baseline: ref x gather)."""
    cfg, spec, params, rp, batch = _setup(key, experts=experts, impl=impl,
                                          backend=backend)
    base_spec = dataclasses.replace(spec, routing_impl="gather",
                                    kernel_backend="ref")
    pol = _pol(0.5, cfg, experts)

    def loss(rp, sp):
        out, aux = forward(params, rp, batch, cfg, sp, mode="train",
                           policy=pol)
        return jnp.sum(out ** 2) * 1e-4 + aux.topk + aux.load, out

    (l_b, out_b), g_b = jax.value_and_grad(loss, has_aux=True)(rp, base_spec)
    (l_t, out_t), g_t = jax.value_and_grad(loss, has_aux=True)(rp, spec)
    np.testing.assert_allclose(np.asarray(out_t), np.asarray(out_b),
                               atol=2e-4)
    np.testing.assert_allclose(float(l_t), float(l_b), rtol=1e-4)
    for pt, pb in zip(jax.tree.leaves(g_t), jax.tree.leaves(g_b)):
        np.testing.assert_allclose(np.asarray(pt), np.asarray(pb), atol=2e-4)
    assert sum(float(jnp.abs(x).sum()) for x in jax.tree.leaves(g_t)) > 0


# ------------------------- kernel call counting ------------------------------

def test_interpret_backend_calls_all_pallas_kernels(key, monkeypatch):
    """Acceptance: the model forward with kernel_backend="interpret"
    dispatches through all three prefill Pallas kernels, not the jnp
    twins."""
    import sys
    # the package __init__ shadows the submodule names with the ops
    # wrappers, so resolve the real modules through sys.modules
    flash_mod = sys.modules["repro.kernels.flash_attention"]
    mlp_mod = sys.modules["repro.kernels.fused_mlp"]
    gmm_mod = sys.modules["repro.kernels.moe_gmm"]

    calls = {"flash": 0, "fused_mlp": 0, "moe_gmm": 0}

    def count(name, fn):
        def wrapped(*a, **kw):
            calls[name] += 1
            return fn(*a, **kw)
        return wrapped

    monkeypatch.setattr(flash_mod, "flash_attention",
                        count("flash", flash_mod.flash_attention))
    monkeypatch.setattr(mlp_mod, "fused_mlp",
                        count("fused_mlp", mlp_mod.fused_mlp))
    monkeypatch.setattr(gmm_mod, "moe_gmm",
                        count("moe_gmm", gmm_mod.moe_gmm))
    jax.clear_caches()  # the jitted ops wrappers must re-trace

    # dense-MLP spec: flash attention + fused_mlp on the plan's bucket
    cfg, spec, params, rp, batch = _setup(key, backend="interpret")
    forward(params, rp, batch, cfg, spec, mode="train",
            policy=_pol(0.5, cfg, False))
    # teacher-mode forward: the unrouted MLP goes through fused_mlp too
    forward(params, None, batch, cfg, spec, mode="base")
    # moefied spec: expert dispatch goes through moe_gmm
    cfg, spec, params, rp, batch = _setup(key, experts=True,
                                          backend="interpret")
    forward(params, rp, batch, cfg, spec, mode="train",
            policy=_pol(0.5, cfg, True))
    assert all(c > 0 for c in calls.values()), calls


def test_pallas_backend_refused_off_tpu():
    """"pallas" names the compiled TPU kernels: off a TPU it raises instead
    of running them interpreted under the device path's name."""
    from repro.kernels.ops import resolve_backend
    assert jax.default_backend() != "tpu"
    with pytest.raises(ValueError, match="interpret"):
        resolve_backend("pallas")
    assert resolve_backend("auto") == "ref"
    assert resolve_backend("interpret") == "interpret"


# --------------------------- one sort per block ------------------------------

def _count_plan_sorts(fn, *args):
    before = R.PLAN_SORT_COUNT
    jax.jit(fn).lower(*args)     # trace only — sorts are counted per trace
    return R.PLAN_SORT_COUNT - before


def test_one_routing_plan_sort_per_block_trace(key):
    """Acceptance: the attention and MLP students share ONE RoutingPlan —
    a single sort per block trace (the toy pattern scan traces its period
    once), where the pre-refactor path issued 3+ per component."""
    cfg = f32(toy_lm(vocab=256))
    spec = ElasticSpec(mha_token_routed=True, mlp_token_routed=True)
    params = model_init(key, cfg, spec)
    rp = router_init(jax.random.fold_in(key, 1), cfg, spec)
    batch = {"tokens": jnp.zeros((2, 256), jnp.int32)}

    def fwd(budget):
        pol = ElasticPolicy.uniform(budget, static=True)
        return lambda rp, b: forward(params, rp, b, cfg, spec, mode="train",
                                     policy=pol)[0]

    # toy-lm: homogeneous pattern -> the block body is traced exactly once
    assert _count_plan_sorts(fwd(0.5), rp, batch) == 1
    # identity (full-budget) graph: no routing work at all
    assert _count_plan_sorts(fwd(1.0), rp, batch) == 0
    # teacher forward: no sorts either
    assert _count_plan_sorts(
        lambda b: forward(params, None, b, cfg, None, mode="base")[0],
        batch) == 0

    # hloprof-verified: the COMPILED forward lowers exactly one sort op
    # (shared across all layers via the pattern scan) at a routed budget,
    # and zero on the identity graph
    from repro.launch.hloprof import profile_text

    def hlo_sorts(budget):
        c = jax.jit(fwd(budget)).lower(rp, batch).compile()
        return profile_text(c.as_text()).get("sort", {"count": 0})["count"]

    assert hlo_sorts(0.5) == 1
    assert hlo_sorts(1.0) == 0


# ------------------------- decode kernel parity ------------------------------

def test_decode_kernel_matches_jnp_twin_on_staggered_slots(key):
    """The ring-cache decode kernel == attn_decode's jnp path, with every
    serving slot at its own position (continuous batching)."""
    from repro.models.attention import attn_cache_init, attn_decode, attn_init
    cfg = f32(toy_lm())
    p = attn_init(key, cfg)
    B, L = 3, 16
    cache = attn_cache_init(cfg, B, L, window=0)
    rng = np.random.default_rng(0)
    # warm the ring cache at staggered offsets with real entries
    t = jnp.asarray([2, 7, 13], jnp.int32)
    ks = jax.random.split(key, 8)
    pos = jnp.where(jnp.arange(L)[None, :] <= t[:, None],
                    jnp.arange(L)[None, :], -1).astype(jnp.int32)
    cache = {
        "k": jax.random.normal(ks[0], cache["k"].shape, cache["k"].dtype),
        "v": jax.random.normal(ks[1], cache["v"].shape, cache["v"].dtype),
        "valid": jnp.asarray(rng.random((B, L)) < 0.9),
        "pos": pos,
    }
    x = jax.random.normal(ks[2], (B, 1, cfg.d_model), jnp.float32)
    write = jnp.asarray([True, False, True])
    for window in (0, 6):
        y_ref, c_ref = attn_decode(p, x, cache, t, cfg=cfg, window=window,
                                   write=write, backend=None)
        y_k, c_k = attn_decode(p, x, cache, t, cfg=cfg, window=window,
                               write=write, backend="interpret")
        np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref),
                                   atol=2e-5, rtol=2e-5)
        for a, b in zip(jax.tree.leaves(c_ref), jax.tree.leaves(c_k)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_identity_graph_is_bit_exact_teacher(key):
    """The identity bucket (== S) skips all routing work and reproduces
    the teacher bit-for-bit, for traced full-budget policies."""
    cfg, spec, params, rp, batch = _setup(key)
    teacher, _ = forward(params, None, batch, cfg, None, mode="base")
    pol = jax.tree.map(jnp.asarray, ElasticPolicy.uniform(1.0))
    s = batch["tokens"].shape[1]
    assert ragged_bucket(pol, s) == R.IDENTITY_BUCKET
    out, _ = forward(params, rp, batch, cfg, spec, mode="train", policy=pol,
                     bucket=R.IDENTITY_BUCKET)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(teacher))
    # a real bucket that merely EQUALS a (shorter) batch's length is not
    # an identity assertion: it degrades to the dense fallback, which
    # still applies routing weights — outputs must differ from teacher
    half = jax.tree.map(jnp.asarray, ElasticPolicy.uniform(0.5))
    out_h, _ = forward(params, rp, batch, cfg, spec, mode="train",
                       policy=half, bucket=s)
    assert not np.allclose(np.asarray(out_h), np.asarray(teacher))


# ------------------ backend x layout x dtype parity grid ---------------------
#
# docs/quantization.md: the quantized KV cache + weights must serve from
# both cache layouts on every backend with bounded logit error and greedy-
# token parity vs the fp32 reference, and a slot's logits must not depend
# on what another live slot holds (per-row compute is row-local, and int8
# rows are quantized ONCE at the write site).

# Quantized-vs-fp32 logit bound: int8 weights and KV round each value to
# 1/254 of its channel's range, which moves toy-lm logits by up to ~0.2.
QUANT_LOGIT_TOL = 0.25
# Solo (one slot) vs staggered (two slots) runs of the same request: XLA's
# CPU dot picks its reduction order by batch size (matrix-vector for one
# row, matrix-matrix for two), so the same row's projections differ by
# float32 rounding (measured <= 7.5e-7 on logits of magnitude ~1-5). Where
# that tips a K/V value across a bf16 or int8 rounding boundary, the cache
# stores it one storage ulp apart, which moves logits further (measured
# <= 2.3e-5, bf16 paged).
BATCH_ROUNDING_TOL = 1e-4

def _ring_logits(params, cfg, spec, toks, kv_dtype, *, other=None):
    """Prefill ``toks`` into the LAST ring slot, 3 greedy decode steps;
    ``other`` staggers a second live request in slot 0 at its own t."""
    from repro.models.model import cache_init, cache_insert, prefill
    from repro.models.model import decode_step
    S, L = toks.shape[1], 32
    B = 2 if other is not None else 1
    caches = cache_init(cfg, B, L, kv_dtype=kv_dtype)
    logits, row = prefill(params, None, {"tokens": toks}, cfg, spec,
                          mode="base", max_cache_len=L)
    caches = cache_insert(caches, row, B - 1)
    tok = jnp.argmax(logits, -1)[:, None]
    ts = [S]
    if other is not None:
        lo, row2 = prefill(params, None, {"tokens": other}, cfg, spec,
                           mode="base", max_cache_len=L)
        caches = cache_insert(caches, row2, 0)
        ts = [other.shape[1], S]
        tok = jnp.concatenate([jnp.argmax(lo, -1)[:, None], tok], 0)
    t = jnp.asarray(ts, jnp.int32)
    outs = []
    for _ in range(3):
        logits, caches = decode_step(params, None, tok, caches, t, cfg,
                                     spec, mode="base")
        outs.append(logits[B - 1])
        tok = jnp.argmax(logits, -1)[:, None]
        t = t + 1
    return jnp.stack(outs)


def _paged_logits(params, cfg, spec, toks, kv_dtype, *, other=None):
    """Chunked-prefill ``toks`` into pages [3, 5], 3 greedy decode steps;
    ``other`` staggers a second request in pages [7, 9]."""
    from repro.models.model import paged_cache_init, prefill_chunk_step
    from repro.models.model import decode_step
    ps, P = 8, 4
    caches = paged_cache_init(cfg, 16, ps, kv_dtype=kv_dtype)

    def pf(tk, pages):
        nonlocal caches
        S_ = tk.shape[1]
        trow = jnp.full((P,), -1, jnp.int32)
        for i, pg in enumerate(pages):
            trow = trow.at[i].set(pg)
        lg = None
        for c in range(-(-S_ // ps)):
            chunk = jnp.zeros((1, ps), jnp.int32)
            n = min(ps, S_ - c * ps)
            chunk = chunk.at[0, :n].set(tk[0, c * ps:c * ps + n])
            lg, caches = prefill_chunk_step(
                params, None, chunk, caches, jnp.asarray(pages[c]), trow,
                jnp.asarray(c * ps), jnp.asarray(S_), cfg, spec,
                mode="base")
        return lg, trow

    lg, trow = pf(toks, [3, 5])
    rows, ts = [trow], [toks.shape[1]]
    toks_d = [jnp.argmax(lg, -1)[:, None]]
    if other is not None:
        lo, trow2 = pf(other, [7, 9])
        rows, ts = [trow2, trow], [other.shape[1], toks.shape[1]]
        toks_d = [jnp.argmax(lo, -1)[:, None]] + toks_d
    table = jnp.stack(rows)
    t = jnp.asarray(ts, jnp.int32)
    tok = jnp.concatenate(toks_d, 0)
    trash = jnp.full((len(ts),), 15, jnp.int32)
    outs = []
    for _ in range(3):
        lg, caches = decode_step(params, None, tok, caches, t, cfg, spec,
                                 mode="base", table=table, trash=trash)
        outs.append(lg[-1])
        tok = jnp.argmax(lg, -1)[:, None]
        t = t + 1
    return jnp.stack(outs)


@pytest.mark.parametrize("layout", ["ring", "paged"])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_quantized_kv_layout_dtype_grid(key, backend, kv_dtype, layout):
    """Quantized serving parity: bounded logit error + greedy match (where
    the fp32 margin exceeds the bound) vs the fp32 reference on the same
    backend; a staggered slot's logits equal its solo run's up to batch-
    size rounding and do not depend on the other slot's request."""
    from repro.models.quant import quantize_params_tree
    cfg = f32(toy_lm())
    spec = ElasticSpec(kernel_backend=backend)
    qspec = dataclasses.replace(spec, kv_dtype=kv_dtype,
                                weight_dtype=kv_dtype)
    params = model_init(key, cfg, spec)
    qparams = quantize_params_tree(params, kv_dtype)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 12),
                                    dtype=np.int32))
    other = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 9),
                                     dtype=np.int32))
    run = _ring_logits if layout == "ring" else _paged_logits
    ref_out = np.asarray(run(params, cfg, spec, toks, "fp32"))
    q_out = np.asarray(run(qparams, cfg, qspec, toks, kv_dtype))
    err = float(np.max(np.abs(ref_out - q_out)))
    assert err <= QUANT_LOGIT_TOL, \
        f"{layout}/{kv_dtype}/{backend}: logit error {err}"
    # greedy parity wherever the fp32 top-2 margin is wider than twice the
    # measured logit gap between the paths; a narrower margin may flip on
    # quantization rounding alone
    top2 = np.sort(ref_out, -1)[:, -2:]
    decided = top2[:, 1] - top2[:, 0] > 2 * err
    np.testing.assert_array_equal(np.argmax(ref_out, -1)[decided],
                                  np.argmax(q_out, -1)[decided],
                                  err_msg="greedy tokens diverged from fp32")
    # a second live request at its own position: this slot's logits match
    # its solo run up to batch-size rounding, and are bitwise independent
    # of WHICH request the other slot holds (quantize-once rows +
    # row-local compute)
    q_stag = np.asarray(run(qparams, cfg, qspec, toks, kv_dtype,
                            other=other))
    np.testing.assert_allclose(q_stag, q_out, rtol=0,
                               atol=BATCH_ROUNDING_TOL)
    q_stag2 = np.asarray(run(qparams, cfg, qspec, toks, kv_dtype,
                             other=(other + 7) % cfg.vocab_size))
    np.testing.assert_array_equal(q_stag, q_stag2)
