"""Pallas kernel sweeps: shapes x dtypes vs the pure-jnp oracles (ref.py),
executed in interpret mode on CPU (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_mlp import fused_mlp
from repro.kernels.moe_gmm import moe_gmm

TOLS = {jnp.float32: dict(atol=2e-5, rtol=2e-5),
        jnp.bfloat16: dict(atol=5e-2, rtol=5e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Sq,Sk,H,K,Dh", [
    (1, 128, 128, 4, 4, 64),     # MHA square
    (2, 256, 256, 8, 2, 64),     # GQA 4:1
    (1, 64, 320, 4, 1, 128),     # MQA, ragged Sk (block padding path)
    (1, 384, 128, 4, 4, 128),    # Sq > Sk
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 96), (False, 0)])
def test_flash_attention_sweep(B, Sq, Sk, H, K, Dh, causal, window, dtype, key):
    if causal and Sq > Sk:
        pytest.skip("causal requires Sq <= Sk alignment in this harness")
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, Sq, H, Dh), dtype)
    k = jax.random.normal(ks[1], (B, Sk, K, Dh), dtype)
    v = jax.random.normal(ks[2], (B, Sk, K, Dh), dtype)
    valid = jax.random.bernoulli(ks[3], 0.9, (B, Sk))
    got = flash_attention(q, k, v, causal=causal, window=window,
                          kv_valid=valid, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                   kv_valid=valid)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOLS[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("T,D,F,gated,act", [
    (256, 128, 512, True, "swiglu"),
    (100, 128, 384, True, "geglu"),      # ragged T
    (512, 256, 1024, False, "gelu"),
    (64, 128, 320, True, "swiglu"),      # partial last F tile (256 + 64)
])
def test_fused_mlp_sweep(T, D, F, gated, act, dtype, key):
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (T, D), dtype)
    wi = (jax.random.normal(ks[1], (D, F)) * 0.05).astype(dtype)
    wo = (jax.random.normal(ks[2], (F, D)) * 0.05).astype(dtype)
    wg = (jax.random.normal(ks[3], (D, F)) * 0.05).astype(dtype) if gated else None
    tw = jax.random.uniform(ks[4], (T,))
    got = fused_mlp(x, wi, wo, wg, tw, act=act, interpret=True)
    want = ref.fused_mlp_ref(x, wi, wo, wg, tw, act=act)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOLS[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("E,C,D,Fe,gated", [
    (4, 128, 128, 256, True),
    (8, 96, 64, 128, False),     # ragged C
    (2, 256, 128, 512, True),
    (2, 64, 128, 296, True),     # partial last Fe tile (256 + 40)
])
def test_moe_gmm_sweep(E, C, D, Fe, gated, dtype, key):
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (E, C, D), dtype)
    wi = (jax.random.normal(ks[1], (E, D, Fe)) * 0.05).astype(dtype)
    wo = (jax.random.normal(ks[2], (E, Fe, D)) * 0.05).astype(dtype)
    wg = (jax.random.normal(ks[3], (E, D, Fe)) * 0.05).astype(dtype) if gated else None
    w = jax.random.uniform(ks[4], (E, C))
    got = moe_gmm(x, wi, wo, wg, w, act="swiglu", interpret=True)
    want = ref.moe_gmm_ref(x, wi, wo, wg, w, act="swiglu")
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOLS[dtype])


@pytest.mark.parametrize("count", [1, 100, 130, 256])
def test_flash_attention_kv_count_ragged(count, key):
    """Traced valid-token count: keys/queries past it are skipped/zeroed."""
    B, S, H, K, Dh = 2, 256, 4, 2, 64
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, Dh))
    k = jax.random.normal(ks[1], (B, S, K, Dh))
    v = jax.random.normal(ks[2], (B, S, K, Dh))
    got = flash_attention(q, k, v, causal=True, kv_count=jnp.int32(count),
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, kv_count=count)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-5)
    assert not np.asarray(got[:, count:]).any(), "tail rows must be zero"
    # the count is a hard prefix: it must equal full attention on the prefix
    full = ref.flash_attention_ref(q[:, :count], k[:, :count], v[:, :count],
                                   causal=True)
    np.testing.assert_allclose(np.asarray(got[:, :count], np.float32),
                               np.asarray(full, np.float32), atol=2e-5)


def test_flash_attention_per_row_kv_count(key):
    """(B,) counts: every batch row is cut at its own prefix length."""
    B, S, H, K, Dh = 3, 256, 4, 4, 32
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, Dh))
    k = jax.random.normal(ks[1], (B, S, K, Dh))
    v = jax.random.normal(ks[2], (B, S, K, Dh))
    cnt = jnp.asarray([7, 130, 256], jnp.int32)
    got = flash_attention(q, k, v, causal=True, window=96, kv_count=cnt,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=96,
                                   kv_count=cnt)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-5)


@pytest.mark.parametrize("count", [1, 100, 256, 300])
def test_fused_mlp_valid_count_ragged(count, key):
    T, D, F = 300, 64, 256
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (T, D))
    wi = (jax.random.normal(ks[1], (D, F)) * 0.05)
    wo = (jax.random.normal(ks[2], (F, D)) * 0.05)
    wg = (jax.random.normal(ks[3], (D, F)) * 0.05)
    tw = jax.random.uniform(ks[4], (T,))
    got = fused_mlp(x, wi, wo, wg, tw, act="swiglu",
                    valid_count=jnp.int32(count), interpret=True)
    want = ref.fused_mlp_ref(x, wi, wo, wg, tw, act="swiglu",
                             valid_count=count)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    assert not np.asarray(got[count:]).any()


def test_moe_gmm_group_counts_ragged(key):
    """(E,) per-expert occupancy: capacity slots past it are zeroed."""
    E, C, D, Fe = 4, 128, 64, 128
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (E, C, D))
    wi = (jax.random.normal(ks[1], (E, D, Fe)) * 0.05)
    wo = (jax.random.normal(ks[2], (E, Fe, D)) * 0.05)
    w = jax.random.uniform(ks[4], (E, C))
    cnt = jnp.asarray([0, 5, 100, 128], jnp.int32)
    got = moe_gmm(x, wi, wo, None, w, act="gelu", group_counts=cnt,
                  interpret=True)
    want = ref.moe_gmm_ref(x, wi, wo, None, w, act="gelu", group_counts=cnt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    for e in range(E):
        assert not np.asarray(got[e, int(cnt[e]):]).any()


def test_fused_mlp_batched_per_row_counts(key):
    """(B, T, D) input with per-row (B,) valid counts: each batch row is
    cut at its own ragged prefix."""
    B, T, D, F = 3, 128, 64, 192
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, T, D))
    wi = jax.random.normal(ks[1], (D, F)) * 0.05
    wo = jax.random.normal(ks[2], (F, D)) * 0.05
    wg = jax.random.normal(ks[3], (D, F)) * 0.05
    tw = jax.random.uniform(ks[4], (B, T))
    cnt = jnp.asarray([1, 70, 128], jnp.int32)
    got = fused_mlp(x, wi, wo, wg, tw, act="swiglu", valid_count=cnt,
                    interpret=True)
    want = ref.fused_mlp_ref(x, wi, wo, wg, tw, act="swiglu",
                             valid_count=cnt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    for b in range(B):
        assert not np.asarray(got[b, int(cnt[b]):]).any()


@pytest.mark.parametrize("gated", [True, False])
def test_plan_gather_fused_mlp_scatter(gated, key):
    """The routed MLP as the model runs it: a RoutingPlan gathers the
    selected rows into a bucket buffer, fused_mlp runs on its valid
    prefix (per-row counts), and the weighted outputs scatter back to
    their token positions; rows the plan dropped stay exactly zero."""
    from repro.core import routing as R
    B, S, D, F = 2, 96, 64, 128
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, S, D))
    wi = jax.random.normal(ks[1], (D, F)) * 0.05
    wo = jax.random.normal(ks[2], (F, D)) * 0.05
    wg = (jax.random.normal(ks[3], (D, F)) * 0.05) if gated else None
    scores = jax.random.uniform(ks[4], (B, S))
    plan = R.make_plan(scores, jnp.asarray([24, 10], jnp.int32), 32)
    w = jnp.take_along_axis(scores, plan.idx, 1) * plan.valid
    y = fused_mlp(R.plan_gather(x, plan), wi, wo, wg, act="swiglu",
                  valid_count=plan.count, interpret=True)
    got = R.plan_scatter(plan, x, y * w[..., None])
    want = jnp.where(plan.keep[..., None],
                     ref.fused_mlp_ref(x, wi, wo, wg, scores, act="swiglu"),
                     0.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    assert not np.asarray(got)[~np.asarray(plan.keep)].any()
    assert np.asarray(plan.keep).sum(-1).tolist() == [24, 10]


def test_moe_gmm_batched_group_counts(key):
    """(B, E, C, D) dispatch buffers with (B, E) per-expert occupancy."""
    B, E, C, D, Fe = 2, 4, 64, 32, 96
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, E, C, D))
    wi = jax.random.normal(ks[1], (E, D, Fe)) * 0.05
    wo = jax.random.normal(ks[2], (E, Fe, D)) * 0.05
    wg = jax.random.normal(ks[3], (E, D, Fe)) * 0.05
    w = jax.random.uniform(ks[4], (B, E, C))
    cnt = jnp.asarray([[0, 5, 33, 64], [64, 1, 0, 17]], jnp.int32)
    got = moe_gmm(x, wi, wo, wg, w, act="swiglu", group_counts=cnt,
                  interpret=True)
    want = ref.moe_gmm_ref(x, wi, wo, wg, w, act="swiglu", group_counts=cnt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    for b in range(B):
        for e in range(E):
            assert not np.asarray(got[b, e, int(cnt[b, e]):]).any()


@pytest.mark.parametrize("window,block_k", [
    (0, 128), (24, 128),
    # block_k < L: exercises the cross-block online-softmax carry,
    # including blocks an aggressive window masks out ENTIRELY (their
    # poisoned p=1 contributions must be annihilated by the alpha rescale)
    (0, 16), (8, 16),
])
def test_decode_attention_ring_cache(window, block_k, key):
    """Ring-cache decode kernel vs the jnp oracle: staggered per-slot
    positions, wrapped ring slots, empty (-1) and invalid entries."""
    B, L, H, K, Dh = 3, 64, 4, 2, 32
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (B, 1, H, Dh))
    k = jax.random.normal(ks[1], (B, L, K, Dh))
    v = jax.random.normal(ks[2], (B, L, K, Dh))
    t = jnp.asarray([5, 63, 150], jnp.int32)       # row 2 wrapped the ring
    slots = jnp.arange(L)[None, :]
    pos = jnp.where(slots <= t[:, None] % L, t[:, None] - t[:, None] % L,
                    t[:, None] - t[:, None] % L - L) + slots
    pos = jnp.where(pos >= 0, pos, -1).astype(jnp.int32)
    valid = jax.random.bernoulli(ks[3], 0.85, (B, L))
    got = decode_attention(q, k, v, pos, t, window=window, kv_valid=valid,
                           block_k=block_k, interpret=True)
    want = ref.decode_attention_ref(q, k, v, pos, t, window=window,
                                    kv_valid=valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_matches_model_blocked_sdpa(key):
    """The Pallas kernel, the blocked jnp path, and the dense path agree."""
    from repro.models.attention import blocked_sdpa, sdpa, _mask
    B, S, H, K, Dh = 1, 256, 4, 2, 64
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, Dh))
    k = jax.random.normal(ks[1], (B, S, K, Dh))
    v = jax.random.normal(ks[2], (B, S, K, Dh))
    pos = jnp.arange(S)
    dense = sdpa(q, k, v, _mask(pos, pos, True, 0))
    blocked = blocked_sdpa(q, k, v, pos[None], pos[None], True, 0, block=64)
    kernel = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(blocked),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(kernel),
                               atol=2e-5)
