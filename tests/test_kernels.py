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
from repro.kernels.paged_decode_attention import paged_decode_attention

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


def _paged_sweep_rows(P, ps):
    """(t, table row) per slot, one case a row; page ids are placeholders
    (letters) bound to pool pages by the caller."""
    full = [f"f{i}" for i in range(P)]
    return [
        # a long table with few live pages; entries past t name pages, as
        # the engine's pre-allocation for the next writes leaves them
        (ps + 4, ["a0", "a1", "a2", "a3"]),
        (5 * ps + 7, [f"b{i}" for i in range(6)]),          # t mid-page
        (3 * ps + ps - 1, [f"c{i}" for i in range(5)]),     # last lane
        (0, ["d0", "d1"]),                                  # t = 0
        (P * ps - 1, full),                                 # a full row
        # -1 holes inside the live range (11 entries: no whole number of
        # blocks of 8 pages either)
        (10 * ps + 3, ["e0", "e1", -1, "e3", "e4", "e5", "e6", -1, "e8",
                       "e9", "e10", "e11"]),
        # shares its first two pages with the mid-page row
        (2 * ps + 9, ["b0", "b1", "g2", "g3"]),
        (ps + 2, ["z0", "z1", "z2"]),    # every live lane routed out
    ]


@pytest.mark.parametrize("H,K", [(28, 4), (4, 2)])       # GQA 7:1, 2:1
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_decode_attention_sweep(H, K, kv):
    """Paged decode kernel vs the jnp oracle over per-slot cases (see
    _paged_sweep_rows), with pvalid holes throughout. Every pool page no
    live entry references (entries past t, the page -1 entries clamp to)
    holds NaN — in the pages themselves for bf16, in the scale pools for
    int8: the kernel must neither read them nor let them leak, so its
    output is finite and equals the oracle on the pool with those pages
    zeroed."""
    ps, P, Dh = 16, 24, 128
    rows = _paged_sweep_rows(P, ps)
    B = len(rows)
    names = sorted({e for _, r in rows for e in r if e != -1},
                   key=lambda s: (s[0], int(s[1:])))
    rng = np.random.default_rng(7)
    N = len(names) + 2
    ids = dict(zip(names, rng.permutation(N)[:len(names)].tolist()))
    table = np.full((B, P), -1, np.int32)
    for b, (_, r) in enumerate(rows):
        table[b, :len(r)] = [ids.get(e, -1) for e in r]
    t = np.asarray([tt for tt, _ in rows], np.int32)
    live = np.zeros(N, bool)
    for b in range(B):
        e = table[b, :t[b] // ps + 1]
        live[e[e >= 0]] = True
    pvalid = rng.random((N, ps)) < 0.8
    pvalid[ids["z0"]] = pvalid[ids["z1"]] = False
    q = jnp.asarray(rng.normal(size=(B, 1, H, Dh)),
                    jnp.bfloat16 if kv == "bf16" else jnp.float32)
    dead = ~live[:, None, None, None]
    if kv == "bf16":
        kp, vp = (rng.normal(size=(N, ps, K, Dh)) for _ in range(2))
        scales = {}
        clean = dict(kp=np.where(dead, 0.0, kp), vp=np.where(dead, 0.0, vp))
        kp, vp = np.where(dead, np.nan, kp), np.where(dead, np.nan, vp)
        kp, vp = (jnp.asarray(x, jnp.bfloat16) for x in (kp, vp))
        clean = {k: jnp.asarray(v, jnp.bfloat16) for k, v in clean.items()}
    else:
        kp, vp = (jnp.asarray(rng.integers(-127, 128, (N, ps, K, Dh)),
                              jnp.int8) for _ in range(2))
        ks, vs = (rng.uniform(0.005, 0.02, (N, ps, K)) for _ in range(2))
        clean = dict(kp=kp, vp=vp,
                     kscale=jnp.asarray(np.where(dead[..., 0], 0.0, ks),
                                        jnp.float32),
                     vscale=jnp.asarray(np.where(dead[..., 0], 0.0, vs),
                                        jnp.float32))
        scales = dict(kscale=jnp.asarray(np.where(dead[..., 0], np.nan, ks),
                                         jnp.float32),
                      vscale=jnp.asarray(np.where(dead[..., 0], np.nan, vs),
                                         jnp.float32))
    table, t, pvalid = (jnp.asarray(x) for x in (table, t, pvalid))
    got = paged_decode_attention(q, kp, vp, table, t, pvalid, **scales,
                                 interpret=True)
    want = ref.paged_decode_attention_ref(
        q, clean["kp"], clean["vp"], table, t, pvalid,
        kscale=clean.get("kscale"), vscale=clean.get("vscale"))
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    assert (got[-1] == 0).all()            # no attendable key: exact zeros
    tol = TOLS[jnp.bfloat16 if kv == "bf16" else jnp.float32]
    np.testing.assert_allclose(got, want, **tol)
