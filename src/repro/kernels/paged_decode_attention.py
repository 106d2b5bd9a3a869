"""Pallas TPU decode attention over the PAGED KV pool — the one-token
hot path when the serving engine runs the block-paged cache
(``runtime/pagedkv.py``).

Unlike the ring kernel there is no per-slot (B, L) cache: K/V live in a
global page pool of shape (N, page_size, K, Dh) and slot ``b`` owns the
pages named by its page-table row ``table[b]`` (int32, -1 = unused).
Positions are implicit in the table layout — table entry ``p`` of a row
holds absolute positions ``[p * page_size, (p+1) * page_size)`` — so the
kernel needs no position array: a key at page-entry ``p``, lane ``j`` is
attendable iff

    table[b, p] >= 0                      (entry backed by a page)
    p * page_size + j <= t[b]             (causal at this slot's position)
    pvalid[table[b, p], j]                (ElastiFormer token routing:
                                           skipped tokens hold no KV)

The page table and per-slot lengths ride scalar prefetch and the K/V
BlockSpec index_map gathers pages straight from the pool (index
prefetch), with ``max(entry, 0)``
keeping unused entries in bounds (their lanes are masked). One
(B, H, table_len) grid with the online-softmax f32 accumulator carried
across the page dimension, GQA via the head-major index map; the jnp
oracle is ``kernels/ref.py::paged_decode_attention_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def analysis_example():
    """Representative paged-pool decode call for the static kernel
    verifier: a pool with free pages, table rows with -1 holes, per-slot
    offsets riding scalar prefetch, GQA 2:1."""
    import numpy as np
    B, N, ps, H, K, Dh = 2, 8, 16, 4, 2, 128
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.normal(size=(B, 1, H, Dh)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(N, ps, K, Dh)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(N, ps, K, Dh)), jnp.float32)
    table = np.full((B, 3), -1, np.int32)
    table[0, :2] = [4, 1]                 # 2 pages, mid-page offset
    table[1, :3] = [0, 6, 2]              # 3 pages, page-boundary offset
    t = jnp.asarray([20, 47], jnp.int32)
    pvalid = jnp.asarray(rng.integers(0, 2, size=(N, ps)), bool)
    return (paged_decode_attention,
            (q, kp, vp, jnp.asarray(table), t, pvalid),
            dict(interpret=True))


def _kernel(tbl_ref, t_ref, q_ref, k_ref, v_ref, pv_ref, ks_ref, vs_ref,
            o_ref, m_sc, l_sc, acc_sc, *, page_size: int, sm_scale: float,
            n_pb: int):
    ib = pl.program_id(0)
    ip = pl.program_id(2)
    t = t_ref[ib]
    entry = tbl_ref[ib, ip]

    @pl.when(ip == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0, 0].astype(jnp.float32)                   # (1, d)
    k = k_ref[0, 0].astype(jnp.float32)                   # (ps, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * sm_scale                                      # (1, ps)
    if ks_ref is not None:
        # int8 pool: each key lane's per-(lane, kv-head) f32 scale folds
        # into its score column — HBM only ever saw the int8 page
        # (docs/quantization.md)
        s = s * ks_ref[0, 0]
    pos = ip * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)                     # (1, ps)
    mask = (entry >= 0) & (pos <= t) & (pv_ref[0] > 0)
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_sc[:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    # masked keys get probability exactly 0 — also in a block where every
    # key is masked (there s - m_new == 0), so a row with no attendable
    # key keeps l == 0 and finishes as exact zeros
    p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
    l_sc[:, 0] = l_sc[:, 0] * alpha + jnp.sum(p, axis=1)
    m_sc[:, 0] = m_new
    v = v_ref[0, 0].astype(jnp.float32)
    if vs_ref is not None:
        p = p * vs_ref[0, 0]       # value-row scales fold into p's columns
    acc_sc[...] = acc_sc[...] * alpha[:, None] + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)

    @pl.when(ip == n_pb - 1)
    def _finish():
        l = jnp.maximum(l_sc[:, 0], 1e-30)
        o_ref[0, 0] = (acc_sc[...] / l[:, None]).astype(o_ref.dtype)


def paged_decode_attention(q, kp, vp, table, t, pvalid, *, kscale=None,
                           vscale=None, sm_scale: float | None = None,
                           interpret: bool = False):
    """q: (B, 1, H, Dh); kp, vp: (N, page_size, K, Dh) global page pool;
    table: (B, P) i32 page-table rows (-1 = unused entry); t: (B,) i32
    per-slot decode positions; pvalid: (N, page_size) bool per-lane
    routing validity; kscale/vscale: (N, page_size, K) f32 per-(lane,
    kv-head) dequant scale pools when kp/vp are int8 (both or neither).
    Returns (B, 1, H, Dh)."""
    B, Sq, H, Dh = q.shape
    N, ps, K = kp.shape[0], kp.shape[1], kp.shape[2]
    P = table.shape[1]
    G = H // K
    quantized = kscale is not None
    sm_scale = Dh ** -0.5 if sm_scale is None else sm_scale
    table = jnp.asarray(table, jnp.int32)
    t = jnp.broadcast_to(jnp.asarray(t, jnp.int32).reshape(-1), (B,))

    kt = kp.transpose(2, 0, 1, 3)                         # (K, N, ps, Dh)
    vt = vp.transpose(2, 0, 1, 3)
    qt = q.transpose(0, 2, 1, 3)                          # (B, H, 1, Dh)

    kernel = functools.partial(_kernel, page_size=ps, sm_scale=sm_scale,
                               n_pb=P)
    # unused entries (-1) clamp to page 0 for the DMA; their lanes are
    # masked in-kernel by the entry >= 0 test
    page_im = lambda b, h, p, tbl, tt: \
        (h // G, jnp.maximum(tbl[b, p], 0), 0, 0)
    # per-lane pools ride with a unit axis before the lane axis, so every
    # block's last two dims are (1 == full axis, ps == full axis) — the
    # TPU tiling rule for (8, 128) blocks
    in_specs = [
        pl.BlockSpec((1, 1, 1, Dh),
                     lambda b, h, p, tbl, tt: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, ps, Dh), page_im),
        pl.BlockSpec((1, 1, ps, Dh), page_im),
        pl.BlockSpec((1, 1, ps),
                     lambda b, h, p, tbl, tt:
                     (jnp.maximum(tbl[b, p], 0), 0, 0)),
    ]
    args = [qt, kt, vt, pvalid.astype(jnp.int32)[:, None, :]]
    if quantized:
        # scale pool rides head-major like the KV pool, (K, N, 1, ps),
        # gathered by the same page-table index map
        sspec = pl.BlockSpec((1, 1, 1, ps), page_im)
        in_specs += [sspec, sspec]
        args += [kscale.astype(jnp.float32).transpose(2, 0, 1)[:, :, None],
                 vscale.astype(jnp.float32).transpose(2, 0, 1)[:, :, None]]
        kfn = kernel
    else:
        kfn = lambda tbl_ref, t_ref, q_ref, k_ref, v_ref, pv_ref, *rest: \
            kernel(tbl_ref, t_ref, q_ref, k_ref, v_ref, pv_ref, None, None,
                   *rest)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H, P),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, 1, Dh),
                               lambda b, h, p, tbl, tt: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, LANES), jnp.float32),
            pltpu.VMEM((1, LANES), jnp.float32),
            pltpu.VMEM((1, Dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kfn,
        name="paged_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, Dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(table, t, *args)
    return out.transpose(0, 2, 1, 3)
