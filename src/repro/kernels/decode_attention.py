"""Pallas TPU decode attention over the serving engine's RING KV cache —
the one-token-per-slot hot path of the continuous-batching decode step.

Unlike prefill flash attention, the ring cache is NOT position-ordered:
entry for absolute position p lives at slot p % L, empty slots carry
pos == -1, and every serving slot decodes at its own offset t[b]. So the
kernel masks by the cache's absolute-position array instead of by array
index: a key at slot j is attendable iff

    kv_pos[b, j] >= 0            (slot ever written)
    kv_pos[b, j] <= t[b]         (causal at this slot's position)
    t[b] - kv_pos[b, j] < window (sliding window, if any)
    kv_valid[b, j]               (ElastiFormer token routing: skipped
                                  tokens never entered the cache)

Per-slot positions ride scalar prefetch; one (B, H, L/block) grid with an
online-softmax f32 accumulator carried across the kv-block dimension, GQA
via the head-major block index map — the decode twin of
kernels/flash_attention.py, shaped for Sq == 1.
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
    """Representative ring-cache decode call for the static kernel
    verifier: partially-filled ring (pos == -1 holes), per-slot offsets
    riding scalar prefetch, GQA 2:1."""
    import numpy as np
    B, L, H, K, Dh = 2, 256, 4, 2, 128
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.normal(size=(B, 1, H, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, L, K, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, L, K, Dh)), jnp.float32)
    pos = np.full((B, L), -1, np.int32)
    pos[0, :40] = np.arange(40)
    pos[1, :200] = np.arange(200)
    t = jnp.asarray([39, 199], jnp.int32)
    valid = jnp.asarray(rng.integers(0, 2, size=(B, L)), bool)
    return (decode_attention, (q, k, v, jnp.asarray(pos), t),
            dict(kv_valid=valid, interpret=True))


def _kernel(t_ref, q_ref, k_ref, v_ref, pos_ref, valid_ref, ks_ref, vs_ref,
            o_ref, m_sc, l_sc, acc_sc, *, window: int, sm_scale: float,
            n_kb: int):
    ib = pl.program_id(0)
    ik = pl.program_id(2)
    t = t_ref[ib]

    @pl.when(ik == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0, 0].astype(jnp.float32)                  # (1, d)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * sm_scale                                      # (1, bk)
    if ks_ref is not None:
        # int8 cache: each key row's per-(slot, kv-head) f32 scale folds
        # into its score column, so the widened tile needs no per-row
        # broadcast — HBM only ever saw the int8 tile (docs/quantization.md)
        s = s * ks_ref[0, 0]
    pos = pos_ref[0]                                      # (1, bk) i32
    mask = (pos >= 0) & (pos <= t)
    if window and window > 0:
        mask &= (t - pos) < window
    if valid_ref is not None:
        mask &= valid_ref[0] > 0
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

    @pl.when(ik == n_kb - 1)
    def _finish():
        l = jnp.maximum(l_sc[:, 0], 1e-30)
        o_ref[0, 0] = (acc_sc[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention(q, k, v, kv_pos, t, *, window: int = 0, kv_valid=None,
                     kscale=None, vscale=None, block_k: int = 128,
                     sm_scale: float | None = None,
                     interpret: bool = False):
    """q: (B, 1, H, Dh); k, v: (B, L, K, Dh) ring caches; kv_pos: (B, L)
    i32 absolute positions (-1 = empty slot); t: (B,) i32 per-slot decode
    positions; kv_valid: (B, L) bool (routing validity); kscale/vscale:
    (B, L, K) f32 per-(slot, kv-head) dequant scales when k/v are int8
    (both or neither). Returns (B, 1, H, Dh)."""
    B, Sq, H, Dh = q.shape
    L, K = k.shape[1], k.shape[2]
    G = H // K
    quantized = kscale is not None
    sm_scale = Dh ** -0.5 if sm_scale is None else sm_scale
    bk = min(block_k, L)
    nkb = pl.cdiv(L, bk)
    t = jnp.broadcast_to(jnp.asarray(t, jnp.int32).reshape(-1), (B,))
    # pad slots carry pos == -1 -> masked, so block padding is inert
    pos = kv_pos.astype(jnp.int32)
    if nkb * bk != L:
        pad = nkb * bk - L
        pos = jnp.pad(pos, [(0, 0), (0, pad)], constant_values=-1)
        padw = [(0, 0), (0, pad), (0, 0), (0, 0)]
        k, v = jnp.pad(k, padw), jnp.pad(v, padw)
        if kv_valid is not None:
            kv_valid = jnp.pad(kv_valid, [(0, 0), (0, pad)])
        if quantized:
            kscale = jnp.pad(kscale, [(0, 0), (0, pad), (0, 0)])
            vscale = jnp.pad(vscale, [(0, 0), (0, pad), (0, 0)])

    qt = q.transpose(0, 2, 1, 3)                          # (B,H,1,Dh)
    kt = k.transpose(0, 2, 1, 3)                          # (B,K,L,Dh)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(_kernel, window=window, sm_scale=sm_scale,
                               n_kb=nkb)
    # per-slot rows ride as (B, 1, L) so every block's last two dims are
    # (1 == full axis, bk) — the TPU tiling rule for (8, 128) blocks
    row_spec = pl.BlockSpec((1, 1, bk), lambda b, h, j, *_: (b, 0, j))
    in_specs = [
        pl.BlockSpec((1, 1, 1, Dh), lambda b, h, j, *_: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, bk, Dh), lambda b, h, j, *_: (b, h // G, j, 0)),
        pl.BlockSpec((1, 1, bk, Dh), lambda b, h, j, *_: (b, h // G, j, 0)),
        row_spec,
    ]
    args = [qt, kt, vt, pos[:, None, :]]
    have_valid = kv_valid is not None
    if have_valid:
        in_specs.append(row_spec)
        args.append(kv_valid.astype(jnp.int32)[:, None, :])
    if quantized:
        # scales ride as regular VMEM blocks, head-major like k/v:
        # (B, K, 1, L) rows, one (1, bk) lane block per kv-head
        sspec = pl.BlockSpec((1, 1, 1, bk),
                             lambda b, h, j, *_: (b, h // G, 0, j))
        in_specs += [sspec, sspec]
        args += [kscale.astype(jnp.float32).transpose(0, 2, 1)[:, :, None],
                 vscale.astype(jnp.float32).transpose(0, 2, 1)[:, :, None]]

    def kfn(t_ref, q_ref, k_ref, v_ref, pos_ref, *rest):
        rs = list(rest)
        valid_ref = rs.pop(0) if have_valid else None
        ks_ref = rs.pop(0) if quantized else None
        vs_ref = rs.pop(0) if quantized else None
        return kernel(t_ref, q_ref, k_ref, v_ref, pos_ref, valid_ref,
                      ks_ref, vs_ref, *rs)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, nkb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, 1, Dh), lambda b, h, j, *_: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, LANES), jnp.float32),
            pltpu.VMEM((1, LANES), jnp.float32),
            pltpu.VMEM((1, Dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kfn,
        name="decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, Dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(t, *args)
    return out.transpose(0, 2, 1, 3)
