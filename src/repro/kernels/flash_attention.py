"""Pallas TPU flash attention (forward) with causal + sliding-window masks,
GQA, and a per-key validity mask (ElastiFormer token routing: unselected
tokens are invalid keys).

Layout: q (B, H, Sq, Dh), k/v (B, K, Sk, Dh) — heads-major so each grid cell
streams contiguous (block, Dh) tiles HBM->VMEM. Online softmax with f32
scratch accumulators carried across the innermost (sequential) kv-block grid
dimension; causal/window-dead blocks are skipped via pl.when so the lowered
kernel does ~half the work of the dense score matrix.

Ragged capacity-bucket execution: ``kv_count`` (scalar or per-row (B,),
scalar-prefetched) marks the first N tokens of the q/kv buffers as real —
kv blocks entirely past the count are skipped, q blocks past it write zeros
without computing, and the straddling block masks per-position. A
bucket-sized compile therefore does work quadratic in the *count*, not the
buffer. The ragged token-routing gather (core/routing.make_plan — the
block-shared RoutingPlan whose traced count IS this kernel's ``kv_count``)
keeps selected tokens position-ascending in the prefix, so array-index
causal masking inside the kernel IS causal masking over the selected
tokens. The model hot path reaches this kernel through kernels/ops.py
under ``ElasticSpec.kernel_backend`` ("pallas" on TPU, "interpret" for CPU
verification); sliding-window masking is index-based, so windowed GATHERED
attention stays on the jnp twin (models/attention._kernel_ok).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def analysis_example():
    """Representative call for the static kernel verifier
    (``repro.analysis.pallas_lint``): production-shaped tiles (Dh = 128,
    MXU-aligned 128-blocks), a ragged per-row count, GQA 2:1, both masks.
    Returns ``(fn, args, kwargs)``; the verifier intercepts the inner
    ``pallas_call`` and statically evaluates its grid x BlockSpec
    index_maps — the call itself never executes."""
    import numpy as np
    B, Sq, H, K, Dh = 2, 256, 4, 2, 128
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, Sq, H, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Sq, K, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Sq, K, Dh)), jnp.float32)
    valid = jnp.asarray(rng.integers(0, 2, size=(B, Sq)), bool)
    cnt = jnp.asarray([Sq, 160], jnp.int32)
    return (flash_attention, (q, k, v),
            dict(causal=True, kv_valid=valid, kv_count=cnt, interpret=True))


def _kernel(cnt_ref, valid_ref, q_ref, k_ref, v_ref, o_ref, m_sc, l_sc,
            acc_sc, *, causal: bool, window: int, block_q: int, block_k: int,
            sm_scale: float, n_kb: int, sk: int):
    ib = pl.program_id(0)
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    cnt = cnt_ref[ib]

    @pl.when(ik == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q_start = iq * block_q
    k_start = ik * block_k
    run = q_start < cnt                # q block fully past the valid prefix
    run &= k_start < cnt               # kv block fully past the valid prefix
    if causal:  # skip blocks entirely above the diagonal
        run &= k_start <= q_start + block_q - 1
    if window and window > 0:  # skip blocks entirely outside the window
        run &= q_start - (k_start + block_k - 1) < window

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                                  # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = (kpos < sk) & (kpos < cnt)
        if causal:
            mask &= kpos <= qpos
        if window and window > 0:
            mask &= (qpos - kpos) < window
        if valid_ref is not None:
            mask &= valid_ref[0] > 0                      # (1, bk) row
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_sc[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_sc[:, 0] = l_sc[:, 0] * alpha + jnp.sum(p, axis=1)
        m_sc[:, 0] = m_new
        v = v_ref[0, 0].astype(jnp.float32)
        # Rows past Sk / the valid count are block padding (NaN in interpret
        # mode); p is 0 there but 0*NaN = NaN in the dot, so zero them.
        vpos = k_start + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        v = jnp.where((vpos < sk) & (vpos < cnt), v, 0.0)
        acc_sc[...] = acc_sc[...] * alpha[:, None] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)

    @pl.when(ik == n_kb - 1)
    def _finish():
        l = jnp.maximum(l_sc[:, 0], 1e-30)
        y = acc_sc[...] / l[:, None]
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
        y = jnp.where(rows < cnt, y, 0.0)
        o_ref[0, 0] = y.astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    kv_valid=None, block_q: int = 128, block_k: int = 128,
                    sm_scale: float | None = None, kv_count=None,
                    interpret: bool = False):
    """q: (B, Sq, H, Dh); k, v: (B, Sk, K, Dh); kv_valid: (B, Sk) bool;
    kv_count: scalar or (B,) count of real leading tokens (None = Sk) —
    keys/queries past the count are skipped/zeroed (ragged bucket buffers).
    Returns (B, Sq, H, Dh)."""
    B, Sq, H, Dh = q.shape
    Sk, K = k.shape[1], k.shape[2]
    G = H // K
    sm_scale = Dh ** -0.5 if sm_scale is None else sm_scale
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    nq, nkb = pl.cdiv(Sq, bq), pl.cdiv(Sk, bk)
    # default count caps nothing (kv padding is already masked via `sk`,
    # and q rows past Sk are legal when Sq > Sk)
    full = max(Sq, Sk)
    cnt = jnp.clip(jnp.asarray(
        full if kv_count is None else kv_count, jnp.int32), 0, full)
    cnt = jnp.broadcast_to(cnt.reshape(-1), (B,))

    qt = q.transpose(0, 2, 1, 3)                          # (B,H,Sq,Dh)
    kt = k.transpose(0, 2, 1, 3)                          # (B,K,Sk,Dh)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _kernel, causal=causal, window=window, block_q=bq, block_k=bk,
        sm_scale=sm_scale, n_kb=nkb, sk=Sk)
    in_specs = [
        pl.BlockSpec((1, 1, bq, Dh), lambda b, h, i, j, *_: (b, h, i, 0)),
        pl.BlockSpec((1, 1, bk, Dh), lambda b, h, i, j, *_: (b, h // G, j, 0)),
        pl.BlockSpec((1, 1, bk, Dh), lambda b, h, i, j, *_: (b, h // G, j, 0)),
    ]
    args = [qt, kt, vt]
    if kv_valid is not None:
        # (B, 1, Sk) rows: the block's last two dims are (1 == full axis,
        # bk) — the TPU tiling rule for (8, 128) blocks
        in_specs.insert(0, pl.BlockSpec((1, 1, bk),
                                        lambda b, h, i, j, *_: (b, 0, j)))
        args.insert(0, kv_valid.astype(jnp.int32)[:, None, :])
        kfn = kernel
    else:
        kfn = lambda cnt_ref, *rest: kernel(cnt_ref, None, *rest)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, nq, nkb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, bq, Dh),
                               lambda b, h, i, j, *_: (b, h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, Dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kfn,
        name="flash_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, Dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(cnt, *args)
    return out.transpose(0, 2, 1, 3)
