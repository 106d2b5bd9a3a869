"""Pallas TPU grouped expert matmul — the compute hot-spot of ElastiFormer's
*parameter subset selection* (expert routing over moefied dense MLPs and
native MoE layers).

Inputs are the capacity-dispatched per-expert token buffers produced by the
router (see models/moe.py):

    y[e, c] = w[e, c] * ( act(x[e,c] @ Wg[e]) * (x[e,c] @ Wi[e]) ) @ Wo[e]

Grid (E, C/bc, Fe/bf): expert-major so each expert's weight tiles are
streamed once per token-block column; the hidden activation is fused in VMEM
exactly like fused_mlp. Routing weights multiply the output (straight-through
gradient path of Alg. 1).

Ragged capacity-bucket execution: ``group_counts`` (an (E,) scalar-prefetched
vector of per-expert valid-slot counts) lets a single bucket-sized compile
skip every token tile past an expert's true occupancy (`pl.when` on tile
index vs count) — work proportional to dispatched tokens, not to capacity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_mlp import down_proj, ffn_hidden, vmem_limit


def analysis_example():
    """Representative ``moe_gmm`` call for the static kernel verifier:
    batched dispatch buffers, per-(row, expert) ragged occupancy."""
    import numpy as np
    B, E, C, D, Fe = 2, 2, 128, 128, 256
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(B, E, C, D)), jnp.float32)
    wi = jnp.asarray(rng.normal(size=(E, D, Fe)), jnp.float32)
    wg = jnp.asarray(rng.normal(size=(E, D, Fe)), jnp.float32)
    wo = jnp.asarray(rng.normal(size=(E, Fe, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(B, E, C)), jnp.float32)
    cnt = jnp.asarray([[C, 40], [96, 0]], jnp.int32)
    return (moe_gmm, (x, wi, wo, wg, w),
            dict(group_counts=cnt, interpret=True))


def _kernel(cnt_ref, x_ref, wi_ref, wg_ref, wo_ref, w_ref, wis_ref, wgs_ref,
            wos_ref, o_ref, acc_sc, *, act: str, n_fb: int, block_c: int,
            block_f: int, f_total: int):
    ib = pl.program_id(0)
    ie = pl.program_id(1)
    ic = pl.program_id(2)
    jf = pl.program_id(3)
    cnt = cnt_ref[ib, ie]
    live = ic * block_c < cnt
    # an expert width that is not a multiple of the tile (qwen2-7b moefied
    # 16 ways: 1184) leaves a partial last tile, masked inside
    f_left = (f_total - jf * block_f) if f_total % block_f else None

    @pl.when(jnp.logical_not(live) & (jf == n_fb - 1))
    def _dead():  # capacity tile past this expert's occupancy: zeros only
        o_ref[0, 0] = jnp.zeros_like(o_ref[0, 0])

    @pl.when(live)
    def _run():
        @pl.when(jf == 0)
        def _init():
            acc_sc[...] = jnp.zeros_like(acc_sc)

        x = x_ref[0, 0]                                        # (bc, D)
        at0 = lambda r: None if r is None else r[0]
        h = ffn_hidden(x, wi_ref[0], at0(wg_ref), at0(wis_ref),
                       at0(wgs_ref), act=act, f_left=f_left)
        acc_sc[...] += down_proj(h, wo_ref[0], x.dtype, f_left)

        @pl.when(jf == n_fb - 1)
        def _finish():
            y = acc_sc[...]
            if wos_ref is not None:    # wo's per-(expert, D-column) scale
                y = y * wos_ref[0]
            y = y * w_ref[0, 0].astype(jnp.float32)[:, :1]
            rows = ic * block_c + jax.lax.broadcasted_iota(
                jnp.int32, y.shape, 0)
            y = jnp.where(rows < cnt, y, 0.0)
            o_ref[0, 0] = y.astype(o_ref.dtype)


def moe_gmm(x, wi, wo, wg=None, weights=None, *, act: str = "swiglu",
            block_c: int = 128, block_f: int = 256, group_counts=None,
            wi_scale=None, wo_scale=None, wg_scale=None,
            interpret: bool = False):
    """x: (E, C, D) or batched (B, E, C, D) dispatched tokens; wi/wg:
    (E, D, Fe); wo: (E, Fe, D) — expert weights are shared across the batch
    dim; weights: (E, C) / (B, E, C) routing weights (0 for empty capacity
    slots); group_counts: (E,) / (B, E) per-expert count of real leading
    slots (None = C) — slots >= the count produce zeros and their tiles are
    skipped. wi_scale/wg_scale: (E, Fe) and wo_scale: (E, D) f32
    per-(expert, output-channel) dequant scales when the weights are int8.
    Returns x-shaped output."""
    squeeze = x.ndim == 3
    if squeeze:
        x = x[None]
        if weights is not None:
            weights = jnp.asarray(weights)[None]
        if group_counts is not None:
            group_counts = jnp.asarray(group_counts).reshape(1, -1)
    B, E, C, D = x.shape
    Fe = wi.shape[2]
    bc, bf = min(block_c, C), min(block_f, Fe)
    nc, nf = pl.cdiv(C, bc), pl.cdiv(Fe, bf)
    w = jnp.ones((B, E, C), jnp.float32) if weights is None else weights
    w = jnp.broadcast_to(w.astype(jnp.float32)[..., None], (B, E, C, 128))
    cnt = (jnp.full((B, E), C, jnp.int32) if group_counts is None
           else jnp.clip(jnp.asarray(group_counts, jnp.int32), 0, C))
    cnt = jnp.broadcast_to(cnt, (B, E))
    have_g = wg is not None
    qw = wi_scale is not None

    kernel = functools.partial(_kernel, act=act, n_fb=nf, block_c=bc,
                               block_f=bf, f_total=Fe)
    in_specs = [
        pl.BlockSpec((1, 1, bc, D), lambda b, e, i, j, *_: (b, e, i, 0)),
        pl.BlockSpec((1, D, bf), lambda b, e, i, j, *_: (e, 0, j)),
    ]
    args = [x, wi]
    if have_g:
        in_specs.append(
            pl.BlockSpec((1, D, bf), lambda b, e, i, j, *_: (e, 0, j)))
        args.append(wg)
    in_specs += [
        pl.BlockSpec((1, bf, D), lambda b, e, i, j, *_: (e, j, 0)),
        pl.BlockSpec((1, 1, bc, 128), lambda b, e, i, j, *_: (b, e, i, 0)),
    ]
    args += [wo, w]
    if qw:
        # per-(expert, output-channel) scale rows as (E,1,Fe)/(E,1,D) blocks
        fspec = pl.BlockSpec((1, 1, bf), lambda b, e, i, j, *_: (e, 0, j))
        dspec = pl.BlockSpec((1, 1, D), lambda b, e, i, j, *_: (e, 0, 0))
        in_specs.append(fspec)
        args.append(wi_scale.astype(jnp.float32).reshape(E, 1, Fe))
        if have_g:
            in_specs.append(fspec)
            args.append(wg_scale.astype(jnp.float32).reshape(E, 1, Fe))
        in_specs.append(dspec)
        args.append(wo_scale.astype(jnp.float32).reshape(E, 1, D))

    def kfn(cnt_ref, x_ref, *rest):
        rs = list(rest)
        wi_ref = rs.pop(0)
        wg_ref = rs.pop(0) if have_g else None
        wo_ref, w_ref = rs.pop(0), rs.pop(0)
        wis_ref = rs.pop(0) if qw else None
        wgs_ref = rs.pop(0) if (qw and have_g) else None
        wos_ref = rs.pop(0) if qw else None
        return kernel(cnt_ref, x_ref, wi_ref, wg_ref, wo_ref, w_ref,
                      wis_ref, wgs_ref, wos_ref, *rs)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, E, nc, nf),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, bc, D),
                               lambda b, e, i, j, *_: (b, e, i, 0)),
        scratch_shapes=[pltpu.VMEM((bc, D), jnp.float32)],
    )
    blocks = (2 * bc * D * x.dtype.itemsize           # x and out
              + (3 if have_g else 2) * D * bf * wi.dtype.itemsize
              + bc * 128 * 4)
    out = pl.pallas_call(
        kfn,
        name="moe_gmm",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, E, C, D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=vmem_limit(blocks, bc * D * 4)),
        interpret=interpret,
    )(cnt, *args)
    return out[0] if squeeze else out
