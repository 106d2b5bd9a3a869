"""Pallas TPU fused (gated) MLP with per-token output weighting — the compute
hot-spot of ElastiFormer's *input subset selection* (routed MLP).

y[t] = w[t] * ( act(x[t] @ Wg) * (x[t] @ Wi) ) @ Wo

Fusing both matmuls + activation means the (T, F) hidden activation never
round-trips to HBM (F is 3-4x D on the assigned archs); the kernel tiles
F into VMEM-sized blocks and accumulates the down-projection into an f32
scratch across the sequential F-grid dimension.

``fused_mlp``: x is a (T, D) or batched (B, T, D) buffer (the routed
capacity-bucket buffer a RoutingPlan gathered in XLA). ``valid_count``
(scalar or per-row (B,), scalar-prefetched) marks the first N rows as real
tokens — token tiles entirely past the count are skipped (zero write, no
matmuls), the straddling tile zeroes its trailing rows. A bucket-sized
compile therefore does work proportional to the *count*, not the buffer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def vmem_limit(block_bytes: int, scratch_bytes: int) -> int:
    """Scoped-VMEM request for a kernel whose pipelined blocks total
    ``block_bytes``: each block is double-buffered, the scratch is not,
    and 8 MiB is left for the body's temporaries (the (rows, block_f) f32
    hidden tiles, the masked down-projection tile). Full-D MLP tiles at
    published widths (D = 3584) need ~30 MiB, past the 16 MiB default
    scoped limit of a v5e core and well inside its 128 MiB of VMEM."""
    return 2 * block_bytes + scratch_bytes + (8 << 20)


def analysis_example():
    """Representative ``fused_mlp`` call for the static kernel verifier
    (see flash_attention.analysis_example): bucket-buffer layout, ragged
    per-row counts, gated act."""
    import numpy as np
    B, T, D, F = 2, 256, 128, 512
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(B, T, D)), jnp.float32)
    wi = jnp.asarray(rng.normal(size=(D, F)), jnp.float32)
    wg = jnp.asarray(rng.normal(size=(D, F)), jnp.float32)
    wo = jnp.asarray(rng.normal(size=(F, D)), jnp.float32)
    tw = jnp.asarray(rng.normal(size=(B, T)), jnp.float32)
    cnt = jnp.asarray([T, 100], jnp.int32)
    return (fused_mlp, (x, wi, wo, wg, tw),
            dict(valid_count=cnt, interpret=True))


def _proj(x, w, scale):
    """(rows, D) @ (D, bf) -> f32. Operands enter the MXU in x's dtype
    (bf16 on the serving path) with an f32 accumulator; an int8 tile is
    widened to that dtype in-register and its per-output-channel scale
    multiplies the product's columns — HBM only ever saw the int8 tile
    (docs/quantization.md)."""
    y = jax.lax.dot(x, w.astype(x.dtype), preferred_element_type=jnp.float32)
    return y if scale is None else y * scale


def ffn_hidden(x, wi, wg, wi_scale=None, wg_scale=None, *, act: str,
               f_left=None):
    """Hidden tile act(x @ Wg) * (x @ Wi), (rows, bf) f32, from one F tile
    of the up/gate weights ((1, bf) scale rows for int8 tiles). ``f_left``:
    the number of real F columns from this tile on (traced), set when F is
    not a multiple of the tile: columns past it are the block padding of a
    partial last tile, which holds garbage, and are zeroed."""
    hi = _proj(x, wi, wi_scale)
    if wg is not None:
        hg = _proj(x, wg, wg_scale)
        a = jax.nn.silu(hg) if act == "swiglu" else jax.nn.gelu(hg)
        h = a * hi
    else:
        h = jax.nn.gelu(hi) if act == "gelu" else jax.nn.silu(hi)
    if f_left is not None:
        cols = jax.lax.broadcasted_iota(jnp.int32, h.shape, 1)
        h = jnp.where(cols < f_left, h, 0.0)
    return h


def down_proj(h, wo, dtype, f_left=None):
    """(rows, bf) hidden @ (bf, D) wo tile -> f32, in ``dtype`` on the
    MXU. Rows of a partial last tile past ``f_left`` are block padding:
    they are zeroed, since 0 * garbage can still be NaN."""
    wo = wo.astype(dtype)
    if f_left is not None:
        rows = jax.lax.broadcasted_iota(jnp.int32, wo.shape, 0)
        wo = jnp.where(rows < f_left, wo, jnp.zeros_like(wo))
    return jax.lax.dot(h.astype(dtype), wo,
                       preferred_element_type=jnp.float32)


def _load(ref):
    return None if ref is None else ref[...]


def _kernel(cnt_ref, x_ref, wi_ref, wg_ref, wo_ref, tw_ref, wis_ref,
            wgs_ref, wos_ref, o_ref, acc_sc, *,
            act: str, n_fb: int, weighted: bool, block_t: int,
            block_f: int, f_total: int):
    ib = pl.program_id(0)
    it = pl.program_id(1)
    jf = pl.program_id(2)
    cnt = cnt_ref[ib]
    live = it * block_t < cnt
    f_left = (f_total - jf * block_f) if f_total % block_f else None

    @pl.when(jnp.logical_not(live) & (jf == n_fb - 1))
    def _dead():  # tile fully past the valid count: zero write, no compute
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live)
    def _run():
        @pl.when(jf == 0)
        def _init():
            acc_sc[...] = jnp.zeros_like(acc_sc)

        x = x_ref[0]                                           # (bt, D)
        h = ffn_hidden(x, wi_ref[...], _load(wg_ref), _load(wis_ref),
                       _load(wgs_ref), act=act, f_left=f_left)
        acc_sc[...] += down_proj(h, wo_ref[...], x.dtype, f_left)

        @pl.when(jf == n_fb - 1)
        def _finish():
            y = acc_sc[...]
            if wos_ref is not None:    # wo's per-D-column int8 scale
                y = y * wos_ref[...]
            if weighted:
                y = y * tw_ref[0].astype(jnp.float32)[:, :1]
            rows = it * block_t + jax.lax.broadcasted_iota(
                jnp.int32, y.shape, 0)
            y = jnp.where(rows < cnt, y, 0.0)
            o_ref[0] = y.astype(o_ref.dtype)


def fused_mlp(x, wi, wo, wg=None, token_weights=None, *, act: str = "swiglu",
              block_t: int = 256, block_f: int = 256, valid_count=None,
              wi_scale=None, wo_scale=None, wg_scale=None,
              interpret: bool = False):
    """x: (T, D) or (B, T, D); wi/wg: (D, F); wo: (F, D); token_weights:
    (T,) / (B, T) or None; valid_count: traced/static count of real leading
    rows — scalar or per-row (B,); None = T. Rows >= the count produce
    zeros and their tiles are skipped. wi_scale/wg_scale: (F,) and
    wo_scale: (D,) f32 per-output-channel dequant scales when the weights
    are int8. Returns x-shaped output."""
    squeeze = x.ndim == 2
    if squeeze:
        x = x[None]
        if token_weights is not None:
            token_weights = jnp.asarray(token_weights).reshape(1, -1)
    B, T, D = x.shape
    F = wi.shape[1]
    bt, bf = min(block_t, T), min(block_f, F)
    nt, nf = pl.cdiv(T, bt), pl.cdiv(F, bf)
    if token_weights is None:
        tw = jnp.ones((B, T, 1), jnp.float32)
    else:  # (T,) broadcasts across the batch; (B, T) is per-row
        tw = jnp.broadcast_to(
            jnp.asarray(token_weights, jnp.float32).reshape(-1, T), (B, T)
        ).reshape(B, T, 1)
    tw = jnp.broadcast_to(tw, (B, T, 128))  # lane-replicated for TPU layout
    cnt = jnp.clip(jnp.asarray(
        T if valid_count is None else valid_count, jnp.int32), 0, T)
    cnt = jnp.broadcast_to(cnt.reshape(-1), (B,))
    have_g = wg is not None
    qw = wi_scale is not None

    kernel = functools.partial(_kernel, act=act, n_fb=nf,
                               weighted=token_weights is not None,
                               block_t=bt, block_f=bf, f_total=F)
    in_specs = [
        pl.BlockSpec((1, bt, D), lambda b, i, j, *_: (b, i, 0)),
        pl.BlockSpec((D, bf), lambda b, i, j, *_: (0, j)),
    ]
    args = [x, wi]
    if have_g:
        in_specs.append(pl.BlockSpec((D, bf), lambda b, i, j, *_: (0, j)))
        args.append(wg)
    in_specs += [
        pl.BlockSpec((bf, D), lambda b, i, j, *_: (j, 0)),
        pl.BlockSpec((1, bt, 128), lambda b, i, j, *_: (b, i, 0)),
    ]
    args += [wo, tw]
    if qw:
        # per-output-channel scale rows as (1, F)/(1, D) VMEM blocks
        fspec = pl.BlockSpec((1, bf), lambda b, i, j, *_: (0, j))
        dspec = pl.BlockSpec((1, D), lambda b, i, j, *_: (0, 0))
        in_specs.append(fspec)
        args.append(wi_scale.astype(jnp.float32).reshape(1, F))
        if have_g:
            in_specs.append(fspec)
            args.append(wg_scale.astype(jnp.float32).reshape(1, F))
        in_specs.append(dspec)
        args.append(wo_scale.astype(jnp.float32).reshape(1, D))

    def kfn(cnt_ref, x_ref, *rest):
        rs = list(rest)
        wi_ref = rs.pop(0)
        wg_ref = rs.pop(0) if have_g else None
        wo_ref, tw_ref = rs.pop(0), rs.pop(0)
        wis_ref = rs.pop(0) if qw else None
        wgs_ref = rs.pop(0) if (qw and have_g) else None
        wos_ref = rs.pop(0) if qw else None
        return kernel(cnt_ref, x_ref, wi_ref, wg_ref, wo_ref, tw_ref,
                      wis_ref, wgs_ref, wos_ref, *rs)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nt, nf),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bt, D), lambda b, i, j, *_: (b, i, 0)),
        scratch_shapes=[pltpu.VMEM((bt, D), jnp.float32)],
    )
    wsize = wi.dtype.itemsize
    blocks = (2 * bt * D * x.dtype.itemsize           # x and out
              + (3 if have_g else 2) * D * bf * wsize + bt * 128 * 4)
    out = pl.pallas_call(
        kfn,
        name="fused_mlp",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, T, D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit(blocks, bt * D * 4)),
        interpret=interpret,
    )(cnt, *args)
    return out[0] if squeeze else out
