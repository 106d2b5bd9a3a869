"""jit'd public wrappers for the Pallas kernels + the kernel-backend switch.

The model hot path dispatches through these wrappers under a *backend*
resolved from ``ElasticSpec.kernel_backend``:

  * ``"pallas"``    — real pallas_call, lowered by Mosaic for the TPU;
                      refused on any other backend, so a kernel never runs
                      interpreted under the name of the device path;
  * ``"interpret"`` — pallas_call under interpret=True (CPU verification of
                      the exact kernel logic, incl. the scalar-prefetch
                      ragged skip paths);
  * ``"ref"``       — the pure-jnp oracles in kernels/ref.py (and the jnp
                      twins inside the model, which are the same math) —
                      the fast CPU path;
  * ``"auto"``/None — "pallas" on TPU backends, "ref" elsewhere.

The ragged valid-count arguments (``valid_count`` / ``group_counts`` /
``kv_count``) are traced, so one bucket-sized compile serves every
occupancy. Kernel-backed ops carry a custom VJP that replays the jnp
reference backward (the standard arrangement while the hand-written
backward kernels don't exist): forward runs the kernel, gradients are the
reference's — numerically the kernels and references agree to float
tolerance, so training under ``interpret``/``pallas`` matches ``ref``.

Tests may monkeypatch the kernel modules' entry points; dispatch goes
through the module attributes (``_fused_mlp_mod.fused_mlp`` etc.) so a
patch is observed at trace time.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

import repro.kernels.decode_attention as _decode_mod
import repro.kernels.flash_attention as _flash_mod
import repro.kernels.fused_mlp as _fused_mlp_mod
import repro.kernels.moe_gmm as _moe_gmm_mod
import repro.kernels.paged_decode_attention as _paged_decode_mod
from repro.kernels import ref

BACKENDS = ("pallas", "interpret", "ref")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_backend(name=None) -> str:
    """Map an ``ElasticSpec.kernel_backend`` value to a concrete backend."""
    if name in (None, "auto"):
        return "pallas" if _on_tpu() else "ref"
    if name not in BACKENDS:
        raise ValueError(f"kernel_backend must be one of {BACKENDS} or "
                         f"'auto', got {name!r}")
    if name == "pallas" and not _on_tpu():
        raise ValueError(
            f"kernel_backend='pallas' lowers the kernels for a TPU, but JAX's "
            f"default backend is {jax.default_backend()!r}; use 'interpret' "
            f"to run the kernel logic there")
    return name


def _interp(backend: str) -> bool:
    return backend == "interpret"


def _f0(x):
    """float0 cotangent for integer/bool primal args in custom VJPs."""
    return np.zeros(jax.numpy.shape(x), jax.dtypes.float0)


# ----------------------------- flash attention -------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _flash_fwd_op(causal, window, backend, q, k, v, kv_valid, cnt):
    return _flash_mod.flash_attention(
        q, k, v, causal=causal, window=window, kv_valid=kv_valid,
        kv_count=cnt, interpret=_interp(backend))


def _flash_ref(causal, window, q, k, v, kv_valid, cnt):
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                   kv_valid=kv_valid, kv_count=cnt)


def _flash_vjp_fwd(causal, window, backend, q, k, v, kv_valid, cnt):
    out = _flash_fwd_op(causal, window, backend, q, k, v, kv_valid, cnt)
    return out, (q, k, v, kv_valid, cnt)


def _flash_vjp_bwd(causal, window, backend, res, g):
    q, k, v, kv_valid, cnt = res
    _, vjp = jax.vjp(lambda q, k, v: _flash_ref(causal, window, q, k, v,
                                                kv_valid, cnt), q, k, v)
    dq, dk, dv = vjp(g)
    return (dq, dk, dv,
            None if kv_valid is None else _f0(kv_valid),
            None if cnt is None else _f0(cnt))


_flash_fwd_op.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@partial(jax.jit, static_argnames=("causal", "window", "backend"))
def flash_attention(q, k, v, kv_valid=None, kv_count=None, *, causal=True,
                    window=0, backend=None):
    kb = resolve_backend(backend)
    if kb == "ref":
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                       kv_valid=kv_valid, kv_count=kv_count)
    return _flash_fwd_op(causal, window, kb, q, k, v, kv_valid, kv_count)


# -------------------------------- fused MLP ----------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _fused_mlp_op(act, backend, x, wi, wo, wg, tw, cnt):
    return _fused_mlp_mod.fused_mlp(x, wi, wo, wg, tw, act=act,
                                    valid_count=cnt,
                                    interpret=_interp(backend))


def _fused_mlp_vjp_fwd(act, backend, x, wi, wo, wg, tw, cnt):
    out = _fused_mlp_op(act, backend, x, wi, wo, wg, tw, cnt)
    return out, (x, wi, wo, wg, tw, cnt)


def _fused_mlp_vjp_bwd(act, backend, res, g):
    x, wi, wo, wg, tw, cnt = res
    diff = tuple(a for a in (x, wi, wo, wg, tw) if a is not None)

    def f(*args):
        it = iter(args)
        a = [next(it) if v is not None else None
             for v in (x, wi, wo, wg, tw)]
        return ref.fused_mlp_ref(a[0], a[1], a[2], a[3], a[4], act=act,
                                 valid_count=cnt)

    _, vjp = jax.vjp(f, *diff)
    grads = iter(vjp(g))
    out = [next(grads) if v is not None else None
           for v in (x, wi, wo, wg, tw)]
    return (*out, None if cnt is None else _f0(cnt))


_fused_mlp_op.defvjp(_fused_mlp_vjp_fwd, _fused_mlp_vjp_bwd)


@partial(jax.jit, static_argnames=("act", "backend"))
def fused_mlp(x, wi, wo, wg=None, token_weights=None, valid_count=None,
              wi_scale=None, wo_scale=None, wg_scale=None, *,
              act="swiglu", backend=None):
    kb = resolve_backend(backend)
    if kb == "ref":
        return ref.fused_mlp_ref(x, wi, wo, wg, token_weights, act=act,
                                 valid_count=valid_count, wi_scale=wi_scale,
                                 wo_scale=wo_scale, wg_scale=wg_scale)
    if wi_scale is not None:
        # int8 weights are a serving-only configuration (never
        # differentiated), so the quantized path skips the custom VJP
        return _fused_mlp_mod.fused_mlp(
            x, wi, wo, wg, token_weights, act=act, valid_count=valid_count,
            wi_scale=wi_scale, wo_scale=wo_scale, wg_scale=wg_scale,
            interpret=_interp(kb))
    return _fused_mlp_op(act, kb, x, wi, wo, wg, token_weights, valid_count)


# --------------------------------- MoE GMM -----------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _moe_gmm_op(act, backend, x, wi, wo, wg, w, cnt):
    return _moe_gmm_mod.moe_gmm(x, wi, wo, wg, w, act=act,
                                group_counts=cnt, interpret=_interp(backend))


def _moe_gmm_vjp_fwd(act, backend, x, wi, wo, wg, w, cnt):
    out = _moe_gmm_op(act, backend, x, wi, wo, wg, w, cnt)
    return out, (x, wi, wo, wg, w, cnt)


def _moe_gmm_vjp_bwd(act, backend, res, g):
    x, wi, wo, wg, w, cnt = res
    diff = tuple(a for a in (x, wi, wo, wg, w) if a is not None)

    def f(*args):
        it = iter(args)
        a = [next(it) if v is not None else None
             for v in (x, wi, wo, wg, w)]
        return ref.moe_gmm_ref(a[0], a[1], a[2], a[3], a[4], act=act,
                               group_counts=cnt)

    _, vjp = jax.vjp(f, *diff)
    grads = iter(vjp(g))
    out = [next(grads) if v is not None else None
           for v in (x, wi, wo, wg, w)]
    return (*out, None if cnt is None else _f0(cnt))


_moe_gmm_op.defvjp(_moe_gmm_vjp_fwd, _moe_gmm_vjp_bwd)


@partial(jax.jit, static_argnames=("act", "backend"))
def moe_gmm(x, wi, wo, wg=None, weights=None, group_counts=None,
            wi_scale=None, wo_scale=None, wg_scale=None, *,
            act="swiglu", backend=None):
    kb = resolve_backend(backend)
    if kb == "ref":
        return ref.moe_gmm_ref(x, wi, wo, wg, weights, act=act,
                               group_counts=group_counts, wi_scale=wi_scale,
                               wo_scale=wo_scale, wg_scale=wg_scale)
    if wi_scale is not None:
        # serving-only int8 path: no VJP (see fused_mlp above)
        return _moe_gmm_mod.moe_gmm(
            x, wi, wo, wg, weights, act=act, group_counts=group_counts,
            wi_scale=wi_scale, wo_scale=wo_scale, wg_scale=wg_scale,
            interpret=_interp(kb))
    return _moe_gmm_op(act, kb, x, wi, wo, wg, weights, group_counts)


# ----------------------------- decode attention ------------------------------

@partial(jax.jit, static_argnames=("window", "backend"))
def decode_attention(q, k, v, kv_pos, t, kv_valid=None, kscale=None,
                     vscale=None, *, window=0, backend=None):
    """Ring-cache decode attention (see kernels/decode_attention.py).
    kscale/vscale: (B, L, K) f32 dequant scales for int8 k/v caches.
    Inference-only: no VJP (decode is never differentiated)."""
    kb = resolve_backend(backend)
    if kb == "ref":
        return ref.decode_attention_ref(q, k, v, kv_pos, t, window=window,
                                        kv_valid=kv_valid, kscale=kscale,
                                        vscale=vscale)
    return _decode_mod.decode_attention(q, k, v, kv_pos, t, window=window,
                                        kv_valid=kv_valid, kscale=kscale,
                                        vscale=vscale,
                                        interpret=_interp(kb))


# -------------------------- paged decode attention ---------------------------

@partial(jax.jit, static_argnames=("backend",))
def paged_decode_attention(q, kp, vp, table, t, pvalid, kscale=None,
                           vscale=None, *, backend=None):
    """Paged-pool decode attention (see kernels/paged_decode_attention.py).
    q: (B, 1, H, Dh); kp/vp: (N, ps, K, Dh) page pool, read in place;
    table: (B, P) page-table rows (-1 = unused); t: (B,) positions;
    pvalid: (N, ps) routing validity; kscale/vscale: (N, ps, K) f32
    dequant scale pools for int8 kp/vp. The kernel runs one grid step per
    slot and reads only the pages of its entries 0 .. t // ps, all kv
    heads of a page at once, so its work follows the live context, not P.
    Inference-only: no VJP (decode is never differentiated)."""
    kb = resolve_backend(backend)
    if kb == "ref":
        return ref.paged_decode_attention_ref(q, kp, vp, table, t, pvalid,
                                              kscale=kscale, vscale=vscale)
    return _paged_decode_mod.paged_decode_attention(
        q, kp, vp, table, t, pvalid, kscale=kscale, vscale=vscale,
        interpret=_interp(kb))


# --------------------------- SPMD kernel wrappers -----------------------------
#
# A pallas_call is a custom call — OPAQUE to GSPMD, and Mosaic refuses to
# let it be auto-partitioned at all. Under a mesh the kernel entry points
# below therefore run the kernel INSIDE shard_map: each shard's grid covers
# only its local block (heads/kv-heads or the FFN dim over `model`, batch
# over the data axes), which is exactly how the kernels lower on a real TPU
# slice. An axis whose dims do not divide is replicated instead: every
# device then runs the kernel on the whole of that axis (correct, and
# slower). The jnp "ref" backend needs none of this — XLA partitions jnp
# ops natively — so these wrappers fall through to the plain call for
# "ref", off-mesh, on a one-device mesh, and inside an enclosing manual
# shard_map region.


def _shard_axes(mesh, kb, batch_dims, model_dims):
    """(mesh, data axes or None, `model` or None) for a per-shard kernel
    call, or None for a plain call (see the section comment). The data
    axes shard when the mesh's data size divides every ``batch_dims``
    entry, `model` when its size divides every ``model_dims`` entry."""
    from repro.runtime import sharding as SH
    mesh = mesh if mesh is not None else SH.active_mesh()
    if (mesh is None or kb == "ref" or mesh.devices.size == 1
            or jax.sharding.get_abstract_mesh().manual_axes):
        return None
    d, m = SH.data_axis_size(mesh), mesh.shape.get("model", 1)
    bx = (SH.batch_axes(mesh)
          if d > 1 and all(n % d == 0 for n in batch_dims) else None)
    md = "model" if m > 1 and all(n % m == 0 for n in model_dims) else None
    return mesh, bx, md


def _per_shard(call, operands, out_spec, mesh, psum_axis=None):
    """``call(**operands)`` inside shard_map. ``operands``: name ->
    (array or None, PartitionSpec); None operands stay None in the body.
    ``psum_axis``: reduce the per-shard partial results over it."""
    names = [n for n, (a, _) in operands.items() if a is not None]

    def body(*xs):
        y = call(**dict(zip(names, xs)))
        return jax.lax.psum(y, psum_axis) if psum_axis else y

    return jax.shard_map(
        body, mesh=mesh, in_specs=tuple(operands[n][1] for n in names),
        out_specs=out_spec, check_vma=False,
    )(*(operands[n][0] for n in names))


def _count_spec(cnt, bx):
    """A valid count is () — replicated — or per batch row (B,)."""
    return P(bx) if getattr(cnt, "ndim", 0) else P()


def flash_attention_sharded(q, k, v, kv_valid=None, kv_count=None, *,
                            causal=True, window=0, backend=None, mesh=None):
    """Prefill flash attention, one grid PER SHARD: q heads and kv heads
    over `model` (each shard's local head->kv-group mapping is then exact,
    as in the decode wrapper), batch over the data axes. Differentiable
    through the inner op's ref-replay VJP."""
    kb = resolve_backend(backend)
    lay = _shard_axes(mesh, kb, (q.shape[0],), (q.shape[2], k.shape[2]))
    if lay is None:
        return flash_attention(q, k, v, kv_valid, kv_count, causal=causal,
                               window=window, backend=backend)
    mesh, bx, md = lay
    heads = P(bx, None, md, None)
    return _per_shard(
        lambda q, k, v, kv_valid=None, cnt=None: _flash_fwd_op(
            causal, window, kb, q, k, v, kv_valid, cnt),
        {"q": (q, heads), "k": (k, heads), "v": (v, heads),
         "kv_valid": (kv_valid, P(bx, None)),
         "cnt": (kv_count, _count_spec(kv_count, bx))},
        heads, mesh)


def fused_mlp_sharded(x, wi, wo, wg=None, token_weights=None,
                      valid_count=None, wi_scale=None, wo_scale=None,
                      wg_scale=None, *, act="swiglu", backend=None,
                      mesh=None):
    """Fused MLP with the FFN dim sharded over `model` (the dense-MLP TP
    rules: wi/wg (D, F/m), wo (F/m, D)): each shard runs the kernel on its
    slice of F and the partial outputs are psummed; batch rows over the
    data axes. x: (B, T, D) or (T, D)."""
    kb = resolve_backend(backend)
    batched = x.ndim == 3
    lay = _shard_axes(mesh, kb, (x.shape[0],) if batched else (),
                      (wi.shape[-1],))
    if lay is None:
        return fused_mlp(x, wi, wo, wg, token_weights, valid_count, wi_scale,
                         wo_scale, wg_scale, act=act, backend=backend)
    mesh, bx, md = lay
    rows = P(bx, None, None) if batched else P(None, None)
    tw_spec = (P(bx, None) if getattr(token_weights, "ndim", 0) == 2
               else P(None))

    def call(x, wi, wo, wg=None, tw=None, cnt=None, wis=None, wos=None,
             wgs=None):
        if wis is not None:     # serving-only int8 path: no VJP
            return _fused_mlp_mod.fused_mlp(
                x, wi, wo, wg, tw, act=act, valid_count=cnt, wi_scale=wis,
                wo_scale=wos, wg_scale=wgs, interpret=_interp(kb))
        return _fused_mlp_op(act, kb, x, wi, wo, wg, tw, cnt)

    # per-output-channel scales shard with their weight's output axis:
    # wi/wg scales (F,) over `model`, wo scale (D,) replicated
    return _per_shard(call, {
        "x": (x, rows), "wi": (wi, P(None, md)), "wo": (wo, P(md, None)),
        "wg": (wg, P(None, md)), "tw": (token_weights, tw_spec),
        "cnt": (valid_count, _count_spec(valid_count, bx)),
        "wis": (wi_scale, P(md)), "wos": (wo_scale, P(None)),
        "wgs": (wg_scale, P(md))}, rows, mesh, psum_axis=md)


def moe_gmm_sharded(x, wi, wo, wg=None, weights=None, group_counts=None,
                    wi_scale=None, wo_scale=None, wg_scale=None, *,
                    act="swiglu", backend=None, mesh=None):
    """Grouped expert matmul with each expert's FFN dim sharded over
    `model` (the MoE TP rules: wi/wg (E, D, Fe/m), wo (E, Fe/m, D)): each
    shard runs the kernel on its slice and the partial outputs are
    psummed; dispatch-buffer rows over the data axes. x: (B, E, C, D) or
    (E, C, D)."""
    kb = resolve_backend(backend)
    batched = x.ndim == 4
    lay = _shard_axes(mesh, kb, (x.shape[0],) if batched else (),
                      (wi.shape[-1],))
    if lay is None:
        return moe_gmm(x, wi, wo, wg, weights, group_counts, wi_scale,
                       wo_scale, wg_scale, act=act, backend=backend)
    mesh, bx, md = lay
    lead = (bx,) if batched else ()

    def call(x, wi, wo, wg=None, w=None, cnt=None, wis=None, wos=None,
             wgs=None):
        if wis is not None:     # serving-only int8 path: no VJP
            return _moe_gmm_mod.moe_gmm(
                x, wi, wo, wg, w, act=act, group_counts=cnt, wi_scale=wis,
                wo_scale=wos, wg_scale=wgs, interpret=_interp(kb))
        return _moe_gmm_op(act, kb, x, wi, wo, wg, w, cnt)

    rows = P(*lead, None, None, None)
    return _per_shard(call, {
        "x": (x, rows), "wi": (wi, P(None, None, md)),
        "wo": (wo, P(None, md, None)), "wg": (wg, P(None, None, md)),
        "w": (weights, P(*lead, None, None)),
        "cnt": (group_counts, P(*lead, None)),
        "wis": (wi_scale, P(None, md)), "wos": (wo_scale, P(None, None)),
        "wgs": (wg_scale, P(None, md))}, rows, mesh, psum_axis=md)


def decode_attention_sharded(q, k, v, kv_pos, t, kv_valid, *, window=0,
                             backend=None, mesh=None, kscale=None,
                             vscale=None):
    """Ring-cache decode kernel, one grid PER SHARD: q heads and kv heads
    shard over `model`, batch (serving slots) over the data axes. Per-head
    attention has no cross-head contraction, so no collective is needed —
    the output stays head-sharded and the caller's wo projection reduces it
    under GSPMD. Scale leaves (int8 caches) shard like k/v minus the Dh
    axis. Heads shard only when H % model == 0 and K % model == 0 (each
    shard's local head->kv-group mapping is then exact)."""
    kb = resolve_backend(backend)
    lay = _shard_axes(mesh, kb, (q.shape[0],), (q.shape[2], k.shape[2]))
    if lay is None:
        return decode_attention(q, k, v, kv_pos, t, kv_valid, kscale,
                                vscale, window=window, backend=backend)
    mesh, bx, md = lay
    heads = P(bx, None, md, None)
    return _per_shard(
        lambda q, k, v, pos, t, ok=None, ks=None, vs=None:
            _decode_mod.decode_attention(
                q, k, v, pos, t, window=window, kv_valid=ok, kscale=ks,
                vscale=vs, interpret=_interp(kb)),
        {"q": (q, heads), "k": (k, heads), "v": (v, heads),
         "pos": (kv_pos, P(bx, None)), "t": (t, P(bx)),
         "ok": (kv_valid, P(bx, None)),
         "ks": (kscale, P(bx, None, md)), "vs": (vscale, P(bx, None, md))},
        heads, mesh)


def paged_decode_attention_sharded(q, kp, vp, table, t, pvalid, *,
                                   backend=None, mesh=None, kscale=None,
                                   vscale=None):
    """Paged-pool decode kernel, one grid PER SHARD: kv heads shard over
    `model`, and the POOL's page axis shards over the data axes alongside
    the slot batch — replica locality (the serving engine only hands a
    slot pages from its own replica's contiguous id range, enforced by
    ``PagePool``) is exactly pool-shard locality, so each shard gathers
    only local pages. Page-table entries arrive as GLOBAL ids and are
    rebased in-body by the shard's page offset. Heads shard only when
    H % model == 0 and K % model == 0; pages and slots only when the data
    size divides both B and N."""
    kb = resolve_backend(backend)
    N = kp.shape[0]
    lay = _shard_axes(mesh, kb, (q.shape[0], N), (q.shape[2], kp.shape[2]))
    if lay is None:
        return paged_decode_attention(q, kp, vp, table, t, pvalid, kscale,
                                      vscale, backend=backend)
    mesh, bx, md = lay
    pages_per_shard = N // (1 if bx is None else
                            int(np.prod([mesh.shape[a] for a in bx])))

    def call(q, kp, vp, table, t, pvalid, ks=None, vs=None):
        if bx is not None:
            ridx = 0
            for ax in bx:
                ridx = ridx * mesh.shape[ax] + jax.lax.axis_index(ax)
            table = jnp.where(table >= 0,
                              table - ridx * pages_per_shard, -1)
        return _paged_decode_mod.paged_decode_attention(
            q, kp, vp, table, t, pvalid, kscale=ks, vscale=vs,
            interpret=_interp(kb))

    heads = P(bx, None, md, None)
    # scale pools shard like the KV pool minus the Dh axis: pages over
    # the data axes, kv-heads over `model`
    return _per_shard(call, {
        "q": (q, heads), "kp": (kp, heads), "vp": (vp, heads),
        "table": (table, P(bx, None)), "t": (t, P(bx)),
        "pvalid": (pvalid, P(bx, None)),
        "ks": (kscale, P(bx, None, md)), "vs": (vscale, P(bx, None, md))},
        heads, mesh)
