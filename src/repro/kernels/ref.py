"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth
that tests/test_kernels.py sweeps shapes/dtypes against). The ragged
valid-count arguments mirror the kernels' scalar-prefetch contract: rows
past the count produce exact zeros."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _dq_kv(x, scale):
    """int8 KV + per-(token, head) scale -> f32 (identity when no scale)."""
    if scale is None:
        return x
    return x.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]


def _dq_w(w, scale):
    """int8 weight + per-output-channel scale -> f32: the scale spans the
    LAST axis block — (F,) for (D, F), (D,) for (F, D), (E, F)/(E, D) for
    expert stacks — broadcasting over the reduced axis at -2."""
    if scale is None:
        return w
    return w.astype(jnp.float32) * scale.astype(jnp.float32)[..., None, :]


def flash_attention_ref(q, k, v, *, causal=True, window=0, kv_valid=None,
                        sm_scale=None, kv_count=None):
    """q: (B,Sq,H,Dh); k,v: (B,Sk,K,Dh) -> (B,Sq,H,Dh). Dense softmax.
    kv_count: scalar or (B,) ragged prefix count over the q/kv buffers."""
    B, Sq, H, Dh = q.shape
    Sk, K = k.shape[1], k.shape[2]
    G = H // K
    sm_scale = Dh ** -0.5 if sm_scale is None else sm_scale
    qg = q.reshape(B, Sq, K, G, Dh).astype(jnp.float32)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k.astype(jnp.float32)) * sm_scale
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window and window > 0:
        mask &= (qpos - kpos) < window
    mask = jnp.broadcast_to(mask, (B, 1, 1, Sq, Sk))
    if kv_valid is not None:
        mask = mask & kv_valid[:, None, None, None, :]
    if kv_count is not None:
        cnt = jnp.broadcast_to(jnp.asarray(kv_count, jnp.int32).reshape(-1),
                               (B,))
        mask = mask & (kpos < cnt[:, None, None, None, None])
    s = jnp.where(mask, s, NEG_INF)
    a = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bkgqs,bskd->bqkgd", a, v.astype(jnp.float32))
    ctx = ctx.reshape(B, Sq, H, Dh)
    if kv_count is not None:
        ctx = jnp.where(
            jnp.arange(Sq)[None, :, None, None] < cnt[:, None, None, None],
            ctx, 0.0)
    return ctx.astype(q.dtype)


def decode_attention_ref(q, k, v, kv_pos, t, *, window=0, kv_valid=None,
                         kscale=None, vscale=None, sm_scale=None):
    """Ring-cache decode attention oracle. q: (B,1,H,Dh); k,v: (B,L,K,Dh);
    kv_pos: (B,L) absolute positions (-1 = empty); t: (B,) per-slot decode
    positions; kscale/vscale: (B,L,K) f32 dequant scales for int8 k/v.
    Masks by the cache's position array, not by slot index."""
    k, v = _dq_kv(k, kscale), _dq_kv(v, vscale)
    B, Sq, H, Dh = q.shape
    L, K = k.shape[1], k.shape[2]
    G = H // K
    sm_scale = Dh ** -0.5 if sm_scale is None else sm_scale
    t = jnp.broadcast_to(jnp.asarray(t, jnp.int32).reshape(-1), (B,))
    qg = q.reshape(B, Sq, K, G, Dh).astype(jnp.float32)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k.astype(jnp.float32)) * sm_scale
    pos = kv_pos.astype(jnp.int32)
    mask = (pos >= 0) & (pos <= t[:, None])
    if window and window > 0:
        mask &= (t[:, None] - pos) < window
    if kv_valid is not None:
        mask &= kv_valid
    s = jnp.where(mask[:, None, None, None, :], s, NEG_INF)
    a = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bkgqs,bskd->bqkgd", a, v.astype(jnp.float32))
    ctx = ctx.reshape(B, Sq, H, Dh)
    # rows with NO attendable key (fresh slot, everything routed out):
    # softmax of an all -NEG_INF row is uniform garbage — the kernel
    # returns exact zeros there, and this oracle must match it
    ctx = jnp.where(mask.any(-1)[:, None, None, None], ctx, 0.0)
    return ctx.astype(q.dtype)


def paged_decode_attention_ref(q, kp, vp, table, t, pvalid, *, kscale=None,
                               vscale=None, sm_scale=None):
    """Paged-pool decode attention oracle. q: (B,1,H,Dh); kp, vp:
    (N, page_size, K, Dh) global page pool; table: (B,P) i32 page-table
    rows (-1 = unused); t: (B,) per-slot decode positions; pvalid:
    (N, page_size) routing validity; kscale/vscale: (N, page_size, K) f32
    dequant scale pools for int8 kp/vp. Gathers each slot's pages and
    masks by the implicit position ``p * page_size + lane``."""
    kp, vp = _dq_kv(kp, kscale), _dq_kv(vp, vscale)
    B, Sq, H, Dh = q.shape
    N, ps, K = kp.shape[0], kp.shape[1], kp.shape[2]
    P = table.shape[1]
    G = H // K
    sm_scale = Dh ** -0.5 if sm_scale is None else sm_scale
    table = jnp.asarray(table, jnp.int32)
    t = jnp.broadcast_to(jnp.asarray(t, jnp.int32).reshape(-1), (B,))
    pid = jnp.maximum(table, 0)                       # (B, P)
    k = kp[pid].reshape(B, P * ps, K, Dh)             # gather pages
    v = vp[pid].reshape(B, P * ps, K, Dh)
    pos = (jnp.arange(P)[:, None] * ps
           + jnp.arange(ps)[None, :]).reshape(-1)     # (P*ps,) implicit
    mask = ((table[:, :, None] >= 0)
            & pvalid[pid]).reshape(B, P * ps)
    mask &= pos[None, :] <= t[:, None]
    qg = q.reshape(B, Sq, K, G, Dh).astype(jnp.float32)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k.astype(jnp.float32)) * sm_scale
    s = jnp.where(mask[:, None, None, None, :], s, NEG_INF)
    a = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bkgqs,bskd->bqkgd", a, v.astype(jnp.float32))
    ctx = ctx.reshape(B, Sq, H, Dh)
    # rows with no attendable key match the kernel's exact zeros
    ctx = jnp.where(mask.any(-1)[:, None, None, None], ctx, 0.0)
    return ctx.astype(q.dtype)


def _act(name):
    return jax.nn.silu if name == "swiglu" else jax.nn.gelu


def fused_mlp_ref(x, wi, wo, wg=None, token_weights=None, *, act="swiglu",
                  valid_count=None, wi_scale=None, wo_scale=None,
                  wg_scale=None):
    """x: (T, D) or (B, T, D); valid_count: None | scalar | (B,);
    wi_scale/wg_scale (F,) and wo_scale (D,): int8 weight dequant."""
    wi, wo, wg = _dq_w(wi, wi_scale), _dq_w(wo, wo_scale), \
        (_dq_w(wg, wg_scale) if wg is not None else None)
    xf = x.astype(jnp.float32)
    h = xf @ wi.astype(jnp.float32)
    if wg is not None:
        g = _act(act)(xf @ wg.astype(jnp.float32))
        h = g * h
    else:
        h = jax.nn.gelu(h) if act == "gelu" else jax.nn.silu(h)
    y = h @ wo.astype(jnp.float32)
    if token_weights is not None:
        y = y * token_weights.astype(jnp.float32)[..., None]
    if valid_count is not None:
        cnt = jnp.asarray(valid_count, jnp.int32)
        rows = jnp.arange(x.shape[-2])
        if x.ndim == 3:
            cnt = jnp.broadcast_to(cnt.reshape(-1), (x.shape[0],))
            y = jnp.where(rows[None, :, None] < cnt[:, None, None], y, 0.0)
        else:
            y = jnp.where(rows[:, None] < cnt, y, 0.0)
    return y.astype(x.dtype)


def moe_gmm_ref(x, wi, wo, wg=None, weights=None, *, act="swiglu",
                group_counts=None, wi_scale=None, wo_scale=None,
                wg_scale=None):
    """x: (E, C, D) or batched (B, E, C, D); group_counts: (E,) / (B, E);
    wi_scale/wg_scale (E, Fe) and wo_scale (E, D): int8 weight dequant."""
    wi, wo, wg = _dq_w(wi, wi_scale), _dq_w(wo, wo_scale), \
        (_dq_w(wg, wg_scale) if wg is not None else None)
    xf = x.astype(jnp.float32)
    h = jnp.einsum("...ecd,edf->...ecf", xf, wi.astype(jnp.float32))
    if wg is not None:
        g = _act(act)(jnp.einsum("...ecd,edf->...ecf", xf,
                                 wg.astype(jnp.float32)))
        h = g * h
    else:
        h = _act(act)(h)
    y = jnp.einsum("...ecf,efd->...ecd", h, wo.astype(jnp.float32))
    if weights is not None:
        y = y * weights.astype(jnp.float32)[..., None]
    if group_counts is not None:
        cnt = jnp.asarray(group_counts, jnp.int32)
        y = jnp.where(
            jnp.arange(x.shape[-2])[:, None] < cnt[..., None, None], y, 0.0)
    return y.astype(x.dtype)
