"""Grouped-query attention with RoPE, sliding windows, cross-attention,
KV caches, and ElastiFormer hooks (head routing weights, LoRA q/v).

TP formulation (§Perf H1): the jnp twins compute GQA in *repeat-kv* form —
k/v are expanded from K kv heads to the q-head count with a static take —
so every head-indexed tensor shards cleanly on one axis and XLA partitions
attention over `model` with no partial-sum all-reduces. Heads that do not
divide the axis replicate (runtime/sharding.py).

Two softmax-attention implementations:
  * plain: materializes (B,H,Sq,Sk) scores — short sequences.
  * blocked: lax.scan over KV chunks with online softmax (flash-style) —
    long sequences; numerically identical (f32 accumulation) and the
    jnp twin of kernels/flash_attention.py.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.lora import lora_apply
from repro.kernels import ops as OPS
from repro.models import quant
from repro.models.layers import (dense_init, dtype_of, norm_apply,
                                 norm_init, rope_apply, rope_tables)
from repro.runtime import sharding as SH

NEG_INF = -1e30
BLOCKED_THRESHOLD = 2048   # use blocked attention when Sk exceeds this
KV_BLOCK = 1024


def _kernel_ok(backend, *, window: int = 0, gathered: bool = False,
               causal: bool = True) -> bool:
    """Whether the Pallas flash/decode kernels may serve this attention
    call. The kernels mask causality/window by ARRAY INDEX (the ragged
    prefix contract: gathered tokens stay position-ascending, so
    index-causal == position-causal), but a sliding WINDOW measures
    position distance — on a gathered subset index distance underestimates
    it regardless of causality, so windowed gathered attention keeps the
    jnp twins."""
    del causal  # window masking is position-based whether causal or not
    if backend not in ("pallas", "interpret"):
        return False
    return not (window and window > 0 and gathered)


def _expand_kv(t, h: int):
    """(B,S,K,Dh) -> (B,S,H,Dh) repeat-kv (exact GQA; shards on heads)."""
    return jnp.take(t, jnp.arange(h) // (h // t.shape[2]), axis=2)


def attn_init(key, cfg, cross: bool = False):
    D, H, K, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], D, H * Dh, dt).reshape(D, H, Dh),
        "wk": dense_init(ks[1], D, K * Dh, dt).reshape(D, K, Dh),
        "wv": dense_init(ks[2], D, K * Dh, dt).reshape(D, K, Dh),
        "wo": dense_init(ks[3], H * Dh, D, dt).reshape(H, Dh, D),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, Dh), dt)
        p["bk"] = jnp.zeros((K, Dh), dt)
        p["bv"] = jnp.zeros((K, Dh), dt)
    if cfg.qk_norm:
        p["q_norm"] = norm_init(Dh, "rmsnorm")
        p["k_norm"] = norm_init(Dh, "rmsnorm")
    return p


def _lora_scale(lora, d):
    """Optional traced on/off multiplier ((), or (B,)) set by the policy:
    0 disables the adapter (full-budget / teacher rows stay lossless)."""
    s = lora.get("scale")
    return None if s is None else jnp.reshape(
        jnp.asarray(s), jnp.shape(s) + (1,) * (d - jnp.ndim(s)))


def _project_q(p, x, positions, cfg, lora, use_rope):
    # maybe_dequant: identity for fp32/bf16 trees, int8 * scale otherwise
    q = jnp.einsum("bsd,dhk->bshk", x,
                   quant.maybe_dequant(p, "wq", x.dtype))
    # (B,S,H,Dh)
    if lora is not None and "q" in lora:
        H, Dh = cfg.n_heads, cfg.d_head
        dq = lora_apply(lora["q"], x).reshape(x.shape[0], x.shape[1], H, Dh)
        s = _lora_scale(lora, dq.ndim)
        if s is not None:
            dq = dq * s.astype(dq.dtype)
        q = q + dq
    if "bq" in p:
        q = q + p["bq"]
    if "q_norm" in p:   # qk-norm: RMSNorm over head_dim, before RoPE
        q = norm_apply(p["q_norm"], q, "rmsnorm", cfg.norm_eps)
    if use_rope:
        cos, sin = rope_tables(positions, cfg.d_head, cfg.rope_theta)
        if cos.ndim == 2:  # (S, half) -> broadcast over batch
            cos, sin = cos[None], sin[None]
        q = rope_apply(q, cos, sin)
    return q


def _project_kv(p, x, positions, cfg, lora, use_rope):
    k = jnp.einsum("bsd,dhk->bshk", x,
                   quant.maybe_dequant(p, "wk", x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x,
                   quant.maybe_dequant(p, "wv", x.dtype))
    if lora is not None and "v" in lora:
        K, Dh = p["wv"].shape[1], p["wv"].shape[2]
        dv = lora_apply(lora["v"], x).reshape(x.shape[0], x.shape[1], K, Dh)
        s = _lora_scale(lora, dv.ndim)
        if s is not None:
            dv = dv * s.astype(dv.dtype)
        v = v + dv
    if "bk" in p:
        k, v = k + p["bk"], v + p["bv"]
    if "k_norm" in p:
        k = norm_apply(p["k_norm"], k, "rmsnorm", cfg.norm_eps)
    if use_rope:
        cos, sin = rope_tables(positions, cfg.d_head, cfg.rope_theta)
        if cos.ndim == 2:
            cos, sin = cos[None], sin[None]
        k = rope_apply(k, cos, sin)
    return k, v


def _mask(q_pos, kv_pos, causal: bool, window: int, kv_valid=None):
    """(B?, Sq, Sk) boolean allow-mask."""
    qp = q_pos[..., :, None].astype(jnp.int32)
    kp = kv_pos[..., None, :].astype(jnp.int32)
    m = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), bool)
    if causal:
        m &= kp <= qp
    if window and window > 0:
        m &= (qp - kp) < window
    if kv_valid is not None:
        m &= kv_valid[..., None, :]
    return m


def sdpa(q, k, v, mask):
    """q:(B,Sq,H,Dh) k,v:(B,Sk,K,Dh) mask:(B?,Sq,Sk) -> (B,Sq,H,Dh).

    Repeat-kv GQA (head axis shards whole); f32 softmax."""
    B, Sq, H, Dh = q.shape
    mqa = k.shape[2] == 1  # MQA: broadcast kv in the einsum, never expand
    if k.shape[2] != H and not mqa:
        k, v = _expand_kv(k, H), _expand_kv(v, H)
    scale = Dh ** -0.5
    if mqa:
        s = jnp.einsum("bqhd,bsd->bhqs", q, k[:, :, 0])
    else:
        s = jnp.einsum("bqhd,bshd->bhqs", q, k)
    s = s.astype(jnp.float32) * scale
    if mask.ndim == 2:
        mask = mask[None]
    s = jnp.where(mask[:, None], s, NEG_INF)
    a = jax.nn.softmax(s, axis=-1)
    if mqa:
        ctx = jnp.einsum("bhqs,bsd->bqhd", a.astype(v.dtype), v[:, :, 0])
    else:
        ctx = jnp.einsum("bhqs,bshd->bqhd", a.astype(v.dtype), v)
    return ctx


def blocked_sdpa(q, k, v, q_pos, kv_pos, causal, window, kv_valid=None,
                 block: int = KV_BLOCK):
    """Flash-style online-softmax attention, lax.scan over KV blocks.

    Identical math to sdpa (f32 accumulators), O(Sq*block) live memory."""
    B, Sq, H, Dh = q.shape
    Sk = k.shape[1]
    mqa = k.shape[2] == 1  # MQA: broadcast kv in the einsums, never expand
    if k.shape[2] != H and not mqa:
        k, v = _expand_kv(k, H), _expand_kv(v, H)
    kvh = k.shape[2]
    nb = -(-Sk // block)
    pad = nb * block - Sk
    if pad:
        padw = [(0, 0), (0, pad), (0, 0), (0, 0)]
        k = jnp.pad(k, padw)
        v = jnp.pad(v, padw)
        kv_pos_p = jnp.pad(kv_pos, [(0, 0)] * (kv_pos.ndim - 1) + [(0, pad)])
        valid = jnp.ones((Sk,), bool) if kv_valid is None else kv_valid
        valid = jnp.pad(valid, [(0, 0)] * (valid.ndim - 1) + [(0, pad)])
    else:
        kv_pos_p = kv_pos
        valid = jnp.ones((Sk,), bool) if kv_valid is None else kv_valid

    def bcast_b(a):  # give kv-side tensors a batch dim for scan stacking
        return jnp.broadcast_to(a, (B,) + a.shape[-1:]) if a.ndim == 1 else a

    kv_pos_p, valid = bcast_b(kv_pos_p), bcast_b(valid)
    kb = k.reshape(B, nb, block, kvh, Dh).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nb, block, kvh, Dh).transpose(1, 0, 2, 3, 4)
    pb = kv_pos_p.reshape(B, nb, block).transpose(1, 0, 2)
    mb = valid.reshape(B, nb, block).transpose(1, 0, 2)

    scale = Dh ** -0.5
    q_posb = q_pos if q_pos.ndim == 2 else jnp.broadcast_to(q_pos, (B, Sq))

    def body(carry, xs):
        m_i, l_i, acc = carry
        kc, vc, pc, vm = xs
        if mqa:
            s = jnp.einsum("bqhd,bsd->bhqs", q, kc[:, :, 0])
        else:
            s = jnp.einsum("bqhd,bshd->bhqs", q, kc)
        s = s.astype(jnp.float32) * scale
        allow = _mask(q_posb, pc, causal, window, vm)     # (B,Sq,block)
        s = jnp.where(allow[:, None], s, NEG_INF)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_i - m_new)
        p_ij = jnp.exp(s - m_new[..., None])
        l_new = l_i * alpha + jnp.sum(p_ij, axis=-1)
        if mqa:
            pv = jnp.einsum("bhqs,bsd->bhqd", p_ij,
                            vc[:, :, 0].astype(jnp.float32))
        else:
            pv = jnp.einsum("bhqs,bshd->bhqd", p_ij, vc.astype(jnp.float32))
        acc = acc * alpha[..., None] + pv
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, H, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    a0 = jnp.zeros((B, H, Sq, Dh), jnp.float32)
    (m_f, l_f, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, pb, mb))
    out = acc / jnp.maximum(l_f, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


@jax.named_scope("attention")
def attn_apply(
    p, x, *, cfg, positions, causal: bool = True, window: int = 0,
    kv_x=None, kv_positions=None, kv_valid=None, kv_count=None,
    head_weights=None, lora=None, use_rope: bool = True,
    backend=None, gathered: bool = False,
):
    """Full-sequence attention (train / prefill). Self-attn if kv_x is None.

    head_weights: (B, Sq, H) f32 ElastiFormer head-routing weights (already
    masked, logical heads); multiplies per-head context before the output
    projection — Alg. 1 output scaling = straight-through router gradient.

    ``backend`` ("pallas"/"interpret") routes the softmax-attention core
    through ``kernels.ops.flash_attention`` — the scalar-prefetched
    ``kv_count`` (a RoutingPlan's true token count, () or (B,)) then skips
    every kv/q block past the ragged prefix. ``gathered`` declares that
    q/kv rows are a RoutingPlan buffer (position-ascending subset): causal
    masking by index is exact there, sliding windows are not (see
    ``_kernel_ok``). The default/"ref" backend keeps the jnp twins.
    Returns (out (B,Sq,D), k, v) — k/v (logical K heads) for caches."""
    cross = kv_x is not None
    q = _project_q(p, x, positions, cfg, lora, use_rope and not cross)
    if cross:
        k, v = _project_kv(p, kv_x, kv_positions, cfg, lora, use_rope=False)
        kvp = kv_positions if kv_positions is not None else jnp.arange(kv_x.shape[1])
    else:
        k, v = _project_kv(p, x, positions, cfg, lora, use_rope)
        kvp = positions
    if _kernel_ok(backend, window=window, gathered=gathered,
                  causal=causal and not cross):
        if kv_valid is not None and kv_valid.ndim == 1:
            kv_valid = jnp.broadcast_to(kv_valid, k.shape[:2])
        ctx = OPS.flash_attention_sharded(q, k, v, kv_valid=kv_valid,
                                          kv_count=kv_count,
                                          causal=causal and not cross,
                                          window=window or 0,
                                          backend=backend)
    else:
        eff_window = window if (window and window > 0) else k.shape[1]
        if min(k.shape[1], eff_window) > BLOCKED_THRESHOLD:
            qp = positions if positions.ndim == 2 else jnp.broadcast_to(positions, x.shape[:2])
            ctx = blocked_sdpa(q, k, v, qp, kvp, causal and not cross, window,
                               kv_valid)
        else:
            mask = _mask(positions, kvp, causal and not cross, window, kv_valid)
            ctx = sdpa(q, k, v, mask)
    if head_weights is not None:
        ctx = ctx * head_weights[..., None].astype(ctx.dtype)
    out = jnp.einsum("bshk,hkd->bsd", ctx,
                     quant.maybe_dequant(p, "wo", ctx.dtype))
    return out, k, v


@jax.named_scope("attention")
def attn_decode(
    p, x, cache, t, *, cfg, window: int = 0, head_weights=None, lora=None,
    use_rope: bool = True, write: Optional[jnp.ndarray] = None,
    backend=None,
):
    """One decode step. x: (B,1,D); cache: {'k','v': (B,L,K,Dh),
    'valid': (B,L), 'pos': (B,L) i32}; t: scalar position, or a (B,) i32
    vector of PER-ROW positions (continuous batching: every serving slot
    decodes at its own offset inside one compiled step).

    The cache is a RING buffer: entry for position p lives at slot p % L.
    Sliding-window layers allocate L = window so a 500k-token decode keeps
    an O(window) cache; full-attention layers use L = max_seq (slot == p).
    `pos` records absolute positions (-1 = empty) for RoPE-free masking.
    write: (B,) bool — ElastiFormer token routing: skipped tokens do not
    enter the cache.  Returns (out (B,1,D), new_cache)."""
    B = x.shape[0]
    L = cache["k"].shape[1]
    quantized = "kscale" in cache
    t = jnp.asarray(t, jnp.int32)
    per_row = t.ndim == 1
    pos = t[:, None] if per_row else jnp.full((B, 1), t, jnp.int32)
    q = _project_q(p, x, pos, cfg, lora, use_rope)
    k_new, v_new = _project_kv(p, x, pos, cfg, lora, use_rope)
    if quantized:
        # quantize ONCE, at the write site (docs/quantization.md): the
        # stored (int8, scale) bytes are what every later read dequantizes
        k_new, ks_new = quant.quantize_kv(k_new)         # (B,1,K,Dh),(B,1,K)
        v_new, vs_new = quant.quantize_kv(v_new)
    wr = jnp.ones((B,), bool) if write is None else write
    if per_row:
        # per-row ring slots: scatter each row's k/v into its own slot.
        # Under a mesh the scatter result is pinned back to the cache
        # sharding (kv-heads over `model`, slots over data) — GSPMD cannot
        # partition a batch-indexed scatter and would otherwise replicate
        # the updated cache to every device, every decode step.
        slots = jax.lax.rem(t, jnp.int32(L))                 # (B,)
        bi = jnp.arange(B)
        def upd(c, n):
            old = c[bi, slots]                               # (B, K, Dh)
            new = jnp.where(wr[:, None, None], n[:, 0], old).astype(c.dtype)
            return SH.constrain_kv_cache(c.at[bi, slots].set(new), cfg)
        ck = upd(cache["k"], k_new)
        cv = upd(cache["v"], v_new)
        if quantized:
            def upds(c, n):   # scale leaves: same scatter, minus Dh
                old = c[bi, slots]                           # (B, K)
                new = jnp.where(wr[:, None], n[:, 0], old).astype(c.dtype)
                return SH.constrain_kv_scale(c.at[bi, slots].set(new), cfg)
            cks = upds(cache["kscale"], ks_new)
            cvs = upds(cache["vscale"], vs_new)
        # the slot is consumed by position t either way (stale entry
        # evicted). The mask leaves get the same write-site pin as k/v:
        # this scatter is batch-indexed too, and an unpinned mask write
        # replicates (B, L) to every device each step.
        valid = SH.constrain_kv_mask(cache["valid"].at[bi, slots].set(wr),
                                     cfg)
        cpos = SH.constrain_kv_mask(cache["pos"].at[bi, slots].set(t), cfg)
    else:
        slot = jax.lax.rem(t, jnp.int32(L))
        old = lambda c: jax.lax.dynamic_slice_in_dim(c, slot, 1, axis=1)
        upd = lambda c, n: jax.lax.dynamic_update_slice_in_dim(
            c, jnp.where(wr[:, None, None, None], n, old(c)).astype(c.dtype),
            slot, axis=1)
        ck = upd(cache["k"], k_new)
        cv = upd(cache["v"], v_new)
        if quantized:
            upds = lambda c, n: jax.lax.dynamic_update_slice_in_dim(
                c, jnp.where(wr[:, None, None], n, old(c)).astype(c.dtype),
                slot, axis=1)
            cks = upds(cache["kscale"], ks_new)
            cvs = upds(cache["vscale"], vs_new)
        # the slot is consumed by position t either way (stale entry evicted)
        valid = SH.constrain_kv_mask(jax.lax.dynamic_update_slice_in_dim(
            cache["valid"], wr[:, None], slot, axis=1), cfg)
        cpos = SH.constrain_kv_mask(jax.lax.dynamic_update_slice_in_dim(
            cache["pos"], jnp.full((B, 1), t, jnp.int32), slot, axis=1), cfg)
    new_cache = {"k": ck, "v": cv, "valid": valid, "pos": cpos}
    if quantized:
        new_cache["kscale"], new_cache["vscale"] = cks, cvs
    kv_valid = valid & (cpos >= 0)
    if _kernel_ok(backend):
        # ring-cache decode kernel: per-slot positions ride scalar
        # prefetch, masking is by the cache's absolute-position array.
        # Under a mesh the kernel runs per-shard (heads over `model`,
        # slots over data) via shard_map — see ops.decode_attention_sharded.
        tvec = t if per_row else jnp.broadcast_to(t, (B,))
        ctx = OPS.decode_attention_sharded(
            q, ck, cv, cpos, tvec, valid, window=window or 0,
            backend=backend,
            kscale=cks if quantized else None,
            vscale=cvs if quantized else None)
    else:
        ckf = quant.dequantize_kv(ck, cks, q.dtype) if quantized else ck
        cvf = quant.dequantize_kv(cv, cvs, q.dtype) if quantized else cv
        if L > BLOCKED_THRESHOLD:
            ctx = blocked_sdpa(q, ckf, cvf, pos, cpos, True, window,
                               kv_valid)
        else:
            mask = _mask(pos, cpos, True, window, kv_valid)
            ctx = sdpa(q, ckf, cvf, mask)
    if head_weights is not None:
        ctx = ctx * head_weights[..., None].astype(ctx.dtype)
    out = jnp.einsum("bshk,hkd->bsd", ctx,
                     quant.maybe_dequant(p, "wo", ctx.dtype))
    return out, new_cache


def attn_cache_init(cfg, batch: int, max_seq: int, window: int = 0,
                    kv_dtype: str = "fp32"):
    """Ring cache of length window (local layers) or max_seq (global).
    kv_dtype (docs/quantization.md): "fp32" stores the native config dtype,
    "bf16" a plain cast, "int8" adds per-(slot, token, kv-head) f32
    ``kscale``/``vscale`` sibling leaves."""
    L = min(max_seq, window) if window and window > 0 else max_seq
    K, Dh = cfg.n_kv_heads, cfg.d_head
    dt = quant.kv_store_dtype(quant.check_kv_dtype(kv_dtype), dtype_of(cfg))
    cache = {
        "k": jnp.zeros((batch, L, K, Dh), dt),
        "v": jnp.zeros((batch, L, K, Dh), dt),
        "valid": jnp.zeros((batch, L), bool),
        "pos": jnp.full((batch, L), -1, jnp.int32),
    }
    if kv_dtype == "int8":
        cache["kscale"] = jnp.ones((batch, L, K), jnp.float32)
        cache["vscale"] = jnp.ones((batch, L, K), jnp.float32)
    return cache


# ------------------------------ paged KV pool --------------------------------
#
# The block-paged twin of the ring cache (runtime/pagedkv.py): one GLOBAL
# per-layer pool of (n_pages, page_size, K, Dh) pages shared by every
# serving slot, addressed through per-slot int32 page-table rows. Position
# t of slot b lives at (table[b, t // page_size], t % page_size) — the
# position is implicit in the table layout, so there is no `pos` array;
# `pvalid` carries the ElastiFormer token-gate keep decision per lane.


def attn_paged_cache_init(cfg, n_pages: int, page_size: int,
                          kv_dtype: str = "fp32"):
    """One layer's slice of the global page pool. kv_dtype
    (docs/quantization.md): "int8" adds per-(page, lane, kv-head) f32
    ``kscale``/``vscale`` sibling pools."""
    K, Dh = cfg.n_kv_heads, cfg.d_head
    dt = quant.kv_store_dtype(quant.check_kv_dtype(kv_dtype), dtype_of(cfg))
    cache = {
        "kp": jnp.zeros((n_pages, page_size, K, Dh), dt),
        "vp": jnp.zeros((n_pages, page_size, K, Dh), dt),
        "pvalid": jnp.zeros((n_pages, page_size), bool),
    }
    if kv_dtype == "int8":
        cache["kscale"] = jnp.ones((n_pages, page_size, K), jnp.float32)
        cache["vscale"] = jnp.ones((n_pages, page_size, K), jnp.float32)
    return cache


def _paged_gather(cache, table, B: int, dtype=None):
    """Gather a (B, P)-table's pages into position-ordered (B, P*ps, K, Dh)
    K/V plus the (B, P*ps) validity mask and the implicit kv positions.
    int8 pools come back dequantized (``dtype``, default f32) — this is
    the jnp twin, the kernel path dequantizes in-register."""
    ps = cache["kp"].shape[1]
    P = table.shape[-1]
    pid = jnp.maximum(table, 0)
    kg = cache["kp"][pid].reshape(B, P * ps, *cache["kp"].shape[2:])
    vg = cache["vp"][pid].reshape(B, P * ps, *cache["vp"].shape[2:])
    if "kscale" in cache:
        K = kg.shape[-2]
        kg = quant.dequantize_kv(
            kg, cache["kscale"][pid].reshape(B, P * ps, K), dtype)
        vg = quant.dequantize_kv(
            vg, cache["vscale"][pid].reshape(B, P * ps, K), dtype)
    kvv = ((table[..., None] >= 0)
           & cache["pvalid"][pid]).reshape(B, P * ps)
    kvpos = (jnp.arange(P)[:, None] * ps
             + jnp.arange(ps)[None, :]).reshape(-1)
    return kg, vg, kvv, kvpos


@jax.named_scope("attention")
def attn_decode_paged(
    p, x, cache, t, table, trash, *, cfg, head_weights=None, lora=None,
    use_rope: bool = True, write: Optional[jnp.ndarray] = None,
    backend=None,
):
    """One decode step over the paged pool. x: (B,1,D); cache:
    {'kp','vp': (N, ps, K, Dh), 'pvalid': (N, ps)}; t: (B,) i32 per-slot
    positions; table: (B, P) i32 page-table rows (GLOBAL page ids, -1 =
    unused entry — the host guarantees entry t // ps is backed for every
    ACTIVE slot); trash: (B,) i32 per-slot trash-page ids — rows whose
    table entry is -1 (inactive slots) are remapped there, so the write is
    branch-free and never lands on a live page. write: (B,) bool token
    gate. Returns (out (B,1,D), new_cache)."""
    B = x.shape[0]
    ps = cache["kp"].shape[1]
    quantized = "kscale" in cache
    t = jnp.asarray(t, jnp.int32).reshape(-1)
    pos = t[:, None]                                       # (B, 1)
    q = _project_q(p, x, pos, cfg, lora, use_rope)
    k_new, v_new = _project_kv(p, x, pos, cfg, lora, use_rope)
    if quantized:
        # quantize ONCE, at the write site (docs/quantization.md)
        k_new, ks_new = quant.quantize_kv(k_new)         # (B,1,K,Dh),(B,1,K)
        v_new, vs_new = quant.quantize_kv(v_new)
    wr = jnp.ones((B,), bool) if write is None else write
    entries = jnp.take_along_axis(table, (t // ps)[:, None], axis=1)[:, 0]
    pages = jnp.where(entries >= 0, entries, trash)        # (B,)
    offs = jax.lax.rem(t, jnp.int32(ps))
    # per-slot page append: CoW guarantees the append page is exclusively
    # owned, so distinct active rows never scatter to the same (page, lane).
    # Under a mesh the scatter result is pinned back to the pool sharding
    # (pages over data, kv-heads over `model`) — GSPMD cannot partition a
    # page-indexed scatter and would otherwise replicate the whole pool.
    def upd(c, n):
        old = c[pages, offs]                               # (B, K, Dh)
        new = jnp.where(wr[:, None, None], n[:, 0], old).astype(c.dtype)
        return SH.constrain_page_pool(c.at[pages, offs].set(new), cfg)
    kp = upd(cache["kp"], k_new)
    vp = upd(cache["vp"], v_new)
    # the occupancy bitmap is page-indexed like k/v: pin it too, or the
    # depth router's skip writes replicate the (N, ps) mask pool per step
    pvalid = SH.constrain_page_pool(
        cache["pvalid"].at[pages, offs].set(wr), cfg)
    new_cache = {"kp": kp, "vp": vp, "pvalid": pvalid}
    if quantized:
        def upds(c, n):   # scale pools: same scatter, minus Dh
            old = c[pages, offs]                           # (B, K)
            new = jnp.where(wr[:, None], n[:, 0], old).astype(c.dtype)
            return SH.constrain_page_pool(c.at[pages, offs].set(new), cfg)
        new_cache["kscale"] = upds(cache["kscale"], ks_new)
        new_cache["vscale"] = upds(cache["vscale"], vs_new)
    if _kernel_ok(backend):
        # paged decode kernel: the table and per-slot lengths ride scalar
        # prefetch, the BlockSpec index_map gathers pages from the pool.
        # Under a mesh it runs per-shard (kv-heads over `model`, pages and
        # slots over data) — see ops.paged_decode_attention_sharded.
        ctx = OPS.paged_decode_attention_sharded(
            q, kp, vp, table, t, pvalid, backend=backend,
            kscale=new_cache.get("kscale"),
            vscale=new_cache.get("vscale"))
    else:
        kg, vg, kvv, kvpos = _paged_gather(new_cache, table, B,
                                           dtype=q.dtype)
        mask = _mask(pos, kvpos[None], True, 0, kvv)
        ctx = sdpa(q, kg, vg, mask)
        # rows with no attendable key: match the kernel's exact zeros
        ctx = jnp.where(mask.any(-1)[:, :, None, None], ctx, 0.0)
    if head_weights is not None:
        ctx = ctx * head_weights[..., None].astype(ctx.dtype)
    out = jnp.einsum("bshk,hkd->bsd", ctx,
                     quant.maybe_dequant(p, "wo", ctx.dtype))
    return out, new_cache


@jax.named_scope("attention")
def attn_chunk(
    p, x, cache, write_page, table_row, pos0, plen, *, cfg, keep=None,
    head_weights=None, lora=None, use_rope: bool = True,
):
    """One CHUNK of a paged prefill, shaped like a decode: x is (1, C, D)
    with C == page_size, covering absolute positions [pos0, pos0 + C). The
    chunk's K/V fill exactly ONE page (``write_page``, a traced id — the
    replica's trash page when this chunk's prefix page is shared and the
    chunk only recomputes queries), then the queries attend over ALL pages
    of ``table_row`` with causal masking on the implicit positions — so a
    prompt of ANY length streams through this one compiled graph,
    collapsing the per-length prefill buckets to a single compile.
    ``keep``: (1, C) ElastiFormer token gate; lanes at positions >= plen
    (chunk padding) are never marked valid. Returns (out (1,C,D),
    new_cache)."""
    B, C, _ = x.shape
    positions = pos0 + jnp.arange(C, dtype=jnp.int32)[None, :]   # (1, C)
    q = _project_q(p, x, positions, cfg, lora, use_rope)
    k_new, v_new = _project_kv(p, x, positions, cfg, lora, use_rope)
    if "kscale" in cache:
        # quantize ONCE, at the write site; the queries below then attend
        # the QUANTIZED pool via _paged_gather, so a chunked prefill is
        # bitwise identical to the decode path reading the same pages
        # (docs/quantization.md)
        k_new, ks_new = quant.quantize_kv(k_new)         # (1,C,K,Dh),(1,C,K)
        v_new, vs_new = quant.quantize_kv(v_new)
    wr = jnp.ones((B, C), bool) if keep is None else keep
    wr = wr & (positions < plen)

    def upd(c, n):
        out = jax.lax.dynamic_update_slice(
            c, n.astype(c.dtype), (write_page, 0, 0, 0))
        return SH.constrain_page_pool(out, cfg)
    kp = upd(cache["kp"], k_new)                           # (1,C,K,Dh) page
    vp = upd(cache["vp"], v_new)
    pvalid = SH.constrain_page_pool(
        jax.lax.dynamic_update_slice(cache["pvalid"], wr, (write_page, 0)),
        cfg)
    new_cache = {"kp": kp, "vp": vp, "pvalid": pvalid}
    if "kscale" in cache:
        def upds(c, n):
            out = jax.lax.dynamic_update_slice(
                c, n.astype(c.dtype), (write_page, 0, 0))
            return SH.constrain_page_pool(out, cfg)
        new_cache["kscale"] = upds(cache["kscale"], ks_new)
        new_cache["vscale"] = upds(cache["vscale"], vs_new)
    kg, vg, kvv, kvpos = _paged_gather(new_cache, table_row[None], B,
                                       dtype=q.dtype)
    mask = _mask(positions, kvpos[None], True, 0, kvv)
    ctx = sdpa(q, kg, vg, mask)
    if head_weights is not None:
        ctx = ctx * head_weights[..., None].astype(ctx.dtype)
    out = jnp.einsum("bshk,hkd->bsd", ctx,
                     quant.maybe_dequant(p, "wo", ctx.dtype))
    return out, new_cache
