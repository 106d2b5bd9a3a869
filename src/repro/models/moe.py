"""Mixture-of-experts MLP: native (qwen2-moe, grok-1, lfm2-moe) and
ElastiFormer's moefied dense MLP share this machinery.

Dispatch is per-expert capacity gather (exact top-k semantics, FLOPs
proportional to selected experts only, no (B,S,E,C) one-hot): for each expert
take its top-C tokens by routing weight, gather, batched expert matmul,
weighted scatter-add. Sequence-chunked via lax.scan to bound the gather
buffer and keep the HLO small at 512-way SPMD.

Native experts dispatch dropless (C = the chunk's rows: no token loses an
expert it picked, so a prompt's result does not depend on how it is
chunked); the grouped-matmul kernel's per-expert counts skip the empty
capacity tiles. Moefied experts keep the capacity factor.

Native routers: ``softmax`` (scores ``s = softmax(h W_r)``, the top-k of
``s``, each weighted by its ``s``) or ``sigmoid`` (lfm2-moe: ``s =
sigmoid(h W_r)``, the top-k of ``s + expert_bias``, each weighted by its
``s`` renormalised over the chosen experts). The elastic expert knob
keeps the top ``count`` of those k. The moefied
router (``router=None``) is the ElastiFormer one: ``E * softmax``, and a
count >= E runs the dense module.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.routing import RouteAux, bcast_to, is_full, topk_mask, \
    topk_mask_dyn
from repro.kernels import ops as OPS
from repro.models.layers import act_fn, dense_init, dtype_of, is_gated
from repro.models import quant


def moe_init(key, cfg):
    """Native MoE params (router + stacked experts + optional shared)."""
    m = cfg.moe
    D, dt = cfg.d_model, dtype_of(cfg)
    ks = jax.random.split(key, 8)
    E, Fe = m.n_experts, m.d_expert
    p = {
        "router": dense_init(ks[0], D, E, jnp.float32),
        "wi": dense_init(ks[1], D, E * Fe, dt).reshape(D, E, Fe).transpose(1, 0, 2),
        "wo": dense_init(ks[2], Fe, E * D, dt).reshape(Fe, E, D).transpose(1, 0, 2),
    }
    if m.router == "sigmoid":
        p["expert_bias"] = jnp.zeros((E,), jnp.float32)
    if is_gated(cfg.act):
        p["wg"] = dense_init(ks[3], D, E * Fe, dt).reshape(D, E, Fe).transpose(1, 0, 2)
    if m.n_shared_experts:
        Fs = m.d_shared
        p["shared"] = {"wi": dense_init(ks[4], D, Fs, dt),
                       "wo": dense_init(ks[5], Fs, D, dt)}
        if is_gated(cfg.act):
            p["shared"]["wg"] = dense_init(ks[6], D, Fs, dt)
    return p


def _expert_ffn(p, x_sel, act, backend=None, counts=None):
    """x_sel: (B,E,C,D), expert weights (E,D,Fe)/(E,Fe,D) -> (B,E,C,D).

    ``backend`` "pallas"/"interpret" routes through the grouped-matmul
    kernel (``kernels.ops.moe_gmm``); ``counts`` (B,E) per-expert occupancy
    then skips every capacity tile past an expert's dispatched tokens —
    the dispatch gather keeps the valid slots a per-(b,e) prefix, so the
    counts are exact, not a bound."""
    if backend in ("pallas", "interpret"):
        return OPS.moe_gmm_sharded(x_sel, p["wi"], p["wo"], p.get("wg"),
                                   group_counts=counts,
                                   wi_scale=p.get("wi_scale"),
                                   wo_scale=p.get("wo_scale"),
                                   wg_scale=p.get("wg_scale"),
                                   act=act, backend=backend)
    h = jnp.einsum("becd,edf->becf", x_sel,
                   quant.maybe_dequant(p, "wi", x_sel.dtype))
    if "wg" in p:
        h = act_fn(act)(jnp.einsum("becd,edf->becf", x_sel,
                                   quant.maybe_dequant(p, "wg", x_sel.dtype))) * h
    else:
        h = act_fn(act)(h)
    return jnp.einsum("becf,efd->becd", h,
                      quant.maybe_dequant(p, "wo", x_sel.dtype)).astype(x_sel.dtype)


def _router_logits(x, rw, native: bool):
    """Router logits in float32; a native router's at full float32
    precision (its top-k picks experts outright, so a rounded logit can
    swap an expert for another)."""
    x = x.astype(jnp.float32)
    if native:
        return jnp.dot(x, rw, precision=jax.lax.Precision.HIGHEST)
    return x @ rw


def _native_scores(p, logits, router: str):
    """(scores ``s``, selection scores) of a native router."""
    if router == "sigmoid":
        s = jax.nn.sigmoid(logits)
        return s, s + p["expert_bias"]
    s = jax.nn.softmax(logits, axis=-1)
    return s, s


def _native_weights(s, mask, router: str):
    """Combine weights of the chosen experts: ``s``, renormalised over the
    ``mask``ed ones (+1e-6) by the sigmoid router; 0 elsewhere."""
    w = jnp.where(mask, s, 0.0)
    if router == "sigmoid":
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    return w


def _native_select(sel, k: int, top_k_traced):
    """Experts chosen by selection score ``sel``: the top ``k``; with a
    traced elastic count below ``k``, the top ``count`` of them (>= k:
    exactly the native top-k)."""
    mask = topk_mask(sel, k)
    if top_k_traced is None:
        return mask
    full = bcast_to(is_full(top_k_traced, k), sel.ndim)
    part = topk_mask_dyn(sel, jnp.clip(top_k_traced, 1, k))
    return jnp.where(full, mask, part)


@jax.named_scope("mlp")
def moe_apply(
    p, x, *, act: str, top_k: int, router_w=None, normalize_to_m: bool = False,
    capacity_factor: float = 1.25, seq_chunk: int = 2048, top_k_traced=None,
    token_valid=None, dispatch_frac=None, token_count=None, backend=None,
    router=None,
):
    """x: (B,S,D) -> (B,S,D), aux. router_w overrides p['router'] (elastic).

    ``router`` "softmax"/"sigmoid": a native router (module docstring),
    dispatched dropless: capacity is the chunk's rows, so every chosen
    (token, expert) pair is computed. ``top_k_traced`` then keeps the top
    ``count`` of the ``top_k`` chosen experts (>= top_k: the native
    selection exactly).

    ``top_k_traced``: optional traced expert count ((), or (B,)). Dispatch
    buffers are then sized for ``top_k`` (the static maximum — pass E for
    the any-budget graph) and experts beyond the traced count are masked
    out, so one compilation serves every expert budget. A traced count
    >= E forces uniform weight 1 — the exact (lossless) dense module.

    ``token_valid`` (B,S) bars tokens from dispatch (token-routed callers:
    skipped tokens must not evict kept ones from expert capacity), and
    ``dispatch_frac`` (traced token capacity) shrinks the per-expert
    capacity to what the static *gather* path would have used for the same
    budget — together they make the one-graph masked composition match the
    gathered per-budget compile exactly in the single-chunk regime.

    ``token_count`` is the ragged capacity-bucket contract: x is a bucket
    buffer whose first N rows (per batch row, () or (B,)) are real tokens.
    It derives the dispatch shrink (``dispatch_frac = count / S``) so a
    bucket-sized compile dispatches exactly what the per-budget gather
    compile would have."""
    B, S, D = x.shape
    if token_count is not None and dispatch_frac is None:
        if isinstance(token_count, (int, float)):
            dispatch_frac = float(token_count) / S
        else:
            dispatch_frac = jnp.asarray(token_count, jnp.float32) / S
    rw = router_w if router_w is not None else p["router"]
    E = rw.shape[-1]
    k = min(top_k, E)
    chunk = min(seq_chunk, S)
    n_chunks = -(-S // chunk)
    # Elastic token routing hands us ragged S (e.g. ceil(0.8*4096)=3277):
    # pad to a chunk multiple; padded tokens are barred from dispatch.
    s_pad = n_chunks * chunk
    x_orig = x
    if s_pad != S:
        x = jnp.pad(x, [(0, 0), (0, s_pad - S), (0, 0)])
    valid = (jnp.arange(s_pad) < S)
    tv = None
    if token_valid is not None:
        tv = token_valid if s_pad == S else jnp.pad(
            token_valid, [(0, 0), (0, s_pad - S)])
    dropless = router is not None
    if dropless:
        cap = chunk
    else:
        cap = int(math.ceil(k * chunk / E * capacity_factor))
        cap = min(chunk, max(4, -(-cap // 4) * 4))

    def one_chunk(xc, vc, tvc):
        s = xc.shape[1]
        with jax.named_scope("router"):
            logits = _router_logits(xc, rw, router is not None)  # (B,s,E)
            cap_eff = None
            kept = chunk if dispatch_frac is None else jnp.clip(
                jnp.ceil(dispatch_frac * chunk - 1e-9), 1, chunk)
            if router is not None:
                probs, sel = _native_scores(p, logits, router)
                mask = _native_select(sel, k, top_k_traced) \
                    & vc[None, :, None]
                w = _native_weights(probs, mask, router)
                k_for_cap = k
            elif top_k_traced is None:
                probs = jax.nn.softmax(logits, axis=-1)
                w = probs * E if normalize_to_m else probs
                mask = topk_mask(w, k) & vc[None, :, None]
                k_for_cap = k
            else:
                probs = jax.nn.softmax(logits, axis=-1)
                w = probs * E if normalize_to_m else probs
                kt = jnp.clip(top_k_traced, 1, E)
                full = bcast_to(is_full(top_k_traced, E), w.ndim)
                w = jnp.where(full, 1.0, w)
                mask = topk_mask_dyn(w, kt) & vc[None, :, None]
                k_for_cap = kt
            if tvc is not None:
                mask = mask & tvc[:, :, None]
            if not dropless and (top_k_traced is not None
                                 or dispatch_frac is not None):
                # per-expert capacity the static path would have compiled for
                # this budget (buffers stay sized for the static maximum `cap`)
                ce = jnp.ceil(k_for_cap * kept / E * capacity_factor)
                cap_eff = jnp.minimum(kept,
                                      jnp.maximum(4, jnp.ceil(ce / 4) * 4))
            # load-balance stats over REAL tokens only: chunk padding and the
            # ragged bucket's invalid tail must not dilute the denominator
            # (else budgets sharing a bucket train against a weaker signal
            # than the per-budget gather compile would have)
            stat_w = jnp.broadcast_to(vc[None, :, None].astype(jnp.float32),
                                      mask.shape[:2] + (1,))
            if tvc is not None:
                stat_w = stat_w * tvc[:, :, None].astype(jnp.float32)
            denom = jnp.maximum(jnp.sum(stat_w), 1.0)
            red_frac = jnp.sum(mask * stat_w, axis=(0, 1)) / denom
            load = E * jnp.sum(
                red_frac * jnp.sum(probs * stat_w, axis=(0, 1)) / denom)
            sc = jnp.where(mask, w, -jnp.inf)                     # (B,s,E)
            vals, idx = jax.lax.top_k(sc.transpose(0, 2, 1), cap)  # (B,E,C)
            keep = jnp.isfinite(vals)
            if cap_eff is not None:
                keep &= jnp.arange(cap)[None, None, :] < bcast_to(cap_eff, 3)
        # dispatch: token gather into (B,E,C,D) buffers (UNweighted)
        x_sel = jnp.take_along_axis(xc[:, None], idx[..., None], axis=2)
        # per-(b,e) occupancy: top_k returns descending, so the kept slots
        # are a prefix — the exact group_counts the GMM kernel skips by
        y_buf = _expert_ffn(p, x_sel, act, backend=backend,
                            counts=jnp.sum(keep, axis=-1))    # (B,E,C,D)
        # combine by GATHER, not scatter (§Perf H3): XLA upcasts bf16
        # scatter-add to f32 and surrounds it with full-buffer copies
        # (~25 GB/layer of traffic). Instead invert the dispatch index
        # with a tiny int32 scatter, then each token reads back its k
        # expert outputs — bf16 loads proportional to top-k only.
        b3 = jnp.arange(B)[:, None, None]
        e3 = jnp.arange(E)[None, :, None]
        slot_of = jnp.full((B, E, s), -1, jnp.int32)
        slot_of = slot_of.at[b3, e3, idx].set(
            jnp.where(keep, jnp.broadcast_to(jnp.arange(cap), (B, E, cap)),
                      -1))
        wtok, eids = jax.lax.top_k(sc, k)                     # (B,s,k)
        slots = jnp.take_along_axis(slot_of.transpose(0, 2, 1), eids, -1)
        ok = jnp.isfinite(wtok) & (slots >= 0)
        lin = eids * cap + jnp.maximum(slots, 0)              # (B,s,k)
        y_tok = jnp.take_along_axis(
            y_buf.reshape(B, E * cap, D),
            lin.reshape(B, s * k)[..., None], axis=1).reshape(B, s, k, D)
        wt = jnp.where(ok, wtok, 0.0)
        out = jnp.sum(y_tok * wt[..., None].astype(xc.dtype), axis=2)
        return out.astype(xc.dtype), load

    xs = x.reshape(B, n_chunks, chunk, D).transpose(1, 0, 2, 3)
    vs = valid.reshape(n_chunks, chunk)
    if tv is not None:
        tvs = tv.reshape(B, n_chunks, chunk).transpose(1, 0, 2)
        ys, loads = jax.lax.scan(
            lambda c, xv: (c, one_chunk(*xv)), None, (xs, vs, tvs))[1]
    else:
        ys, loads = jax.lax.scan(
            lambda c, xv: (c, one_chunk(xv[0], xv[1], None)), None,
            (xs, vs))[1]
    y = ys.transpose(1, 0, 2, 3).reshape(B, s_pad, D)[:, :S]
    if "shared" in p:
        y = y + _dense_ffn(p["shared"], x_orig, act)
    aux = RouteAux.of(load=jnp.mean(loads))
    return y, aux


def _dense_ffn(p, x, act):
    h = x @ quant.maybe_dequant(p, "wi", x.dtype)
    if "wg" in p:
        h = act_fn(act)(x @ quant.maybe_dequant(p, "wg", x.dtype)) * h
    else:
        h = act_fn(act)(h)
    return (h @ quant.maybe_dequant(p, "wo", x.dtype)).astype(x.dtype)


@jax.named_scope("mlp")
def moe_decode(p, x, *, act: str, top_k: int, router_w=None,
               normalize_to_m: bool = False, top_k_traced=None,
               router=None):
    """Decode path (S==1): gather only the selected experts' weights so HBM
    traffic ∝ top-k experts (memory-roofline critical at 314B scale).

    With ``top_k_traced`` the gather covers the static ``top_k`` maximum and
    experts ranked beyond the traced count get weight 0 (>= E: all weight 1,
    the exact dense module) — variable expert budgets on one graph. A
    native ``router`` gathers its top ``top_k``; a traced count keeps the
    first ``count`` of them (>= top_k: the native weights exactly)."""
    B, S, D = x.shape
    rw = router_w if router_w is not None else p["router"]
    E = rw.shape[-1]
    k = min(top_k, E)
    with jax.named_scope("router"):
        logits = _router_logits(x, rw, router is not None)    # (B,1,E)
        if router is not None:
            s, sel = _native_scores(p, logits[:, 0], router)  # (B,E)
            idx = jax.lax.top_k(sel, k)[1]                    # (B,k)
            s_k = jnp.take_along_axis(s, idx, axis=-1)
            vals = _native_weights(s_k, True, router)
            if top_k_traced is not None:
                kept = jnp.arange(k)[None, :] < bcast_to(
                    jnp.clip(top_k_traced, 1, k), 2)
                full = bcast_to(is_full(top_k_traced, k), 2)
                vals = jnp.where(full, vals,
                                 _native_weights(s_k, kept, router))
        else:
            probs = jax.nn.softmax(logits, axis=-1)
            w = probs * E if normalize_to_m else probs
            vals, idx = jax.lax.top_k(w[:, 0], k)             # (B,k)
        if top_k_traced is not None and router is None:
            kt = jnp.clip(top_k_traced, 1, E)
            sel = jnp.arange(k)[None, :] < bcast_to(kt, 2)    # (B,k)
            full = bcast_to(is_full(top_k_traced, E), 2)
            vals = jnp.where(full, 1.0, jnp.where(sel, vals, 0.0))
    def take_w(name):
        # gather selected experts' weights, then dequant the gathered
        # slice only — HBM traffic stays ∝ top-k int8 expert rows
        w_sel = jnp.take(p[name], idx, axis=0)                # (B,k,D,Fe)
        sc = p.get(name + "_scale")
        if sc is None:
            return w_sel
        return (w_sel.astype(jnp.float32)
                * jnp.take(sc, idx, axis=0)[:, :, None, :]).astype(x.dtype)
    wi_sel, wo_sel = take_w("wi"), take_w("wo")
    h = jnp.einsum("bsd,bkdf->bkf", x, wi_sel)
    if "wg" in p:
        h = act_fn(act)(jnp.einsum("bsd,bkdf->bkf", x, take_w("wg"))) * h
    else:
        h = act_fn(act)(h)
    y = jnp.einsum("bkf,bkfd,bk->bd", h, wo_sel, vals.astype(h.dtype))
    y = y[:, None].astype(x.dtype)
    if "shared" in p:
        y = y + _dense_ffn(p["shared"], x, act)
    return y, RouteAux.zero()
