"""Plain reference of a qwen2 configuration: Qwen2 with ElastiFormer routers.

Straight ``jax.numpy`` in float32 at ``precision="highest"``, one whole
sequence at a time, no cache, no kernels, and nothing imported from the
program under test. It follows the published architecture (arXiv:2407.10671:
pre-norm RMSNorm blocks, GQA with q/k/v biases, RoPE, SwiGLU MLP, untied
LM head) and the routers as the configuration file states them
(arXiv:2411.15281, inference thresholding, section B.1):

* a token router before attention and one before the MLP: a token enters
  the module when ``sigmoid(h . w + b) > theta``, and the module's output
  is scaled by that sigmoid; a token that skips attention also writes no
  key or value, so later tokens do not attend to it;
* a head router: ``H * softmax(h W)``, the ``head_topk`` largest heads
  kept, each head's context scaled by its weight;
* the dense MLP split into ``E`` experts of ``F / E`` columns with an
  expert router ``E * softmax(h W)``; the ``expert_topk`` largest experts
  are summed, each scaled by its weight. Prompt tokens go through the
  admission's expert dispatch, which holds at most
  ``ceil(ceil(k * n / E * capacity_factor) / 4) * 4`` tokens per expert in
  each ``expert_seq_chunk`` of the prompt (the heaviest-weighted first,
  ties to the earlier token); generated tokens are dispatched alone;
* LoRA on q and v, ``(h A) B``, while the budget is below 1.

At a budget of 1.0 the configuration states the plain model (no router
acts). ``served_gaps`` compares served greedy tokens with this model's
logits: the gap ``max(logits) - logits[served]`` at each served position.

``control="fp8"`` computes every projection with float8 e4m3 operands
(weights scaled per output channel, activations per token, float32
accumulation): the next precision below the configuration's bfloat16.
``control="bf16"`` rounds the operands of every product, routers
included, and the residual stream between blocks to bfloat16 (float32
accumulation): the configuration's own precision, a witness of what its
rounding alone does to the served tokens.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
NEG = -1e30
F8_MAX = 448.0


def _q8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax, 1.0) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _b16(x, control):
    """x rounded to bfloat16 under the bf16 control, else x."""
    if control != "bf16":
        return x
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _mm(x, w, control, contract=1):
    """x (T, *in) @ w (*in, *out) over the ``contract`` leading axes of w."""
    w = w.astype(jnp.float32)
    if control == "fp8":
        n_in = contract
        x = _q8(x, tuple(range(x.ndim - n_in, x.ndim)))
        w = _q8(w, tuple(range(n_in)))
    x, w = _b16(x, control), _b16(w, control)
    return jnp.tensordot(x, w, axes=contract, precision=HI)


def _rdot(h, w, control):
    """A router's or adapter's product: float32, or bf16 operands under the
    bf16 control."""
    return jnp.dot(_b16(h, control), _b16(w.astype(jnp.float32), control),
                   precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x (T, n, Dh): rotate-half RoPE at absolute positions ``pos``."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _topk_mask(w, k):
    """Keep the k largest along the last axis (ties to the lower index)."""
    order = jnp.argsort(-w, axis=-1, stable=True)
    ranks = jnp.argsort(order, axis=-1, stable=True)
    return ranks < k


def _capacity_keep(sel, w, is_prompt, plen, k, E, factor, chunk):
    """Expert dispatch capacity over the prompt: (T, E) bool of the
    (token, expert) pairs that keep their expert. Generated tokens (and
    padding) are never dropped. Within each chunk of the prompt an expert
    holds its ``cap`` heaviest tokens."""
    T = sel.shape[0]
    n = jnp.minimum(plen, chunk)
    cap = jnp.ceil(jnp.ceil(k * n / E * factor - 1e-6) / 4.0) * 4.0
    cap = jnp.minimum(n, jnp.maximum(4.0, cap))
    pos = jnp.arange(T)
    blk = jnp.where(is_prompt, pos // jnp.maximum(n, 1), -1)
    same = (blk[:, None] == blk[None, :]) & is_prompt[:, None] \
        & is_prompt[None, :]                                  # (T, T)
    ws = jnp.where(sel, w, -jnp.inf)                          # (T, E)
    ahead = (ws[None, :, :] > ws[:, None, :]) | (
        (ws[None, :, :] == ws[:, None, :])
        & (pos[None, :, None] < pos[:, None, None]))          # (t, t', E)
    ahead &= sel[None, :, :] & same[:, :, None]
    rank = jnp.sum(ahead, axis=1)                             # (T, E)
    return jnp.where(is_prompt[:, None], rank < cap, True)


def _layer(x, lp, lr, *, c, pos, valid, plen, route, control):
    """One block. x (T, D) f32; lp/lr: this layer's weights and routers."""
    T, D = x.shape
    H, K, Dh, eps = c["H"], c["K"], c["Dh"], c["eps"]
    f32 = lambda a: a.astype(jnp.float32)
    h = _rms(x, lp["norm1"]["scale"], eps)
    a = lp["attn"]
    q = _mm(h, a["wq"], control) + f32(a["bq"])               # (T, H, Dh)
    k = _mm(h, a["wk"], control) + f32(a["bk"])               # (T, K, Dh)
    v = _mm(h, a["wv"], control) + f32(a["bv"])
    kv_ok = valid
    if route is not None:
        lq, lv = lr["lora"]["q"], lr["lora"]["v"]
        q = q + _rdot(_rdot(h, lq["a"], control), lq["b"],
                      control).reshape(T, H, Dh)
        v = v + _rdot(_rdot(h, lv["a"], control), lv["b"],
                      control).reshape(T, K, Dh)
        la = _rdot(h, lr["tok_mixer"]["w"], control) + lr["tok_mixer"]["b"]
        keep_a = la > route["theta_logit"]
        wa = keep_a * jax.nn.sigmoid(la)
        kv_ok = kv_ok & keep_a
    q, k = _rope(q, pos, c["rope_theta"]), _rope(k, pos, c["rope_theta"])
    g = H // K
    qg = q.reshape(T, K, g, Dh)
    s = jnp.einsum("tkgd,skd->kgts", _b16(qg, control), _b16(k, control),
                   precision=HI) / math.sqrt(Dh)
    allow = (pos[None, :] <= pos[:, None]) & kv_ok[None, :]   # (t, s)
    s = jnp.where(allow[None, None], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("kgts,skd->tkgd", _b16(p, control), _b16(v, control),
                     precision=HI).reshape(T, H, Dh)
    if route is not None:
        hl = _rdot(h, lr["head"]["w"], control)
        hwt = jax.nn.softmax(hl, -1) * H
        ctx = ctx * (hwt * _topk_mask(hwt, route["head_topk"]))[..., None]
    y = _mm(ctx, a["wo"], control, contract=2)
    if route is not None:
        y = y * wa[:, None]
    x = x + y

    h = _rms(x, lp["norm2"]["scale"], eps)
    m = lp["mlp"]
    act = jax.nn.silu(_mm(h, m["wg"], control)) * _mm(h, m["wi"], control)
    if route is not None and c["E"]:
        E = c["E"]
        el = _rdot(h, lr["expert"]["w"], control)
        we = jax.nn.softmax(el, -1) * E
        sel = _topk_mask(we, route["expert_topk"])
        is_prompt = (pos < plen) & valid
        ok = _capacity_keep(sel, we, is_prompt, plen, route["expert_topk"],
                            E, c["capacity_factor"], c["moe_chunk"])
        coef = jnp.where(sel & ok, we, 0.0)                   # (T, E)
        act = (act.reshape(T, E, -1) * coef[..., None]).reshape(T, -1)
    y = _mm(act, m["wo"], control)
    if route is not None:
        lm = _rdot(h, lr["tok_mlp"]["w"], control) + lr["tok_mlp"]["b"]
        y = y * ((lm > route["theta_logit"]) * jax.nn.sigmoid(lm))[:, None]
    return _b16(x + y, control)


def consts(cfg: dict) -> dict:
    el = cfg["elastic"]
    return {"H": cfg["num_attention_heads"], "K": cfg["num_key_value_heads"],
            "Dh": cfg["hidden_size"] // cfg["num_attention_heads"],
            "eps": cfg["rms_norm_eps"], "rope_theta": cfg["rope_theta"],
            "E": el.get("mlp_n_experts") or 0,
            "capacity_factor": el.get("expert_capacity_factor", 1.25),
            "moe_chunk": el.get("expert_seq_chunk", 512)}


def route_for(cfg: dict, budget: float):
    """The router settings the configuration states at ``budget``, or None
    where no router acts (the plain model)."""
    b = cfg["elastic"]["budgets"][f"{float(budget):.1f}"]
    if not b["routed"]:
        return None
    th = cfg["elastic"]["theta"]
    return {"theta_logit": math.log(th / (1.0 - th)),
            "head_topk": b["head_topk"], "expert_topk": b.get("expert_topk")}


@partial(jax.jit, static_argnames=("c", "route", "control", "block"))
def _gaps(params, rp, tokens, targets, plen, n, *, c, route, control, block):
    """tokens (T,) with n real; targets (T,): the token served after each
    position, -1 where none. Returns (gap at each position of the target in
    the reference's logits, gap of the control's own first token, or of the
    target again without a control), float32 (T,) each."""
    T = tokens.shape[0]
    pos = jnp.arange(T)
    valid = pos < n
    route = None if route is None else dict(route)
    c = dict(c)
    x = params["embed"][tokens].astype(jnp.float32)

    def body(x, layer):
        lp, lr = layer
        return _layer(x, lp, lr, c=c, pos=pos, valid=valid, plen=plen,
                      route=route, control=None), None

    def body_ctl(x, layer):
        lp, lr = layer
        return _layer(x, lp, lr, c=c, pos=pos, valid=valid, plen=plen,
                      route=route, control=control), None

    layers = (params["scan"][0], rp["scan"][0])
    h = jax.lax.scan(body, x, layers)[0]
    h = _rms(h, params["final_norm"]["scale"], c["eps"])
    hc = None
    if control is not None:
        hc = jax.lax.scan(body_ctl, x, layers)[0]
        hc = _rms(hc, params["final_norm"]["scale"], c["eps"])
    W = params["lm_head"]

    def one(args):
        hb, hcb, tb = args
        lg = _mm(hb, W, None)
        top = jnp.max(lg, -1)
        at = jnp.take_along_axis(lg, jnp.maximum(tb, 0)[:, None], -1)[:, 0]
        gap = jnp.where(tb >= 0, top - at, 0.0)
        if hcb is None:
            return gap, gap
        pick = jnp.argmax(_mm(hcb, W, control), -1)
        cgap = top - jnp.take_along_axis(lg, pick[:, None], -1)[:, 0]
        return gap, jnp.where(tb >= 0, cgap, 0.0)

    nb = T // block
    hb = h.reshape(nb, block, -1)
    hcb = None if hc is None else hc.reshape(nb, block, -1)
    g, cg = jax.lax.map(one, (hb, hcb, targets.reshape(nb, block)))
    return g.reshape(T), cg.reshape(T)


def served_gaps(params, rp, cfg: dict, budget: float, prompt, output,
                length: int, control=None, block: int = 256):
    """Reference over ``prompt + output[:-1]`` padded to ``length``; returns
    (gaps of the served tokens, gaps of the control's tokens), numpy float32
    arrays of ``len(output)`` each (the second is the first again without
    a control)."""
    import numpy as np
    prompt = np.asarray(prompt, np.int32)
    output = np.asarray(output, np.int32)
    n = prompt.size + output.size - 1
    if n > length:
        raise ValueError(f"sequence of {n} tokens exceeds {length}")
    toks = np.zeros(length, np.int32)
    toks[:prompt.size] = prompt
    toks[prompt.size:n] = output[:-1]
    tgt = np.full(length, -1, np.int32)
    tgt[prompt.size - 1:n] = output
    route = route_for(cfg, budget)
    block = math.gcd(length, block)
    g, cg = _gaps(params, rp, jnp.asarray(toks), jnp.asarray(tgt),
                  jnp.int32(prompt.size), jnp.int32(n),
                  c=tuple(sorted(consts(cfg).items())),
                  route=None if route is None else tuple(sorted(route.items())),
                  control=control, block=block)
    sl = slice(prompt.size - 1, n)
    return np.asarray(g)[sl], np.asarray(cg)[sl]
