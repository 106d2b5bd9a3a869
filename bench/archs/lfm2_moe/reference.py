"""Plain reference of an lfm2_moe configuration: LFM2-8B-A1B's forward.

Straight ``jax.numpy`` in float32 at ``precision="highest"``, one whole
sequence at a time, no cache, no kernels, and nothing imported from the
program under test. It follows the Hugging Face ``Lfm2Moe`` model
(https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json):

* pre-norm blocks, ``h = x + mixer(RMSNorm(x))``, ``out = h +
  ffn(RMSNorm(h))``, RMSNorm eps ``norm_eps``;
* the ``conv`` mixer: ``in_proj`` (no bias) splits into ``B | C | x``,
  ``Bx = B * x``, a depthwise causal convolution of ``conv_L_cache`` taps
  ``y_t = sum_j w_j Bx_{t-L+1+j}`` (``Bx_{<0} = 0``, no bias), and
  ``out_proj(C * y)``;
* the ``full_attention`` mixer: GQA without biases, an RMSNorm over
  head_dim on q and on k before rotate-half RoPE, scale ``1/sqrt(head_dim)``;
* the MLP: a dense SwiGLU in the first ``num_dense_layers`` layers, then
  ``num_experts`` SwiGLU experts: scores ``s = sigmoid(h W_r)`` in
  float32, the ``num_experts_per_tok`` largest of ``s + expert_bias``
  chosen, each weighted ``s_e / (sum of the chosen s + 1e-6)``: the
  published ``use_expert_bias`` and ``norm_topk_prob`` true and
  ``routed_scaling_factor`` 1, the only values implemented. Every expert
  is applied to every token and weighted 0 where not chosen: no capacity,
  no token dropped;
* a final RMSNorm, then logits ``h E^T``.

Departures from the published model: random weights from the seed, the
``expert_bias`` buffer drawn from the seed too, the embedding tied (not in
the config; inferred from the published 8.3 B parameters), and the layers
cut to ``num_hidden_layers``. Only budget 1.0 is implemented, where the
configuration states the plain model (no router acts).

``served_gaps`` compares served greedy tokens with this model's logits:
the gap ``max(logits) - logits[served]`` at each served position.
``control="fp8"`` computes every projection with float8 e4m3 operands
(weights scaled per output channel, activations per token, float32
accumulation): the next precision below the configuration's bfloat16.
``control="bf16"`` rounds the operands of every product, the routers and
the convolution included, and the residual stream between blocks to
bfloat16 (float32 accumulation): the configuration's own precision.
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
        x = _q8(x, tuple(range(x.ndim - contract, x.ndim)))
        w = _q8(w, tuple(range(contract)))
    x, w = _b16(x, control), _b16(w, control)
    return jnp.tensordot(x, w, axes=contract, precision=HI)


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


def _conv(h, m, control):
    """The gated short convolution over the whole sequence."""
    T = h.shape[0]
    bcx = _mm(h, m["in_proj"], control)                      # (T, 3, D)
    b, c, x = bcx[:, 0], bcx[:, 1], bcx[:, 2]
    bx = _b16(b * x, control)
    w = _b16(m["conv_w"].astype(jnp.float32), control)       # (L, D)
    L = w.shape[0]
    pad = jnp.concatenate([jnp.zeros((L - 1, bx.shape[1])), bx], 0)
    y = sum(pad[j:j + T] * w[j] for j in range(L))
    return _mm(_b16(c * y, control), m["out_proj"], control)


def _attention(h, a, c, pos, valid, control):
    T = h.shape[0]
    H, K, Dh, eps = c["H"], c["K"], c["Dh"], c["eps"]
    q = _rms(_mm(h, a["wq"], control), a["q_norm"]["scale"], eps)
    k = _rms(_mm(h, a["wk"], control), a["k_norm"]["scale"], eps)
    v = _mm(h, a["wv"], control)
    q, k = _rope(q, pos, c["rope_theta"]), _rope(k, pos, c["rope_theta"])
    g = H // K
    qg = q.reshape(T, K, g, Dh)
    s = jnp.einsum("tkgd,skd->kgts", _b16(qg, control), _b16(k, control),
                   precision=HI) / math.sqrt(Dh)
    allow = (pos[None, :] <= pos[:, None]) & valid[None, :]
    s = jnp.where(allow[None, None], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("kgts,skd->tkgd", _b16(p, control), _b16(v, control),
                     precision=HI).reshape(T, H, Dh)
    return _mm(ctx, a["wo"], control, contract=2)


def _swiglu(h, wi, wg, wo, control):
    return _mm(jax.nn.silu(_mm(h, wg, control)) * _mm(h, wi, control), wo,
               control)


def _experts(h, m, c, control):
    """Every expert on every token, weighted by the router (0 where not
    chosen), one expert at a time."""
    logits = jnp.dot(_b16(h, control), _b16(m["router"], control),
                     precision=HI)                           # (T, E)
    s = jax.nn.sigmoid(logits)
    sel = s + m["expert_bias"]
    order = jnp.argsort(-sel, axis=-1, stable=True)
    chosen = jnp.argsort(order, axis=-1, stable=True) < c["top_k"]
    w = jnp.where(chosen, s, 0.0)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)

    def one(acc, e):
        wi, wg, wo, we = e
        return acc + _swiglu(h, wi, wg, wo, control) * we[:, None], None

    return jax.lax.scan(one, jnp.zeros_like(h),
                        (m["wi"], m["wg"], m["wo"], w.T))[0]


def _layer(x, lp, *, c, pos, valid, control):
    """One block. x (T, D) f32; lp: this layer's weights."""
    eps = c["eps"]
    h = _rms(x, lp["norm1"]["scale"], eps)
    if "attn" in lp:
        x = x + _attention(h, lp["attn"], c, pos, valid, control)
    else:
        x = x + _conv(h, lp["mixer"], control)
    h = _rms(x, lp["norm2"]["scale"], eps)
    m = lp["mlp"]
    if "router" in m:
        y = _experts(h, m, c, control)
    else:
        y = _swiglu(h, m["wi"], m["wg"], m["wo"], control)
    return _b16(x + y, control)


def consts(cfg: dict) -> dict:
    return {"H": cfg["num_attention_heads"], "K": cfg["num_key_value_heads"],
            "Dh": cfg["hidden_size"] // cfg["num_attention_heads"],
            "eps": cfg["norm_eps"], "rope_theta": float(cfg["rope_theta"]),
            "top_k": cfg["num_experts_per_tok"]}


def _check_kinds(params, cfg: dict) -> None:
    """The layers the arrays hold are the configuration's, in order."""
    want = ["attn" if k == "full_attention" else "conv"
            for k in cfg["layer_types"][:cfg["num_hidden_layers"]]]
    kind = lambda lp: "attn" if "attn" in lp else "conv"
    got = [kind(lp) for lp in params.get("lead", [])]
    reps = params["scan"][0]["norm1"]["scale"].shape[0] \
        if params["scan"] else 0
    got += [kind(lp) for _ in range(reps) for lp in params["scan"]]
    got += [kind(lp) for lp in params["tail"]]
    if got != want:
        raise ValueError(f"layer kinds {got} are not the configuration's "
                         f"{want}")


def _stack(params, tokens, n, c, control):
    """The final hidden states (T, D) of ``tokens`` (T,), n of them real:
    the leading layers, the scanned period, the tail, the final norm."""
    T = tokens.shape[0]
    pos = jnp.arange(T)
    lay = partial(_layer, c=c, pos=pos, valid=pos < n, control=control)
    x = params["embed"][tokens].astype(jnp.float32)
    for lp in params.get("lead", []):
        x = lay(x, lp)

    def body(x, period):
        for lp in period:
            x = lay(x, lp)
        return x, None

    if params["scan"]:
        x = jax.lax.scan(body, x, params["scan"])[0]
    for lp in params["tail"]:
        x = lay(x, lp)
    return _rms(x, params["final_norm"]["scale"], c["eps"])


def logits(params, cfg: dict, tokens):
    """Logits (T, V) of the whole sequence ``tokens`` (T,)."""
    _check_kinds(params, cfg)
    tokens = jnp.asarray(tokens, jnp.int32)
    h = _stack(params, tokens, tokens.shape[0], consts(cfg), None)
    return _mm(h, params["embed"].T, None)


@partial(jax.jit, static_argnames=("c", "control", "block"))
def _gaps(params, tokens, targets, n, *, c, control, block):
    """tokens (T,) with n real; targets (T,): the token served after each
    position, -1 where none. Returns (gap at each position of the target in
    the reference's logits, gap of the control's own first token, or of the
    target again without a control), float32 (T,) each."""
    T = tokens.shape[0]
    c = dict(c)
    h = _stack(params, tokens, n, c, None)
    hc = None if control is None else _stack(params, tokens, n, c, control)
    W = params["embed"].T                                    # tied

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
    a control). ``rp`` is unused: at budget 1.0 no router acts."""
    import numpy as np
    del rp
    if cfg["elastic"]["budgets"][f"{float(budget):.1f}"]["routed"]:
        raise NotImplementedError("the lfm2_moe reference has no routers: "
                                  "it serves budget 1.0 only")
    _check_kinds(params, cfg)
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
    block = math.gcd(length, block)
    g, cg = _gaps(params, jnp.asarray(toks), jnp.asarray(tgt), jnp.int32(n),
                  c=tuple(sorted(consts(cfg).items())), control=control,
                  block=block)
    sl = slice(prompt.size - 1, n)
    return np.asarray(g)[sl], np.asarray(cg)[sl]
