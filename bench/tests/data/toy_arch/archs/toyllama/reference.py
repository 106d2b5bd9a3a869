"""Plain reference of a toyllama configuration at budget 1.0 (no router
acts): float32 at ``precision="highest"``, one whole sequence, no cache,
nothing from the program under test."""
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    T, half = x.shape[0], x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _logits(params, tokens, H, K, eps, theta):
    f = lambda a: a.astype(jnp.float32)
    x = f(params["embed"])[tokens]
    T = x.shape[0]
    causal = jnp.tril(jnp.ones((T, T), bool))

    def layer(x, lp):
        a, m = lp["attn"], lp["mlp"]
        h = _rms(x, lp["norm1"]["scale"], eps)
        q = _rope(jnp.einsum("td,dhk->thk", h, f(a["wq"]), precision=HI),
                  theta)
        k = _rope(jnp.einsum("td,dhk->thk", h, f(a["wk"]), precision=HI),
                  theta)
        v = jnp.einsum("td,dhk->thk", h, f(a["wv"]), precision=HI)
        Dh = q.shape[-1]
        qg = q.reshape(T, K, H // K, Dh)
        s = jnp.einsum("tkgd,skd->kgts", qg, k, precision=HI) / math.sqrt(Dh)
        p = jax.nn.softmax(jnp.where(causal, s, -1e30), -1)
        ctx = jnp.einsum("kgts,skd->tkgd", p, v, precision=HI)
        x = x + jnp.einsum("thk,hkd->td", ctx.reshape(T, H, Dh), f(a["wo"]),
                           precision=HI)
        h = _rms(x, lp["norm2"]["scale"], eps)
        act = jax.nn.silu(jnp.dot(h, f(m["wg"]), precision=HI)) \
            * jnp.dot(h, f(m["wi"]), precision=HI)
        return x + jnp.dot(act, f(m["wo"]), precision=HI), None

    x = jax.lax.scan(layer, x, params["scan"][0])[0]
    x = _rms(x, params["final_norm"]["scale"], eps)
    return jnp.dot(x, f(params["embed"]).T, precision=HI)


def served_gaps(params, rp, conf, budget, prompt, output, length,
                control=None):
    """Gaps ``max(logits) - logits[served]`` of the served tokens, twice
    (this architecture has no control)."""
    if control is not None or float(budget) != 1.0:
        raise ValueError("toyllama's reference serves budget 1.0 only")
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(output, np.int32)[:-1]])
    lg = np.asarray(_logits(params, jnp.asarray(seq),
                            conf["num_attention_heads"],
                            conf["num_key_value_heads"],
                            conf["rms_norm_eps"], conf["rope_theta"]))
    rows = lg[len(prompt) - 1:]
    g = (rows.max(-1) - rows[np.arange(len(output)), output]) \
        .astype(np.float32)
    return g, g
