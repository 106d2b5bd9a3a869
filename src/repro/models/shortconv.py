"""LFM2 gated short convolution (the ``conv`` layer kind).
[hf:LiquidAI/LFM2-8B-A1B]

``in_proj`` (D -> 3D, no bias) splits into ``B | C | x``; ``Bx = B * x``
runs through a depthwise causal convolution of ``L = cfg.conv_kernel`` taps
(``conv_L_cache``, no bias), and the mixer's output is
``out_proj(C * conv(Bx))``:

    y_t = sum_{j=0..L-1} w_j * Bx_{t-L+1+j}        (Bx_{<0} = 0)

A serving slot's state is its last ``L - 1`` Bx rows (``{"bx": (B, L-1,
D)}``): prefill returns them, decode shifts the new row in.

ElastiFormer token routing (``keep_mask`` / ``write``): a skipped token
never enters the convolution's history — a kept token's window is the
previous ``L - 1`` KEPT tokens, and decode leaves the state unchanged for a
skipped token — the pass-through semantics of the ssm/rglru mixers.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models import quant
from repro.models.layers import dense_init, dtype_of


def conv_init(key, cfg):
    D, L, dt = cfg.d_model, cfg.conv_kernel, dtype_of(cfg)
    ks = jax.random.split(key, 3)
    return {
        "in_proj": dense_init(ks[0], D, 3 * D, dt).reshape(D, 3, D),
        "conv_w": (jax.random.normal(ks[1], (L, D), jnp.float32)
                   / math.sqrt(L)).astype(dt),
        "out_proj": dense_init(ks[2], D, D, dt),
    }


def _gates(p, h):
    """(B, C, Bx) from the input projection, each (B, S, D)."""
    bcx = jnp.einsum("bsd,dke->bske", h,
                     quant.maybe_dequant(p, "in_proj", h.dtype))
    b, c, x = bcx[:, :, 0], bcx[:, :, 1], bcx[:, :, 2]
    return c, b * x


def _prev_kept(keep):
    """(B, S+1) index of the last kept position strictly before each
    position (position S: the last kept one overall); -1 where none."""
    B, S = keep.shape
    m = jnp.where(keep, jnp.arange(S, dtype=jnp.int32), -1)
    cm = jax.lax.cummax(m, axis=1)
    return jnp.concatenate([jnp.full((B, 1), -1, jnp.int32), cm], axis=1)


def _rows(bx, idx):
    """bx rows at (B, n) positions ``idx``; zeros where idx < 0."""
    r = jnp.take_along_axis(bx, jnp.maximum(idx, 0)[..., None], axis=1)
    return jnp.where((idx >= 0)[..., None], r, jnp.zeros((), bx.dtype))


@jax.named_scope("conv")
def conv_apply(p, h, cfg, keep_mask=None):
    """Full sequence. h: (B,S,D) -> (y (B,S,D), state {"bx": (B,L-1,D)})."""
    B, S = h.shape[:2]
    L = cfg.conv_kernel
    c, bx = _gates(p, h)
    w = p["conv_w"].astype(jnp.float32)
    if keep_mask is None:
        pos = jnp.broadcast_to(jnp.arange(S + 1, dtype=jnp.int32) - 1,
                               (B, S + 1))
        prev = [pos - j for j in range(L - 1)]    # j+1 positions back
    else:
        p1 = _prev_kept(keep_mask)
        prev = [p1]
        for _ in range(L - 2):
            q = prev[-1]
            prev.append(jnp.where(
                q >= 0, jnp.take_along_axis(p1, jnp.maximum(q, 0), axis=1),
                -1))
    y = bx.astype(jnp.float32) * w[L - 1]
    for j, q in enumerate(prev):
        y = y + _rows(bx, q[:, :S]).astype(jnp.float32) * w[L - 2 - j]
    out = (c * y.astype(h.dtype)) @ quant.maybe_dequant(p, "out_proj",
                                                         h.dtype)
    # oldest first: the rows L-1, ..., 1 kept positions back from the end
    state = jnp.stack([_rows(bx, q[:, S:])[:, 0] for q in prev[::-1]], 1)
    return out, {"bx": state}


@jax.named_scope("conv")
def conv_decode(p, h, cache, cfg, write=None):
    """One token. h: (B,1,D); cache {"bx": (B,L-1,D)}. ``write`` (B,)
    bool: rows whose token enters the history (None = all)."""
    c, bx = _gates(p, h)
    st = cache["bx"]
    win = jnp.concatenate([st.astype(bx.dtype), bx], axis=1)   # (B,L,D)
    y = jnp.einsum("bld,ld->bd", win.astype(jnp.float32),
                   p["conv_w"].astype(jnp.float32))[:, None]
    out = (c * y.astype(h.dtype)) @ quant.maybe_dequant(p, "out_proj",
                                                         h.dtype)
    new = win[:, 1:].astype(st.dtype)
    if write is not None:
        new = jnp.where(write[:, None, None], new, st)
    return out, {"bx": new}


def conv_cache_init(cfg, batch: int):
    return {"bx": jnp.zeros((batch, cfg.conv_kernel - 1, cfg.d_model),
                            dtype_of(cfg))}
