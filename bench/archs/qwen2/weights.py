"""Weights of a qwen2 configuration, made on the device from ``--seed``.

The benchmark makes the weights itself, in the layout the serving engine
takes (``{"scan": [per-layer leaves stacked over layers], "tail": []}``),
so that the plain reference in ``reference.py`` reads the very same arrays
without taking anything the program under test made. ``archs.make`` makes
them in one jitted call whose only argument is the key.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

import archs


def dims(cfg: dict) -> dict:
    """Shapes of a configuration file, by the Hugging Face key names."""
    D = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    return {"L": cfg["num_hidden_layers"], "D": D, "H": H,
            "K": cfg["num_key_value_heads"], "Dh": D // H,
            "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "E": cfg["elastic"]["mlp_n_experts"] or 0,
            "r": cfg["elastic"]["lora_rank"]}


def _spec(cfg: dict):
    """[(path, shape, dtype, init)] for every leaf, in a fixed order."""
    d = dims(cfg)
    L, D, H, K, Dh, F, V, E, r = (d[k] for k in "L D H K Dh F V E r".split())
    bf, f32 = jnp.dtype(cfg["torch_dtype"]), jnp.float32
    out = [
        (("params", "embed"), (V, D), bf, ("normal", 0.02)),
        (("params", "lm_head"), (D, V), bf, ("normal", 1 / math.sqrt(D))),
        (("params", "final_norm", "scale"), (D,), f32, ("norm", None)),
    ]
    lay = [
        (("attn", "wq"), (L, D, H, Dh), bf, ("normal", 1 / math.sqrt(D))),
        (("attn", "wk"), (L, D, K, Dh), bf, ("normal", 1 / math.sqrt(D))),
        (("attn", "wv"), (L, D, K, Dh), bf, ("normal", 1 / math.sqrt(D))),
        (("attn", "wo"), (L, H, Dh, D), bf, ("normal", 1 / math.sqrt(H * Dh))),
        (("attn", "bq"), (L, H, Dh), bf, ("normal", 0.02)),
        (("attn", "bk"), (L, K, Dh), bf, ("normal", 0.02)),
        (("attn", "bv"), (L, K, Dh), bf, ("normal", 0.02)),
        (("mlp", "wi"), (L, D, F), bf, ("normal", 1 / math.sqrt(D))),
        (("mlp", "wg"), (L, D, F), bf, ("normal", 1 / math.sqrt(D))),
        (("mlp", "wo"), (L, F, D), bf, ("normal", 1 / math.sqrt(F))),
        (("norm1", "scale"), (L, D), f32, ("norm", None)),
        (("norm2", "scale"), (L, D), f32, ("norm", None)),
    ]
    out += [(("params", "scan", 0) + p, s, t, i) for p, s, t, i in lay]
    rs = 1 / math.sqrt(D)
    rlay = [
        (("tok_mixer", "w"), (L, D), f32, ("normal", rs)),
        (("tok_mixer", "b"), (L,), f32, ("normal", 0.1)),
        (("tok_mlp", "w"), (L, D), f32, ("normal", rs)),
        (("tok_mlp", "b"), (L,), f32, ("normal", 0.1)),
        (("head", "w"), (L, D, H), f32, ("normal", rs)),
        (("lora", "q", "a"), (L, D, r), f32, ("normal", rs)),
        (("lora", "q", "b"), (L, r, H * Dh), f32, ("normal", 0.02)),
        (("lora", "v", "a"), (L, D, r), f32, ("normal", rs)),
        (("lora", "v", "b"), (L, r, K * Dh), f32, ("normal", 0.02)),
    ]
    if E:
        rlay.append((("expert", "w"), (L, D, E), f32, ("normal", rs)))
    out += [(("rp", "scan", 0) + p, s, t, i) for p, s, t, i in rlay]
    return out


def make_weights(cfg: dict, seed: int, shardings=None):
    """(params, router params) from the seed, placed by ``shardings`` where
    given, else on the default device."""
    return archs.make(_spec(cfg), seed, shardings)
