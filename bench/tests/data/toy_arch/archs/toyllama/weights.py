"""Weights of a toyllama configuration, made on the device from the seed
by ``archs.make``."""
import math

import jax.numpy as jnp

import archs


def dims(conf: dict) -> dict:
    D, H = conf["hidden_size"], conf["num_attention_heads"]
    return {"L": conf["num_hidden_layers"], "D": D, "H": H,
            "K": conf["num_key_value_heads"], "Dh": D // H,
            "F": conf["intermediate_size"], "V": conf["vocab_size"],
            "E": 0, "r": 0}


def _spec(conf: dict):
    d = dims(conf)
    L, D, H, K, Dh, F, V = (d[k] for k in "L D H K Dh F V".split())
    bf, f32 = jnp.dtype(conf["torch_dtype"]), jnp.float32
    out = [(("params", "embed"), (V, D), bf, ("normal", 0.02)),
           (("params", "final_norm", "scale"), (D,), f32, ("norm", None))]
    lay = [
        (("attn", "wq"), (L, D, H, Dh), bf, ("normal", 1 / math.sqrt(D))),
        (("attn", "wk"), (L, D, K, Dh), bf, ("normal", 1 / math.sqrt(D))),
        (("attn", "wv"), (L, D, K, Dh), bf, ("normal", 1 / math.sqrt(D))),
        (("attn", "wo"), (L, H, Dh, D), bf, ("normal", 1 / math.sqrt(H * Dh))),
        (("mlp", "wi"), (L, D, F), bf, ("normal", 1 / math.sqrt(D))),
        (("mlp", "wg"), (L, D, F), bf, ("normal", 1 / math.sqrt(D))),
        (("mlp", "wo"), (L, F, D), bf, ("normal", 1 / math.sqrt(F))),
        (("norm1", "scale"), (L, D), f32, ("norm", None)),
        (("norm2", "scale"), (L, D), f32, ("norm", None)),
    ]
    out += [(("params", "scan", 0) + p, s, t, i) for p, s, t, i in lay]
    out += [(("rp", "scan", 0, "tok_mlp", "w"), (L, D), f32,
             ("normal", 1 / math.sqrt(D))),
            (("rp", "scan", 0, "tok_mlp", "b"), (L,), f32, ("normal", 0.1))]
    return out


def make_weights(conf: dict, seed: int, shardings=None):
    return archs.make(_spec(conf), seed, shardings)
