"""Weights of an lfm2_moe configuration, made on the device from ``--seed``.

The benchmark makes the weights itself, in the layout the serving engine
takes, so that the plain reference in ``reference.py`` reads the very same
arrays without taking any array the program under test made. The layer
stack is laid out as the program's ``build_pattern`` lays it out: the
leading dense layers one by one under ``"lead"``, then each position of
the repeating period stacked over its repeats under ``"scan"``, then any
layers after the last repeat under ``"tail"`` (the reference checks the
kinds it walks against ``layer_types``). Leaf n is drawn from
``fold_in(key, n)`` in one jitted call whose only argument is the key.
"""
from __future__ import annotations

import math
from pathlib import Path

import jax
import jax.numpy as jnp

import archs

def dims(cfg: dict) -> dict:
    """Shapes of a configuration file, by the Hugging Face key names. ``L``
    counts every layer and ``L_attn`` the attention layers among them: an
    attention count reads ``L_attn``."""
    D = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    L = cfg["num_hidden_layers"]
    return {"L": L, "L_attn": cfg["layer_types"][:L].count("full_attention"),
            "D": D, "H": H, "K": cfg["num_key_value_heads"],
            "Dh": D // H, "F": cfg["intermediate_size"],
            "V": cfg["vocab_size"], "E": cfg["num_experts"],
            "k": cfg["num_experts_per_tok"],
            "Fe": cfg["moe_intermediate_size"],
            "L_moe": max(0, L - cfg["num_dense_layers"]),
            "Lc": cfg["conv_L_cache"], "r": cfg["elastic"]["lora_rank"]}


def layout(cfg: dict):
    """(lead, period, repeats, tail) of the layer stack, each layer as
    (kind, moe), as the program's ``build_pattern`` lays it out."""
    from repro.models.model import build_pattern
    program = archs.load(cfg, Path(__file__).resolve().parents[2]).program
    pat = build_pattern(*program.model_config(cfg, {}))
    ent = lambda ps: [(e.kind, e.moe) for e in ps]
    return ent(pat.lead), ent(pat.period), pat.P, ent(pat.tail)


def _layer(d: dict, kind: str, moe: bool, bf):
    """[(path, shape, dtype, init)] of one layer's params and routers."""
    D, H, K, Dh, F, E, Fe, Lc, r = (d[k] for k in
                                    "D H K Dh F E Fe Lc r".split())
    f32, s = jnp.float32, lambda n: ("normal", 1 / math.sqrt(n))
    p = [(("norm1", "scale"), (D,), f32, ("norm", None))]
    if kind == "attn":
        p += [(("attn", "wq"), (D, H, Dh), bf, s(D)),
              (("attn", "wk"), (D, K, Dh), bf, s(D)),
              (("attn", "wv"), (D, K, Dh), bf, s(D)),
              (("attn", "wo"), (H, Dh, D), bf, s(H * Dh)),
              (("attn", "q_norm", "scale"), (Dh,), f32, ("norm", None)),
              (("attn", "k_norm", "scale"), (Dh,), f32, ("norm", None))]
    else:
        p += [(("mixer", "in_proj"), (D, 3, D), bf, s(D)),
              (("mixer", "conv_w"), (Lc, D), bf, s(Lc)),
              (("mixer", "out_proj"), (D, D), bf, s(D))]
    p.append((("norm2", "scale"), (D,), f32, ("norm", None)))
    if moe:
        p += [(("mlp", "router"), (D, E), f32, s(D)),
              (("mlp", "expert_bias"), (E,), f32, ("normal", 0.1)),
              (("mlp", "wi"), (E, D, Fe), bf, s(D)),
              (("mlp", "wg"), (E, D, Fe), bf, s(D)),
              (("mlp", "wo"), (E, Fe, D), bf, s(Fe))]
    else:
        p += [(("mlp", "wi"), (D, F), bf, s(D)),
              (("mlp", "wg"), (D, F), bf, s(D)),
              (("mlp", "wo"), (F, D), bf, s(F))]
    rt = [(("tok_mixer", "w"), (D,), f32, s(D)),
          (("tok_mixer", "b"), (), f32, ("normal", 0.1))]
    if kind == "attn":
        rt += [(("head", "w"), (D, H), f32, s(D)),
               (("lora", "q", "a"), (D, r), f32, s(D)),
               (("lora", "q", "b"), (r, H * Dh), f32, ("normal", 0.02)),
               (("lora", "v", "a"), (D, r), f32, s(D)),
               (("lora", "v", "b"), (r, K * Dh), f32, ("normal", 0.02))]
    rt += [(("tok_mlp", "w"), (D,), f32, s(D)),
           (("tok_mlp", "b"), (), f32, ("normal", 0.1))]
    return p, rt


def _spec(cfg: dict):
    """[(path, shape, dtype, init)] for every leaf, in a fixed order."""
    d = dims(cfg)
    bf = jnp.dtype(cfg["torch_dtype"])
    out = [(("params", "embed"), (d["V"], d["D"]), bf, ("normal", 0.02)),
           (("params", "final_norm", "scale"), (d["D"],), jnp.float32,
            ("norm", None))]
    lead, period, reps, tail = layout(cfg)
    for part, ents, stack in (("lead", lead, None), ("scan", period, reps),
                              ("tail", tail, None)):
        for j, (kind, moe) in enumerate(ents):
            p, rt = _layer(d, kind, moe, bf)
            for tree, leaves in (("params", p), ("rp", rt)):
                out += [((tree, part, j) + path,
                         shape if stack is None else (stack,) + shape, t, i)
                        for path, shape, t, i in leaves]
    return out


def _tree(key, spec, has_lead: bool):
    """The two trees ``spec`` lists, leaf n drawn from ``fold_in(key, n)``."""
    empty = lambda: {"scan": [], "tail": [], **({"lead": []} if has_lead
                                                else {})}
    trees = {"params": empty(), "rp": empty()}
    base = jax.random.wrap_key_data(key, impl="threefry2x32")
    for n, (path, shape, dtype, (kind, std)) in enumerate(spec):
        x = jax.random.normal(jax.random.fold_in(base, n), shape, jnp.float32)
        x = 1.0 + 0.1 * x if kind == "norm" else x * std
        node = trees
        for p, nxt in zip(path[:-1], path[1:]):
            if isinstance(p, int):
                while len(node) <= p:
                    node.append({})
                node = node[p]
            else:
                node = node.setdefault(p, [] if isinstance(nxt, int) else {})
        node[path[-1]] = x.astype(dtype)
    return trees["params"], trees["rp"]


def make_weights(cfg: dict, seed: int, shardings=None):
    """(params, router params) from the seed, placed by ``shardings`` where
    given, else on the default device."""
    spec = _spec(cfg)
    has_lead = bool(layout(cfg)[0])
    kw = {} if shardings is None else {"out_shardings": shardings}
    fn = jax.jit(lambda k: _tree(k, spec, has_lead), **kw)
    return fn(jnp.asarray(archs.seed_key(seed)))
