"""The modules of a configuration's architecture, found by its
``model_type``, and the helpers they share.

Each architecture keeps three modules in ``archs/<model_type>/``:

- ``program.py``: ``model_config(conf, overrides)``, the program's
  ``ModelConfig`` and ``ElasticConfig`` for the configuration file's keys;
- ``weights.py``: ``dims(conf)`` (the shapes ``counts.py`` and the metric
  readers take) and ``make_weights(conf, seed, shardings=None)`` (the
  params and router params, made on the device from the seed in one
  jitted call, ``make`` below, and placed by ``shardings`` where given);
- ``reference.py``: ``served_gaps(params, rp, conf, budget, prompt,
  output, length, control=None)``, the plain reference's gaps of the
  served tokens, which imports nothing of the program.

So a configuration of another architecture comes with a directory of its
own and needs no edit to the harness.
"""
from __future__ import annotations

import importlib.util
import re
import sys
from pathlib import Path
from types import SimpleNamespace

MODULES = ("program", "weights", "reference")
_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]*")
_LOADED: dict = {}


def load(conf: dict, bench_dir: Path) -> SimpleNamespace:
    """``program``, ``weights`` and ``reference`` of the configuration's
    ``model_type``, from ``bench_dir/archs/<model_type>/``."""
    mt = str(conf.get("model_type", ""))
    d = Path(bench_dir) / "archs" / mt
    if not _NAME.fullmatch(mt) or not d.is_dir():
        raise SystemExit(f"bench: no modules for model_type {mt!r} of "
                         f"configuration {conf.get('name')!r}: {d} is not "
                         f"a directory")
    mods = {}
    for name in MODULES:
        path = (d / f"{name}.py").resolve()
        if not path.is_file():
            raise SystemExit(f"bench: architecture {mt!r} lacks {path}")
        if path not in _LOADED:
            mod_name = f"bench_arch_{len(_LOADED)}_{name}"
            spec = importlib.util.spec_from_file_location(mod_name, path)
            mod = importlib.util.module_from_spec(spec)
            sys.modules[mod_name] = mod
            spec.loader.exec_module(mod)
            _LOADED[path] = mod
        mods[name] = _LOADED[path]
    return SimpleNamespace(**mods)


# ----------------------------- weights --------------------------------------

def seed_key(seed: int):
    """A PRNG key from any non-negative integer seed (64 bits at most)."""
    import numpy as np
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return np.array([seed >> 32 & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)


def _tree(key, spec):
    """The two trees (params, router params) that ``spec`` lists, leaf n
    drawn from ``fold_in(key, n)``."""
    import jax
    import jax.numpy as jnp
    trees = {"params": {"scan": [{}], "tail": []},
             "rp": {"scan": [{}], "tail": []}}
    base = jax.random.wrap_key_data(key, impl="threefry2x32")
    for n, (path, shape, dtype, (kind, std)) in enumerate(spec):
        k = jax.random.fold_in(base, n)
        x = jax.random.normal(k, shape, jnp.float32)
        x = 1.0 + 0.1 * x if kind == "norm" else x * std
        node = trees
        for p in path[:-1]:
            node = node[p] if isinstance(p, int) else node.setdefault(p, {})
        node[path[-1]] = x.astype(dtype)
    return trees["params"], trees["rp"]


def make(spec, seed: int, shardings=None):
    """(params, router params) from ``spec``: [(path, shape, dtype,
    (kind, std))] in a fixed order, paths under ``"params"`` or ``"rp"``
    in the serving engine's layout (``{"scan": [stacked layer leaves],
    "tail": []}``). Kind ``"normal"`` draws N(0, std), ``"norm"`` 1 + 0.1
    N(0, 1). One jitted call whose only argument is the key, so every seed
    reuses one compiled program; with ``shardings`` ((params, router
    params) shardings) each leaf is made where it is placed, never whole
    on one chip, and otherwise on the default device."""
    import jax
    import jax.numpy as jnp
    kw = {} if shardings is None else {"out_shardings": shardings}
    fn = jax.jit(lambda k: _tree(k, spec), **kw)
    return fn(jnp.asarray(seed_key(seed)))


def weight_bytes(params) -> int:
    import jax
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
