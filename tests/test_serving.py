"""Serving engine: batched generation, base-vs-elastic modes, greedy
consistency with the full forward pass."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_elastic
from repro.models import forward, model_init, router_init
from repro.training import GenRequest, ServingEngine
from tests.conftest import f32


def _setup(key, arch="toy-lm"):
    cfg = f32(get_config(arch, "smoke"))
    ecfg = get_elastic(arch, cfg)
    params = model_init(key, cfg, ecfg)
    rp = router_init(jax.random.fold_in(key, 1), cfg, ecfg)
    return cfg, ecfg, params, rp


def test_greedy_generation_matches_forward_rollout(key):
    cfg, ecfg, params, rp = _setup(key)
    engine = ServingEngine(params, rp, cfg, ecfg, mode="base",
                           batch_size=2, max_seq=48)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, 12, dtype=np.int32)
               for _ in range(2)]
    outs = engine.generate([GenRequest(p, 8) for p in prompts])
    # oracle: repeated full forward + argmax
    for p, got in zip(prompts, outs):
        toks = list(p)
        for _ in range(8):
            logits, _ = forward(params, None,
                                {"tokens": jnp.asarray([toks])}, cfg, None,
                                mode="base")
            toks.append(int(jnp.argmax(logits[0, -1])))
        np.testing.assert_array_equal(got, np.asarray(toks[len(p):]))


def test_elastic_mode_changes_compute_path(key):
    cfg, ecfg, params, rp = _setup(key)
    e1 = ServingEngine(params, rp, cfg, ecfg, mode="base", batch_size=2,
                       max_seq=32)
    e2 = ServingEngine(params, rp, cfg, ecfg, mode="infer", batch_size=2,
                       max_seq=32)
    rng = np.random.default_rng(1)
    reqs = [GenRequest(rng.integers(0, cfg.vocab_size, 8, dtype=np.int32), 8)
            for _ in range(2)]
    a = e1.generate(reqs)
    b = e2.generate(reqs)
    assert all(len(x) == 8 for x in a + b)
    # untrained routers: outputs may differ, but must be valid token ids
    assert all((x >= 0).all() and (x < cfg.padded_vocab).all() for x in b)


def test_vlm_serving_with_image_context(key):
    cfg, ecfg, params, rp = _setup(key, "toy-vlm")
    engine = ServingEngine(params, rp, cfg, ecfg, mode="infer",
                           batch_size=2, max_seq=32)
    rng = np.random.default_rng(2)
    img = jnp.asarray(rng.normal(size=(2, cfg.n_image_tokens,
                                       cfg.d_frontend)).astype(np.float32))
    reqs = [GenRequest(rng.integers(0, cfg.vocab_size, 8, dtype=np.int32), 4)
            for _ in range(2)]
    outs = engine.generate(reqs, extra_inputs={"image_embeds": img})
    assert all(len(o) == 4 for o in outs)


def test_entry_points_donate_and_stay_compile_flat(key):
    """The jitted admit/decode graphs must (a) alias every declared-donated
    buffer in their lowerings, (b) actually consume donated inputs at run
    time, and (c) keep compile_counts at {prefill: 1, decode: 1} across a
    mixed-budget/-temperature workload (donation must not retrace)."""
    cfg, ecfg, params, rp = _setup(key)
    engine = ServingEngine(params, rp, cfg, ecfg, mode="infer",
                           batch_size=2, max_seq=32)
    eps = engine.entry_points()
    for name, ep in eps.items():
        n_donated = sum(len(jax.tree.leaves(ep.args[i]))
                        for i in ep.donated)
        txt = ep.fn.lower(*ep.args, **ep.static).as_text()
        assert txt.count("tf.aliasing_output") == n_donated, \
            (name, n_donated, txt.count("tf.aliasing_output"))
    # run-time donation: a sacrificial copy of the decode args dies
    ep = eps["decode"]
    copies = tuple(jax.tree.map(jnp.copy, a) for a in ep.args)
    jax.block_until_ready(ep.fn(*copies, **ep.static))
    for i in ep.donated:
        assert all(leaf.is_deleted()
                   for leaf in jax.tree.leaves(copies[i])), i
    # compile flatness over budgets/temps/seeds (engine state is fresh —
    # the copies above were sacrificial, not the engine's live caches)
    rng = np.random.default_rng(3)
    reqs = [GenRequest(rng.integers(0, cfg.vocab_size, 8, dtype=np.int32), 4,
                       budget=b, temperature=t, top_k=k, seed=s)
            for b, t, k, s in [(0.4, 0.0, 0, 0), (1.0, 0.7, 3, 9)]]
    outs = engine.generate(reqs)
    assert all(len(o) == 4 for o in outs)
    assert engine.compile_counts() == {"prefill": 1, "decode": 1}


def test_compile_cache_dir_is_fixed_or_from_env(monkeypatch):
    """The launchers' persistent compilation cache: JAX_COMPILATION_CACHE_DIR
    when set (JAX reads it; nothing else is set in code), else a fixed
    directory inside the checkout — never a temp name, a pid or the time."""
    from repro.launch.compile_cache import CHECKOUT, enable_compile_cache
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = enable_compile_cache()
        assert path == str(CHECKOUT / ".jax_cache") == enable_compile_cache()
        assert (CHECKOUT / "chip_smoke.py").is_file()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
