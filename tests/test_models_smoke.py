"""Per-architecture smoke tests (assignment deliverable f): every assigned
arch instantiates a reduced same-family config and runs forward + one train
step on CPU, asserting output shapes and no NaNs; plus decode-vs-forward
consistency in base mode (the strongest correctness check for the
cache/ring-buffer machinery)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED, get_config, get_elastic
from repro.models import (cache_init, decode_step, forward, model_init,
                          prefill, router_init)
from repro.training import init_train_state, make_train_step
from tests.conftest import f32


def _batch(key, cfg, B=2, S=32):
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size)}
    if cfg.family == "vlm":
        batch["image_embeds"] = jax.random.normal(
            jax.random.fold_in(key, 9), (B, cfg.n_image_tokens, cfg.d_frontend))
    if cfg.encoder is not None:
        e = cfg.encoder
        batch["frames"] = jax.random.normal(
            jax.random.fold_in(key, 8), (B, e.encoder_seq,
                                         e.d_frontend or e.d_model))
    return batch


@pytest.mark.parametrize("arch", ASSIGNED)
def test_forward_and_train_step(arch, key):
    cfg = f32(get_config(arch, "smoke"))
    ecfg = get_elastic(arch, cfg)
    params = model_init(key, cfg, ecfg)
    rp = router_init(jax.random.fold_in(key, 1), cfg, ecfg)
    B, S = 2, 32
    batch = _batch(key, cfg, B, S)
    logits_t, _ = forward(params, None, batch, cfg, ecfg, mode="base")
    assert logits_t.shape == (B, S, cfg.padded_vocab)
    assert not bool(jnp.isnan(logits_t).any())
    logits_s, aux = forward(params, rp, batch, cfg, ecfg, mode="train")
    assert logits_s.shape == (B, S, cfg.padded_vocab)
    assert not bool(jnp.isnan(logits_s).any())
    step = make_train_step(cfg, ecfg, lr=1e-3, chunked=True)
    state = init_train_state(rp)
    state, m = jax.jit(step)(state, params, batch)
    assert np.isfinite(m["loss"]), (arch, m)
    assert float(m["grad_norm"]) > 0, "router gradients must be nonzero"


@pytest.mark.parametrize("arch", ASSIGNED)
def test_decode_matches_forward_base_mode(arch, key):
    """Prefill + N decode steps must reproduce the full-sequence forward
    logits position-by-position in teacher mode (exercises KV ring caches,
    SSM/RG-LRU state hand-off, cross-attn caches)."""
    # native MoE dispatch is dropless: decode's exact top-k and the
    # full-sequence forward compute the same experts for every token
    cfg = f32(get_config(arch, "smoke"))
    params = model_init(key, cfg, None)
    B, S, n_dec = 2, 24, 6
    batch = _batch(key, cfg, B, S)
    full_logits, _ = forward(params, None, batch, cfg, None, mode="base")

    pre = dict(batch)
    pre["tokens"] = batch["tokens"][:, :S - n_dec]
    logits, caches = prefill(params, None, pre, cfg, None, mode="base",
                             max_cache_len=S)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(full_logits[:, S - n_dec - 1]),
        atol=2e-3, rtol=1e-3, err_msg=f"{arch}: prefill logits mismatch")
    for i in range(n_dec):
        t = S - n_dec + i
        tok = batch["tokens"][:, t:t + 1]
        logits, caches = decode_step(params, None, tok, caches,
                                     jnp.int32(t), cfg, None, mode="base")
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full_logits[:, t]),
            atol=2e-3, rtol=1e-3,
            err_msg=f"{arch}: decode step {i} mismatch")


@pytest.mark.parametrize("arch", ["gemma3-27b", "recurrentgemma-2b"])
def test_ring_cache_window_decode(arch, key):
    """Decode far past the window: ring cache must keep producing finite,
    position-consistent outputs (window entries evicted correctly)."""
    cfg = f32(get_config(arch, "smoke"))
    params = model_init(key, cfg, None)
    B, S = 1, 8
    batch = _batch(key, cfg, B, S)
    logits, caches = prefill(params, None, batch, cfg, None, mode="base",
                             max_cache_len=64)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    for t in range(S, S + 40):   # run past window=16 on the smoke config
        logits, caches = decode_step(params, None, tok, caches,
                                     jnp.int32(t), cfg, None, mode="base")
        assert bool(jnp.isfinite(logits).all()), (arch, t)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)


@pytest.mark.parametrize("arch", ASSIGNED)
def test_student_infer_mode_runs(arch, key):
    cfg = f32(get_config(arch, "smoke"))
    ecfg = get_elastic(arch, cfg)
    params = model_init(key, cfg, ecfg)
    rp = router_init(jax.random.fold_in(key, 1), cfg, ecfg)
    batch = _batch(key, cfg)
    logits, caches = prefill(params, rp, batch, cfg, ecfg, mode="infer",
                             max_cache_len=40)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    logits, _ = decode_step(params, rp, tok, caches, jnp.int32(32), cfg,
                            ecfg, mode="infer")
    assert bool(jnp.isfinite(logits).all())


def test_even_layer_mode(key):
    """Paper §5.2: ElastiFormer on even layers only."""
    cfg = f32(get_config("qwen2-7b", "smoke"))
    ecfg = dataclasses.replace(get_elastic("qwen2-7b", cfg), layers="even")
    params = model_init(key, cfg, ecfg)
    rp = router_init(jax.random.fold_in(key, 1), cfg, ecfg)
    batch = _batch(key, cfg)
    logits, aux = forward(params, rp, batch, cfg, ecfg, mode="train")
    assert bool(jnp.isfinite(logits).all())
    # fewer layers routed -> smaller aux than all-layers (params re-stacked
    # per mode: pattern period differs, weights identical per layer)
    ecfg_all = dataclasses.replace(ecfg, layers="all")
    params_all = model_init(key, cfg, ecfg_all)
    rp_all = router_init(jax.random.fold_in(key, 1), cfg, ecfg_all)
    _, aux_all = forward(params_all, rp_all, batch, cfg, ecfg_all,
                         mode="train")
    assert float(aux.topk) < float(aux_all.topk)
