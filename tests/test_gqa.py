"""Repeat-kv GQA (§Perf H1): q-head h attends kv-head h // (H // K), the
decode step agrees with prefill, and head-routing weights scale each head's
context independently."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.attention import (_mask, attn_apply, attn_cache_init,
                                    attn_decode, attn_init, blocked_sdpa,
                                    sdpa)


def _cfg(kv=2):
    return dataclasses.replace(get_config("toy-lm", "smoke"),
                               dtype="float32", n_kv_heads=kv)


def _grouped_reference(q, k, v):
    """Causal softmax attention, one q-head at a time against its group's
    kv-head (numpy, f64)."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    B, S, H, Dh = q.shape
    g = H // k.shape[2]
    causal = np.tril(np.ones((S, S), bool))
    out = np.zeros_like(q)
    for h in range(H):
        s = np.einsum("bqd,bsd->bqs", q[:, :, h], k[:, :, h // g]) * Dh ** -0.5
        s = np.where(causal, s, -np.inf)
        a = np.exp(s - s.max(-1, keepdims=True))
        a /= a.sum(-1, keepdims=True)
        out[:, :, h] = np.einsum("bqs,bsd->bqd", a, v[:, :, h // g])
    return out


@pytest.mark.parametrize("kv", [1, 2, 4])
def test_repeat_kv_matches_grouped_reference(key, kv):
    B, S, H, Dh = 2, 24, 4, 16
    kq, kk, kvv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, Dh))
    k = jax.random.normal(kk, (B, S, kv, Dh))
    v = jax.random.normal(kvv, (B, S, kv, Dh))
    pos = jnp.arange(S)
    want = _grouped_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(sdpa(q, k, v, _mask(pos, pos, True, 0))),
                               want, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(blocked_sdpa(q, k, v, pos, pos, True, 0, block=8)),
        want, atol=1e-5)


def test_decode_matches_prefill(key):
    cfg = _cfg(kv=2)
    p = attn_init(key, cfg)
    S = 10
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, S, cfg.d_model))
    y, k, _ = attn_apply(p, x, cfg=cfg, positions=jnp.arange(S))
    assert k.shape == (2, S, 2, cfg.d_head)      # caches stay kv-headed
    cache = attn_cache_init(cfg, 2, 16)
    for t in range(S):
        yt, cache = attn_decode(p, x[:, t:t + 1], cache, jnp.int32(t),
                                cfg=cfg)
        np.testing.assert_allclose(np.asarray(yt[:, 0]),
                                   np.asarray(y[:, t]), atol=1e-5)


def test_head_routing_weights_apply_per_head(key):
    """The output is linear in each head's weight: one-hot weights summed
    over heads reproduce the unweighted output."""
    cfg = _cfg(kv=2)
    p = attn_init(key, cfg)
    x = jax.random.normal(jax.random.fold_in(key, 3), (2, 8, cfg.d_model))
    pos = jnp.arange(8)
    full, _, _ = attn_apply(p, x, cfg=cfg, positions=pos)
    parts = sum(
        attn_apply(p, x, cfg=cfg, positions=pos,
                   head_weights=jnp.broadcast_to(
                       jax.nn.one_hot(h, cfg.n_heads), (2, 8, cfg.n_heads)))[0]
        for h in range(cfg.n_heads))
    np.testing.assert_allclose(np.asarray(parts), np.asarray(full), atol=1e-5)
