"""lfm2-8b-a1b (gated short conv + GQA with q/k norm + sigmoid-routed
native experts) at its smoke size against the benchmark's plain float32
reference (``bench/archs/lfm2_moe/reference.py``, loaded by path: it
imports nothing of the program)."""
import dataclasses
import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, get_elastic
from repro.core.policy import ElasticPolicy, as_spec_policy, solve_budget
from repro.models import decode_step, model_init, prefill, router_init
from repro.models import shortconv as SC
from repro.models.moe import moe_apply, moe_decode, moe_init
from repro.training import GenRequest, ServingEngine
from tests.conftest import f32

REF_PATH = (Path(__file__).resolve().parents[1] / "bench" / "archs"
            / "lfm2_moe" / "reference.py")
# both sides compute in float32; they differ only in the order of sums
# (one whole-sequence pass against prefill plus cached decode steps)
F32_TOL = dict(atol=2e-4, rtol=2e-4)


def _ref():
    spec = importlib.util.spec_from_file_location("lfm2_moe_reference",
                                                  REF_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _ref()


def _cfg():
    return f32(get_config("lfm2-8b-a1b", "smoke"))


def _conf(cfg) -> dict:
    """The reference's configuration keys for a program config."""
    m = cfg.moe
    return {"num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "hidden_size": cfg.d_model, "norm_eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta, "num_experts_per_tok": m.top_k,
            "num_hidden_layers": cfg.n_layers,
            "layer_types": ["full_attention" if k == "attn" else "conv"
                            for k in cfg.layer_kinds]}


def _params(cfg, seed=0, ecfg=None):
    """Program params with the norm scales, conv taps and expert biases
    drawn too (init leaves them at ones / zeros)."""
    params = model_init(jax.random.PRNGKey(seed), cfg, ecfg)
    leaves, tdef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, x) in enumerate(leaves):
        k = jax.random.fold_in(jax.random.PRNGKey(seed + 100), i)
        name = jax.tree_util.keystr(path)
        if name.endswith("['scale']"):
            x = x + 0.1 * jax.random.normal(k, x.shape, x.dtype)
        elif name.endswith("['expert_bias']"):
            x = 0.3 * jax.random.normal(k, x.shape, x.dtype)
        out.append(x)
    return jax.tree_util.tree_unflatten(tdef, out)


def _tokens(cfg, n, seed=1, batch=2):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, n), 0,
                              cfg.vocab_size)


@pytest.mark.parametrize("plen,n_dec", [(9, 8), (16, 10)])
def test_prefill_then_decode_matches_reference(plen, n_dec):
    """Prefill a prompt, then decode through the ring cache (K/V rings and
    conv rows side by side): every step's logits are the reference's full
    causal forward at that position."""
    cfg = _cfg()
    params = _params(cfg)
    toks = _tokens(cfg, plen + n_dec)
    logits, caches = prefill(params, None, {"tokens": toks[:, :plen]}, cfg,
                             None, mode="base", max_cache_len=64)
    step = jax.jit(lambda tok, c, t: decode_step(params, None, tok, c, t,
                                                 cfg, None, mode="base"))
    got = [logits]
    for i in range(n_dec - 1):
        t = plen + i
        logits, caches = step(toks[:, t:t + 1], caches, jnp.int32(t))
        got.append(logits)
    got = np.stack([np.asarray(g) for g in got], 1)     # (B, n_dec, V)
    ref = jax.jit(lambda tk: REF.logits(params, _conf(cfg), tk))
    for b in range(toks.shape[0]):
        want = np.asarray(ref(toks[b]))
        np.testing.assert_allclose(got[b], want[plen - 1:plen + n_dec - 1],
                                   **F32_TOL)


@pytest.mark.parametrize("keep", [None, "mask"])
def test_conv_decode_reproduces_full_causal_conv(keep):
    """A prompt decoded token by token from an empty state gives the
    whole-sequence convolution's outputs and final rows; with a token mask,
    each kept token sees the previous kept tokens only (skipped tokens
    never enter the history), as the compacted sequence gives."""
    cfg = _cfg()
    p = SC.conv_init(jax.random.PRNGKey(3), cfg)
    S = 16
    h = jax.random.normal(jax.random.PRNGKey(4), (2, S, cfg.d_model))
    mask = None if keep is None else \
        jax.random.bernoulli(jax.random.PRNGKey(5), 0.6, (2, S))
    y_full, st_full = SC.conv_apply(p, h, cfg, keep_mask=mask)
    st = SC.conv_cache_init(cfg, 2)
    ys = []
    for t in range(S):
        w = None if mask is None else mask[:, t]
        y, st = SC.conv_decode(p, h[:, t:t + 1], st, cfg, write=w)
        ys.append(y)
    y_dec = jnp.concatenate(ys, 1)
    kept = np.ones((2, S), bool) if mask is None else np.asarray(mask)
    np.testing.assert_allclose(np.asarray(y_dec)[kept],
                               np.asarray(y_full)[kept], atol=1e-5)
    np.testing.assert_allclose(np.asarray(st["bx"]),
                               np.asarray(st_full["bx"]), atol=1e-6)
    if mask is not None:
        for b in range(2):
            idx = np.nonzero(kept[b])[0]
            y_c, st_c = SC.conv_apply(p, h[b:b + 1, idx], cfg)
            np.testing.assert_allclose(np.asarray(y_full)[b, idx],
                                       np.asarray(y_c)[0], atol=1e-5)
            np.testing.assert_allclose(np.asarray(st_full["bx"])[b],
                                       np.asarray(st_c["bx"])[0], atol=1e-6)


def _moe_case(skew: bool):
    cfg = _cfg()
    p = moe_init(jax.random.PRNGKey(7), cfg)
    E = cfg.moe.n_experts
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 32, cfg.d_model))
    if skew:
        # every token's scores favour experts 0 and 1: with top-2 of 8 they
        # take all 32 tokens each, against a capacity of 1.25 * 2 * 32 / 8
        # = 10 slots per expert
        p["expert_bias"] = jnp.zeros((E,)).at[:2].set(5.0)
    else:
        p["expert_bias"] = 0.5 * jax.random.normal(jax.random.PRNGKey(9),
                                                   (E,))
    return cfg, p, x


def _ref_moe(cfg, p, x):
    c = REF.consts(_conf(cfg))
    return np.stack([np.asarray(REF._experts(x[b], p, c, None))
                     for b in range(x.shape[0])])


@pytest.mark.parametrize("skew", [False, True], ids=["biased", "skewed"])
def test_native_experts_match_reference(skew):
    """Prefill dispatch (dropless) and decode's expert gather both match
    the reference's dense application of every expert: a skewed router
    that a 1.25 capacity would clip loses no token."""
    cfg, p, x = _moe_case(skew)
    want = _ref_moe(cfg, p, x)
    y, _ = moe_apply(p, x, act=cfg.act, top_k=cfg.moe.top_k,
                     seq_chunk=cfg.moe.seq_chunk, router=cfg.moe.router)
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-5)
    yd = [moe_decode(p, x[:, t:t + 1], act=cfg.act, top_k=cfg.moe.top_k,
                     router=cfg.moe.router)[0] for t in range(x.shape[1])]
    np.testing.assert_allclose(np.asarray(jnp.concatenate(yd, 1)), want,
                               atol=1e-5)
    if skew:      # more tokens chose an expert than a 1.25 capacity holds
        s = jax.nn.sigmoid(x[0] @ p["router"]) + p["expert_bias"]
        load = np.bincount(np.asarray(jax.lax.top_k(s, cfg.moe.top_k)[1])
                           .ravel(), minlength=cfg.moe.n_experts)
        E, n = cfg.moe.n_experts, x.shape[1]
        assert load.max() > math.ceil(1.25 * cfg.moe.top_k * n / E)


def test_bias_picks_experts_but_never_weights_them():
    """A bias that flips a selection: the chosen experts follow s + b, and
    their weights are the unbiased s, renormalised over the chosen."""
    cfg = _cfg()
    p = moe_init(jax.random.PRNGKey(11), cfg)
    x = jax.random.normal(jax.random.PRNGKey(12), (1, 1, cfg.d_model))
    s = jax.nn.sigmoid(x[0, 0] @ p["router"])
    worst = int(jnp.argmin(s))
    p["expert_bias"] = jnp.zeros_like(s).at[worst].set(2.0)
    k = cfg.moe.top_k
    sel = np.argsort(-np.asarray(s + p["expert_bias"]))[:k]
    assert worst in sel and worst not in np.argsort(-np.asarray(s))[:k]
    w = np.zeros(s.shape)
    w[sel] = np.asarray(s)[sel] / (np.asarray(s)[sel].sum() + 1e-6)
    one = {}
    for e in range(cfg.moe.n_experts):   # each expert's own output
        # alone it is chosen with weight s_e / (s_e + 1e-6)
        q = dict(p, expert_bias=jnp.full_like(s, -10.0).at[e].set(10.0))
        se = float(s[e])
        one[e] = np.asarray(moe_decode(q, x, act=cfg.act, top_k=1,
                                       router="sigmoid")[0]) \
            * (se + 1e-6) / se
    want = sum(w[e] * one[e] for e in sel)
    for fn in (lambda: moe_decode(p, x, act=cfg.act, top_k=k,
                                  router=cfg.moe.router)[0],
               lambda: moe_apply(p, x, act=cfg.act, top_k=k, seq_chunk=8,
                                 router=cfg.moe.router)[0]):
        np.testing.assert_allclose(np.asarray(fn()), want, atol=1e-5)


def _elastic(cfg):
    ecfg = get_elastic("lfm2-8b-a1b", cfg)
    params = _params(cfg, ecfg=ecfg)
    rp = router_init(jax.random.PRNGKey(21), cfg, ecfg)
    return ecfg, params, rp


def test_budget_one_is_bit_identical_to_teacher():
    """With every router built (token routers around conv and attention
    mixers and MLPs, head router, LoRA, the expert knob), a budget-1.0
    policy traced through prefill and decode gives the teacher's logits
    bit for bit, and the engine serves the teacher's tokens."""
    cfg = _cfg()
    ecfg, params, rp = _elastic(cfg)
    rp = jax.tree.map(lambda a: a + 0.3, rp)     # routers that would act
    spec, _ = as_spec_policy(ecfg)
    pol = jax.tree.map(lambda v: jnp.asarray(v, jnp.float32),
                       solve_budget(cfg, spec, 1.0, static=True))
    toks = _tokens(cfg, 12)
    # both sides compiled, as the engine runs them (an eager pass fuses
    # differently and rounds differently)
    lt, ct = jax.jit(lambda: prefill(params, None, {"tokens": toks}, cfg,
                                     None, mode="base", max_cache_len=32))()
    le, ce = jax.jit(lambda pl: prefill(
        params, rp, {"tokens": toks}, cfg, spec, mode="infer",
        max_cache_len=32, policy=pl))(pol)
    np.testing.assert_array_equal(np.asarray(le), np.asarray(lt))
    tok = jnp.argmax(lt, -1)[:, None].astype(jnp.int32)
    teach = jax.jit(lambda tk, c, t: decode_step(
        params, None, tk, c, t, cfg, None, mode="base"))
    elast = jax.jit(lambda pl, tk, c, t: decode_step(
        params, rp, tk, c, t, cfg, spec, mode="infer", policy=pl))
    for t in range(12, 15):
        lt, ct = teach(tok, ct, jnp.int32(t))
        le, ce = elast(pol, tok, ce, jnp.int32(t))
        np.testing.assert_array_equal(np.asarray(le), np.asarray(lt))
        tok = jnp.argmax(lt, -1)[:, None].astype(jnp.int32)
    reqs = [GenRequest(np.asarray(toks[i]), 5, budget=1.0) for i in range(2)]
    base = ServingEngine(params, None, cfg, None, mode="base", batch_size=2,
                         max_seq=32)
    el = ServingEngine(params, rp, cfg, ecfg, mode="infer", batch_size=2,
                       max_seq=32)
    assert [list(o) for o in base.generate(reqs)] == \
        [list(o) for o in el.generate(reqs)]


def test_skipped_token_leaves_conv_state_unchanged():
    """At a reduced budget a token its mixer router skips writes no conv
    row: every conv layer's state is the one before the step; a kept
    token shifts it."""
    cfg = _cfg()
    ecfg, params, rp = _elastic(cfg)
    spec, _ = as_spec_policy(ecfg)
    pol = ElasticPolicy.uniform(0.5, n_heads=cfg.n_heads,
                                n_experts=cfg.moe.top_k)
    toks = _tokens(cfg, 10)
    _, caches = prefill(params, rp, {"tokens": toks}, cfg, spec,
                        mode="infer", max_cache_len=32, policy=pol)

    def set_bias(rp, b):
        return jax.tree_util.tree_map_with_path(
            lambda pth, a: jnp.full_like(a, b)
            if "tok_mixer" in jax.tree_util.keystr(pth)
            and jax.tree_util.keystr(pth).endswith("['b']") else a, rp)

    bx = lambda c: [np.asarray(x) for pth, x in
                    jax.tree_util.tree_flatten_with_path(c)[0]
                    if jax.tree_util.keystr(pth).endswith("['bx']")]
    before = bx(caches)
    assert len(before) == 3     # lead (2 layers) and the scanned conv
    step = jax.jit(lambda r, c: decode_step(
        params, r, toks[:, -1:], c, jnp.int32(10), cfg, spec, mode="infer",
        policy=pol)[1])
    skip = step(set_bias(rp, -50.0), caches)
    for a, b in zip(bx(skip), before):
        np.testing.assert_array_equal(a, b)
    kept = step(set_bias(rp, 50.0), caches)
    assert all(np.abs(a - b).max() > 0 for a, b in zip(bx(kept), before))


def test_param_shardings_cover_new_leaves():
    """Every leaf has a rule's spec; the new ones split as documented."""
    from repro.runtime.sharding import cache_specs_tree, param_specs
    from repro.runtime import make_mesh
    from repro.models import cache_specs
    cfg = _cfg()
    params = jax.eval_shape(lambda: model_init(jax.random.PRNGKey(0), cfg))
    specs = param_specs(params)
    flat = {jax.tree_util.keystr(pth): s for pth, s in
            jax.tree_util.tree_flatten_with_path(
                specs, is_leaf=lambda x: isinstance(x, P))[0]}
    assert len(flat) == len(jax.tree.leaves(params))
    want = {
        "['lead'][0]['mixer']['in_proj']": P(None, None, "model"),
        "['lead'][0]['mixer']['conv_w']": P(None, "model"),
        "['lead'][0]['mixer']['out_proj']": P("model", None),
        "['lead'][0]['mlp']['wi']": P(None, "model"),
        "['scan'][1]['mixer']['in_proj']": P(None, None, None, "model"),
        "['scan'][0]['attn']['wq']": P(None, None, "model", None),
        "['scan'][0]['attn']['q_norm']['scale']": P(),
        "['scan'][0]['attn']['k_norm']['scale']": P(),
        "['scan'][0]['mlp']['expert_bias']": P(),
        "['scan'][0]['mlp']['router']": P(),
        "['scan'][0]['mlp']['wi']": P(None, None, None, "model"),
        "['tail'][0]['mlp']['wo']": P(None, "model", None),
    }
    trim = lambda s: tuple(s)[:max([i + 1 for i, a in enumerate(s)
                                    if a is not None] or [0])]
    for k, s in want.items():
        assert trim(flat[k]) == trim(s), (k, flat[k])
    mesh = make_mesh((1, 1), ("data", "model"))
    cs = cache_specs_tree(cache_specs(cfg, 2, 16), cfg, mesh)
    cflat = {jax.tree_util.keystr(pth): s for pth, s in
             jax.tree_util.tree_flatten_with_path(
                 cs, is_leaf=lambda x: isinstance(x, P))[0]}
    assert cflat["['lead'][0]['conv']['bx']"] == P(("data",), None, "model")
    assert cflat["['scan'][1]['conv']['bx']"] == \
        P(None, ("data",), None, "model")


def test_conv_scope_in_compiled_programs():
    """The ``conv`` named scope (the short-conv mixer, its state update
    included) is on the serving programs' operations."""
    cfg = _cfg()
    params = _params(cfg)
    engine = ServingEngine(params, None, cfg, None, mode="base",
                           batch_size=2, max_seq=32)
    for name, ep in engine.entry_points(plen=8).items():
        txt = ep.fn.lower(*ep.args, **ep.static).as_text(debug_info=True)
        assert "conv/" in txt, name


def test_paged_layout_refuses_conv():
    cfg = _cfg()
    params = _params(cfg)
    with pytest.raises(ValueError, match="conv"):
        ServingEngine(params, None, cfg, None, mode="base", batch_size=2,
                      max_seq=32, kv_layout="paged")


def test_published_config_counts():
    cfg = get_config("lfm2-8b-a1b")
    assert cfg.layer_kinds.count("attn") == 6 and cfg.n_layers == 24
    assert cfg.layer_moe == (False,) * 2 + (True,) * 22
    assert round(cfg.n_params() / 1e9, 2) == 8.34
    cut = dataclasses.replace(cfg, n_layers=10)
    assert round(cut.n_params() / 1e9, 3) == 3.197
