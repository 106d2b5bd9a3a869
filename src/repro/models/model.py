"""Model assembly: embedding, pattern-scanned block stack, LM head; prefill &
decode; encoder / enc-dec / VLM plumbing; ElastiFormer router attachment.

Layer stacking uses a *pattern scan*: the layer sequence is grouped into
repeating periods (heterogeneous kinds, windows, and elastic on/off flags are
static per pattern position). Parameters are stacked per position and the
period is unrolled inside a single jax.lax.scan body — so compile time and
HLO size stay ~O(one period) even at 88 layers and 512-way SPMD, with exact
per-kind cost attribution (no lax.switch dual-branch waste). Remainder layers
run unrolled ("tail").

Elasticity API: every entry point takes ``elastic`` as either the legacy
``ElasticConfig`` (static; deprecated shim) or the new ``ElasticSpec``, plus
an optional runtime ``policy`` (``ElasticPolicy`` pytree). When ``policy``
is passed into a jitted call it is *traced*: one compilation serves every
compute budget (capacity sweeps, per-request budgets, annealing schedules).
Policy leaves with a leading layer dim (L, ...) are split per layer and fed
through the pattern scan, enabling per-layer-group capacity schedules.
"""
from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.policy import ElasticPolicy, as_spec_policy
from repro.core.routing import (RouteAux, bcast_to, capacity_k, gate_capacity,
                                is_full, is_static, gather_tokens,
                                token_router_init, topk_indices,
                                topk_mask_dyn)
from repro.models.blocks import (block_apply, block_cache_init, block_chunk,
                                 block_decode, block_paged_cache_init,
                                 block_router_init, block_init,
                                 cache_row_insert)
from repro.models.layers import dense_init, dtype_of, norm_apply, norm_init


class PatternPos(NamedTuple):
    kind: str
    window: int
    elastic: bool
    moe: bool = False          # the layer's MLP is the native MoE


class Pattern(NamedTuple):
    """The layer stack's layout: ``lead`` layers run unrolled, then
    ``period`` is scanned ``P`` times, then ``tail`` runs unrolled."""
    lead: tuple
    period: tuple
    P: int
    tail: tuple


def _total(mesh, axes) -> int:
    n = 1
    for g in axes:
        for a in (g if isinstance(g, tuple) else (g,)):
            n *= mesh.shape.get(a, 1)
    return n


def _best_period(ents) -> tuple:
    """(period length, repeats) of the period that, repeated from the
    start, covers the most of ``ents`` (the shortest among equals); the
    whole list once when nothing repeats."""
    m = len(ents)
    best, covered = (m, 1), 0
    for p in range(1, m // 2 + 1):
        r = 1
        while (r + 1) * p <= m and ents[r * p:(r + 1) * p] == ents[:p]:
            r += 1
        if r >= 2 and p * r > covered:
            best, covered = (p, r), p * r
    return best


def build_pattern(cfg, elastic=None) -> Pattern:
    """The stack's ``Pattern``. ``elastic`` is an ElasticSpec or a legacy
    ElasticConfig (only .layers matters here). The leading dense layers of
    a MoE model (``cfg.n_dense_layers``) form ``lead``; the period is the
    one that repeats most after ``lead``, over each layer's kind, window,
    elastic flag and MLP kind; layers past its last repeat form ``tail``,
    each with its own kind."""
    n = cfg.n_layers
    kinds, wins, moes = cfg.layer_kinds, cfg.layer_windows, cfg.layer_moe
    applies = (lambda i: True) if elastic is None else elastic.applies_to_layer
    ents = [PatternPos(kinds[i], wins[i], applies(i), moes[i])
            for i in range(n)]
    lead = min(cfg.n_dense_layers, n) if cfg.moe is not None else 0
    rest = ents[lead:]
    period_len, P = _best_period(rest) if rest else (0, 0)
    return Pattern(tuple(ents[:lead]), tuple(rest[:period_len]), P,
                   tuple(rest[period_len * P:]))


def _split_layers(per_layer: list, pat: Pattern) -> dict:
    """[L trees] -> {"lead": [trees] (only when the pattern has a lead),
    "scan": [period_len trees stacked over P], "tail": [trees]}."""
    nl, np_ = len(pat.lead), len(pat.period)
    scan = []
    for j in range(np_):
        if pat.P > 0:
            scan.append(jax.tree.map(
                lambda *xs: jnp.stack(xs),
                *[per_layer[nl + p * np_ + j] for p in range(pat.P)]))
    out = {"scan": scan, "tail": per_layer[nl + np_ * pat.P:]}
    if nl:
        out["lead"] = per_layer[:nl]
    return out


# --------------------------- policy threading --------------------------------

def _pol_static(pol) -> bool:
    """True when every policy leaf is a python number (or no policy): the
    values are trace-time constants and must NOT be routed through scan /
    shard_map arguments (that would turn them into tracers and lose the
    static gather path)."""
    return pol is None or all(is_static(l) for l in jax.tree.leaves(pol))


def _split_policy(pol, n_layers: int, pat: Pattern) -> dict:
    """Per-layer split of a traced policy with (L, ...) leaves, mirroring
    the parameter stacking (``_split_layers``)."""
    return _split_layers([pol.for_layer(i) for i in range(n_layers)], pat)


def _unrolled(part: str, params, rparams, pat: Pattern, pol_split, *,
              has_rp: bool, static_pol: bool, pol):
    """Hoisted (params, entry, router-params, policy) tuples of the
    ``lead`` or ``tail`` layers, resolved once per trace (with layered
    (L, B) policy leaves a per-iteration ``for_layer`` gather would cost
    one per layer per trace). ``pol_split`` is ``_split_policy``'s result
    (None when the policy has no layer dim)."""
    lps = params.get(part, [])
    n = len(lps)
    ents = pat.lead if part == "lead" else pat.tail
    rps = rparams.get(part, []) if has_rp else [None] * n
    pols = list(pol_split.get(part, [])) if pol_split is not None else \
        [None if static_pol else pol] * n
    return list(zip(lps, ents, rps, pols))


# ------------------------------- init ---------------------------------------

def model_init(key, cfg, elastic=None):
    pat = build_pattern(cfg, elastic)
    dt = dtype_of(cfg)
    D, V = cfg.d_model, cfg.padded_vocab
    ks = jax.random.split(key, 8)
    params = {"final_norm": norm_init(D, cfg.norm)}
    if V:
        params["embed"] = dense_init(ks[0], V, D, dt, scale=0.02)
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(ks[1], D, V, dt)
    layers = [block_init(jax.random.fold_in(ks[2], i), cfg.layer_kinds[i], cfg,
                         moe=cfg.layer_moe[i])
              for i in range(cfg.n_layers)]
    params.update(_split_layers(layers, pat))
    if cfg.family in ("encoder", "vlm") or cfg.d_frontend:
        params["in_proj"] = dense_init(ks[3], cfg.d_frontend or D, D, dt)
    if cfg.encoder is not None:
        params["encoder"] = model_init(ks[4], cfg.encoder, elastic)
        params["encoder"]["in_proj"] = dense_init(
            ks[5], cfg.encoder.d_frontend or cfg.encoder.d_model,
            cfg.encoder.d_model, dt)
    return params


def router_init(key, cfg, elastic):
    """Trainable ElastiFormer parameter tree (mirrors the layer stacking).
    ``elastic``: ElasticSpec or legacy ElasticConfig."""
    spec, _ = as_spec_policy(elastic)
    pat = build_pattern(cfg, spec)
    ks = jax.random.split(key, 4)
    layers = [block_router_init(jax.random.fold_in(ks[0], i),
                                cfg.layer_kinds[i], cfg, spec,
                                moe=cfg.layer_moe[i])
              for i in range(cfg.n_layers)]
    rp = _split_layers(layers, pat)
    if spec.vlm_routed and (
            cfg.family in ("vlm", "encdec") or cfg.n_image_tokens):
        D = cfg.d_model
        if spec.vlm_router == "mlp":
            h = spec.vlm_router_hidden or D
            rp["vlm"] = {
                "w1": dense_init(ks[1], D, h, jnp.float32),
                "b1": jnp.zeros((h,), jnp.float32),
                "w2": dense_init(ks[2], h, 1, jnp.float32),
                "b2": jnp.zeros((), jnp.float32),
            }
        else:
            rp["vlm"] = token_router_init(ks[1], D)
    if cfg.encoder is not None:
        rp["encoder"] = router_init(ks[3], cfg.encoder, spec)
    return rp


def router_param_count(rp) -> int:
    return sum(x.size for x in jax.tree.leaves(rp))


# --------------------------- context selection -------------------------------

def _vlm_logits(rp, emb):
    if "w1" in rp:  # MLP router (paper §5.3)
        h = jax.nn.gelu(emb.astype(jnp.float32) @ rp["w1"] + rp["b1"])
        return (h @ rp["w2"])[..., 0] + rp["b2"]
    return emb.astype(jnp.float32) @ rp["w"] + rp["b"]


def select_context_tokens(rp, emb, spec, pol, mode: str):
    """Paper §5.3: top-k image/context-token selection before the decoder.
    Non-causal, so top-k applies at inference too (no BCE aux needed).

    Static capacity gathers the (B, k, D) subset (smaller decoder xattn);
    traced capacity keeps full shape and returns a validity mask instead,
    so one compiled graph serves every context budget."""
    if mode == "base" or rp is None or "vlm" not in rp \
            or spec is None or not spec.vlm_routed:
        return emb, None
    B, T, D = emb.shape
    cap = pol.vlm_token_capacity if pol is not None else 1.0
    cap = gate_capacity(cap, pol.student if pol is not None else None)
    logits = _vlm_logits(rp["vlm"], emb)
    scores = jax.nn.sigmoid(logits)
    if is_static(cap):
        if cap >= 1.0:
            return emb, None
        k = max(1, int(math.ceil(cap * T)))
        idx = topk_indices(scores, k)
        sel = gather_tokens(emb, idx)
        w = jnp.take_along_axis(scores, idx, 1)
        return sel * w[..., None].astype(sel.dtype), None
    keep = topk_mask_dyn(scores, capacity_k(cap, T))
    full = bcast_to(is_full(cap), keep.ndim)
    keep = keep | full
    w = jnp.where(full, 1.0, keep * scores)
    return emb * w[..., None].astype(emb.dtype), keep


# ------------------------------ stack runner ---------------------------------

def _run_stack(params, rparams, x, *, cfg, spec, pol, mode, pat, causal,
               enc_kv=None, enc_valid=None, remat=False, bucket=None):
    aux = RouteAux.zero()
    period = pat.period
    static_pol = _pol_static(pol)
    layered = (not static_pol) and pol.has_layer_dim
    pol_split = _split_policy(pol, cfg.n_layers, pat) if layered else None

    def apply_block(ent, lp, lrp, lpol, x, enc_kv, enc_valid):
        return block_apply(
            ent.kind, lp, lrp, x, cfg=cfg, spec=spec,
            pol=(pol if static_pol else lpol), mode=mode,
            elastic_on=ent.elastic, window=ent.window, causal=causal,
            enc_kv=enc_kv, enc_valid=enc_valid, bucket=bucket,
            spmd_auto=spmd_auto)

    # §Perf H2: under a mesh, run each block shard_map-MANUAL over the batch
    # axes (model axis stays auto for GSPMD tensor parallelism). This makes
    # every batch-indexed gather/scatter in token routing / MoE dispatch
    # device-local — GSPMD cannot partition batch-indexed scatters and was
    # replicating them to the full global batch (12 GB f32 tensors + 80 GB
    # of all-reduce per layer at qwen2/train_4k scale).
    from repro.runtime import sharding as _SH
    mesh = _SH.active_mesh()
    ba = _SH.batch_axes(mesh) if mesh is not None else ()
    # skip when the batch axes are trivial (size 1: XLA rejects auto
    # collectives nested in a manual-over-one-partition region) or don't
    # divide the batch
    ba = ba if (ba and _total(mesh, ba) > 1
                and x.shape[0] % _total(mesh, ba) == 0) else ()
    # inside the manual-over-batch wrap, mesh-wide sharding constraints and
    # nested shard_map kernel wrappers are illegal — blocks skip them there
    spmd_auto = not ba

    from jax.sharding import PartitionSpec as P
    # per-request (B,) policy leaves shard with the batch; scalars and
    # size-1 per-layer leaves replicate
    B0 = x.shape[0]
    pol_sample = None if static_pol else (pol.for_layer(0) if layered else pol)
    pol_specs = P() if pol_sample is None else jax.tree.map(
        lambda v: P(ba) if (getattr(v, "ndim", 0) >= 1
                            and v.shape[0] == B0) else P(), pol_sample)

    def shard_block(f):
        if not ba:
            return f

        def body(lp, lrp, lpol, xx, ekv, evd):
            y, a = f(lp, lrp, lpol, xx, ekv, evd)
            return y, jax.tree.map(lambda s: jax.lax.pmean(s, ba), a)

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(), pol_specs, P(ba, None, None),
                      P() if enc_kv is None else P(ba, None, None),
                      P() if enc_valid is None else P(ba, None)),
            out_specs=(P(ba, None, None), P()),
            axis_names=frozenset(a for g in ba for a in
                                 (g if isinstance(g, tuple) else (g,))),
            check_vma=False)

    fns = {}
    for ent in pat.lead + period + pat.tail:
        if ent not in fns:
            f = shard_block(partial(apply_block, ent))
            fns[ent] = jax.checkpoint(f) if remat else f

    has_rp = rparams is not None and mode != "base"
    plan = partial(_unrolled, params=params, rparams=rparams, pat=pat,
                   pol_split=pol_split, has_rp=has_rp,
                   static_pol=static_pol, pol=pol)
    for lp, ent, lrp, lpol in plan("lead"):
        x, a = fns[ent](lp, lrp, lpol, x, enc_kv, enc_valid)
        aux = aux + a

    def body(carry, xs):
        x, aux = carry
        lps = xs["p"]
        lrps = xs["r"] if has_rp else [None] * len(period)
        lpols = xs.get("pol")
        for j, ent in enumerate(period):
            lpol = lpols[j] if lpols is not None else \
                (None if static_pol else pol)
            x, a = fns[ent](lps[j], lrps[j], lpol, x, enc_kv, enc_valid)
            aux = aux + a
        return (x, aux), None

    if params["scan"]:
        assert len(params["scan"]) == len(period), (
            f"param stacking period ({len(params['scan'])}) != apply-time "
            f"pattern period ({len(period)}): init and apply must use the "
            f"same elastic layers mode")
        xs = {"p": params["scan"]}
        if has_rp:
            xs["r"] = rparams["scan"]
        if layered:
            xs["pol"] = pol_split["scan"]
        (x, aux), _ = jax.lax.scan(body, (x, aux), xs)
    for lp, ent, lrp, lpol in plan("tail"):
        x, a = fns[ent](lp, lrp, lpol, x, enc_kv, enc_valid)
        aux = aux + a
    return x, aux


def _embed(params, cfg, tokens):
    return jnp.take(params["embed"], tokens, axis=0)


def _logits(params, cfg, x):
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head
    if cfg.padded_vocab != cfg.vocab_size:
        v = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        logits = jnp.where(v, logits, -1e30)
    return logits


def _context(params, rparams, batch, cfg, spec, pol, mode, remat=False):
    """Image/encoder context for xattn layers -> (enc_kv, enc_valid, aux)."""
    if cfg.family == "vlm":
        emb = batch["image_embeds"].astype(dtype_of(cfg)) @ params["in_proj"]
        emb, valid = select_context_tokens(rparams, emb, spec, pol, mode) \
            if spec is not None else (emb, None)
        return emb, valid, RouteAux.zero()
    if cfg.encoder is not None:
        enc_p = params["encoder"]
        enc_rp = rparams.get("encoder") if (rparams and mode != "base") else None
        x = batch["frames"].astype(dtype_of(cfg)) @ enc_p["in_proj"]
        pat = build_pattern(cfg.encoder, spec)
        # NOTE: no `bucket` here — the caller's bucket is solved for the
        # DECODER sequence length; an undersized bucket would silently drop
        # selected encoder tokens. Traced encoder capacities take the dense
        # fallback (static ones still derive their own bucket inline).
        x, aux = _run_stack(enc_p, enc_rp, x, cfg=cfg.encoder, spec=spec,
                            pol=pol, mode=mode, pat=pat, causal=False,
                            remat=remat)
        x = norm_apply(enc_p["final_norm"], x, cfg.encoder.norm,
                       cfg.encoder.norm_eps)
        x, valid = select_context_tokens(rparams, x, spec, pol, mode) \
            if spec is not None else (x, None)
        return x, valid, aux
    return None, None, RouteAux.zero()


def forward(params, rparams, batch, cfg, ecfg=None, mode: str = "base",
            return_hidden: bool = False, remat: bool = False, policy=None,
            bucket=None):
    """Full-sequence forward. Returns (logits | hidden | embeddings, aux).

    ``ecfg``: legacy ElasticConfig (static shim) or new ElasticSpec.
    ``policy``: optional ElasticPolicy; pass it as a jitted-function argument
    to serve every compute budget from one compilation.
    ``bucket``: static ragged capacity-bucket size for traced policies under
    ``routing_impl == "ragged"`` (see core/policy.ragged_bucket) — one
    compile per bucket, FLOPs proportional to the bucket; the
    ``routing.IDENTITY_BUCKET`` sentinel (what ragged_bucket returns for
    all-full policies) compiles the IDENTITY graph, which skips routing
    work entirely while staying bit-exact.
    ``spec.kernel_backend`` decides whether each block's hot
    math (attention softmax core, fused MLP, MoE grouped matmul) executes
    through the Pallas kernels or the jnp twins — see kernels/ops.py."""
    spec, pol = as_spec_policy(ecfg, policy)
    pat = build_pattern(cfg, spec)
    if cfg.family == "encoder":
        x = batch["embeds"].astype(dtype_of(cfg)) @ params["in_proj"]
        rp = rparams if mode != "base" else None
        x, aux = _run_stack(params, rp, x, cfg=cfg, spec=spec, pol=pol,
                            mode=mode, pat=pat, causal=False,
                            remat=remat, bucket=bucket)
        return (norm_apply(params["final_norm"], x, cfg.norm, cfg.norm_eps),
                aux)
    enc_kv, enc_valid, aux0 = _context(params, rparams, batch, cfg, spec,
                                       pol, mode, remat)
    x = _embed(params, cfg, batch["tokens"])
    rp = rparams if mode != "base" else None
    x, aux = _run_stack(params, rp, x, cfg=cfg, spec=spec, pol=pol, mode=mode,
                        pat=pat, causal=True, enc_kv=enc_kv,
                        enc_valid=enc_valid, remat=remat, bucket=bucket)
    aux = aux + aux0
    with jax.named_scope("lm_head"):
        x = norm_apply(params["final_norm"], x, cfg.norm, cfg.norm_eps)
        out = x if return_hidden else _logits(params, cfg, x)
    return out, aux


# ------------------------------ serving --------------------------------------

def _walk(step, x, params, rparams, cfg, pat, pol, mode, caches=None):
    """Run ``step(ent, lp, lrp, lpol, x, lc) -> (x, new layer cache)``
    over the lead layers, the scanned period and the tail, with each
    layer's params, router params, policy and (when ``caches`` is given)
    cache. Returns (x, the new caches in the params' layout)."""
    static_pol = _pol_static(pol)
    layered = (not static_pol) and pol.has_layer_dim
    pol_split = _split_policy(pol, cfg.n_layers, pat) if layered else None
    has_rp = rparams is not None and mode != "base"
    eff = (lambda lpol: pol) if static_pol else \
        (lambda lpol: pol if lpol is None else lpol)
    out = {}

    def unrolled(part, x):
        lcs = caches[part] if caches is not None else None
        ncs = []
        for i, (lp, ent, lrp, lpol) in enumerate(_unrolled(
                part, params, rparams, pat, pol_split, has_rp=has_rp,
                static_pol=static_pol, pol=pol)):
            x, nc = step(ent, lp, lrp, eff(lpol), x,
                         None if lcs is None else lcs[i])
            ncs.append(nc)
        return x, ncs

    if "lead" in params:
        x, out["lead"] = unrolled("lead", x)

    def body(x, xs):
        lps = xs["p"]
        lrps = xs["r"] if has_rp else [None] * len(pat.period)
        lcs = xs["c"] if caches is not None else [None] * len(pat.period)
        lpols = xs.get("pol")
        ncs = []
        for j, ent in enumerate(pat.period):
            lpol = lpols[j] if lpols is not None else None
            x, nc = step(ent, lps[j], lrps[j], eff(lpol), x, lcs[j])
            ncs.append(nc)
        return x, ncs

    if params["scan"]:
        assert len(params["scan"]) == len(pat.period), (
            f"param stacking period ({len(params['scan'])}) != apply-time "
            f"pattern period ({len(pat.period)}): init and apply must use "
            f"the same elastic layers mode")
        xs = {"p": params["scan"]}
        if has_rp:
            xs["r"] = rparams["scan"]
        if caches is not None:
            xs["c"] = caches["scan"]
        if layered:
            xs["pol"] = pol_split["scan"]
        x, out["scan"] = jax.lax.scan(body, x, xs)
    else:
        out["scan"] = []
    x, out["tail"] = unrolled("tail", x)
    return x, out


def cache_init(cfg, batch: int, max_seq: int, kv_dtype: str = "fp32"):
    enc_len = cfg.n_image_tokens or cfg.encoder_seq
    caches = [block_cache_init(k, cfg, batch, max_seq, enc_len,
                               window=cfg.layer_windows[i],
                               kv_dtype=kv_dtype)
              for i, k in enumerate(cfg.layer_kinds)]
    return _split_layers(caches, build_pattern(cfg, None))


def prefill(params, rparams, batch, cfg, ecfg=None, mode: str = "infer",
            max_cache_len: int = 0, policy=None, bucket=None):
    """Forward + cache collection. Returns (logits_last (B,V), caches).
    ``bucket``: static ragged capacity-bucket hint (train-mode prefill)."""
    spec, pol = as_spec_policy(ecfg, policy)
    pat = build_pattern(cfg, spec)
    enc_kv, enc_valid, _ = _context(params, rparams, batch, cfg, spec, pol,
                                    mode)
    x = _embed(params, cfg, batch["tokens"])
    L = max_cache_len or x.shape[1]

    def step(ent, lp, lrp, lpol, x, _lc):
        x, _, nc = block_apply(
            ent.kind, lp, lrp, x, cfg=cfg, spec=spec, pol=lpol, mode=mode,
            elastic_on=ent.elastic, window=ent.window, causal=True,
            enc_kv=enc_kv, enc_valid=enc_valid, collect_cache=True,
            max_cache_len=L, bucket=bucket)
        return x, nc

    x, caches = _walk(step, x, params, rparams, cfg, pat, pol, mode)
    with jax.named_scope("lm_head"):
        x = norm_apply(params["final_norm"], x, cfg.norm, cfg.norm_eps)
        logits = _logits(params, cfg, x[:, -1])
    return logits, caches


def cache_insert(caches, row_caches, slot, cfg=None):
    """Splice a single-request cache tree (batch dim 1, collected by
    ``prefill`` at the slot array's ``max_cache_len``) into batch row
    ``slot`` of a live slot-array cache. ``slot`` may be traced, so ONE
    compiled insert serves every slot index. When ``cfg`` is given and a
    mesh is active, the spliced tree is pinned back to the serving cache
    shardings (kv-heads over `model`, slots over data) — the row update is
    a batch-dim dynamic_update_slice, which GSPMD would otherwise resolve
    by replicating the whole live cache."""
    out = {part: [cache_row_insert(f, r, slot,
                                   batch_axis=1 if part == "scan" else 0)
                  for f, r in zip(caches[part], row_caches[part])]
           for part in caches}
    if cfg is not None:
        from repro.runtime import sharding as SH
        out = SH.constrain_cache_tree(out, cfg)
    return out


def prefill_into_slot(params, rparams, batch, caches, slot, cfg, ecfg=None,
                      mode: str = "infer", max_cache_len: int = 0,
                      policy=None, live_policy=None, bucket=None):
    """Admission path for continuous batching: prefill ONE request (batch
    leaves carry a leading dim of 1) and splice its caches — and its solved
    per-request policy row — into row ``slot`` of the live slot arrays.

    Everything downstream of the (static) prompt-length bucket is traced:
    slot index, policy rows, and the live (B,)-leaf ``live_policy`` ride
    through one compiled graph, so admissions never recompile.
    Returns (last-token logits (1, V), caches, live_policy)."""
    logits, row = prefill(params, rparams, batch, cfg, ecfg, mode=mode,
                          max_cache_len=max_cache_len, policy=policy,
                          bucket=bucket)
    caches = cache_insert(caches, row, slot, cfg)
    if live_policy is not None and policy is not None:
        live_policy = live_policy.set_row(slot, policy)
    return logits, caches, live_policy


def decode_step(params, rparams, token, caches, t, cfg, ecfg=None,
                mode: str = "infer", policy=None, table=None, trash=None):
    """One decode step. token: (B,1) i32; t: scalar i32 position, or (B,)
    i32 per-row positions (continuous batching: each serving slot decodes
    at its own offset inside the same compiled step).
    Returns (logits (B,V), new caches). ``policy`` is traced: one compiled
    decode step serves every (mixed-per-request) budget.

    ``table``/``trash``: paged-KV mode — the (B, P) page-table rows and
    (B,) per-slot trash-page ids. One table serves EVERY layer: pages are
    allocated per slot once and each layer's pool slice is indexed with the
    same page ids, so the table rides the scan as a loop-invariant capture
    (never stacked into xs)."""
    spec, pol = as_spec_policy(ecfg, policy)
    pat = build_pattern(cfg, spec)
    x = _embed(params, cfg, token)

    def step(ent, lp, lrp, lpol, x, lc):
        return block_decode(
            ent.kind, lp, lrp, x, lc, t, cfg=cfg, spec=spec, pol=lpol,
            mode=mode, elastic_on=ent.elastic, window=ent.window,
            table=table, trash=trash)

    x, caches = _walk(step, x, params, rparams, cfg, pat, pol, mode, caches)
    with jax.named_scope("lm_head"):
        x = norm_apply(params["final_norm"], x, cfg.norm, cfg.norm_eps)
        logits = _logits(params, cfg, x[:, -1])
    return logits, caches


# --------------------------- paged serving -----------------------------------

def paged_cache_init(cfg, n_pages: int, page_size: int,
                     kv_dtype: str = "fp32"):
    """Paged twin of ``cache_init``: per-layer slices of the GLOBAL page
    pool, stacked into the same scan/tail pattern tree (scan leaves gain a
    leading period dim). Attention-only — validated per layer kind."""
    caches = [block_paged_cache_init(k, cfg, n_pages, page_size,
                                     kv_dtype=kv_dtype)
              for k in cfg.layer_kinds]
    return _split_layers(caches, build_pattern(cfg, None))


def prefill_chunk_step(params, rparams, tokens, caches, write_page, table_row,
                       pos0, plen, cfg, ecfg=None, mode: str = "infer",
                       policy=None):
    """One CHUNK of a paged prefill through the whole stack (the decode-
    shaped prefill graph): tokens is (1, C) i32 with C == page_size,
    zero-padded past ``plen``; ``write_page`` (scalar i32) is the pool page
    this chunk's K/V land in at EVERY layer (each layer's pool slice shares
    the id — same invariant as ``decode_step``'s table); ``table_row`` (P,)
    i32 is the slot's page-table row (entries <= this chunk present);
    ``pos0``/``plen`` are traced scalars. Chaining ceil(plen / C) calls of
    this ONE compiled graph replaces every per-length prefill bucket.
    Returns (logits (1, V) at the chunk's LAST REAL position — only the
    final chunk's logits feed sampling — and the new caches)."""
    spec, pol = as_spec_policy(ecfg, policy)
    pat = build_pattern(cfg, spec)
    x = _embed(params, cfg, tokens)

    def step(ent, lp, lrp, lpol, x, lc):
        return block_chunk(
            ent.kind, lp, lrp, x, lc, write_page, table_row, pos0, plen,
            cfg=cfg, spec=spec, pol=lpol, mode=mode, elastic_on=ent.elastic)

    x, caches = _walk(step, x, params, rparams, cfg, pat, pol, mode, caches)
    with jax.named_scope("lm_head"):
        x = norm_apply(params["final_norm"], x, cfg.norm, cfg.norm_eps)
        lidx = jnp.clip(jnp.asarray(plen, jnp.int32) - 1
                        - jnp.asarray(pos0, jnp.int32), 0, x.shape[1] - 1)
        h_last = jax.lax.dynamic_index_in_dim(x, lidx, axis=1,
                                              keepdims=False)
        logits = _logits(params, cfg, h_last)
    return logits, caches


# ------------------------------ input specs ----------------------------------

def batch_specs(cfg, seq_len: int, global_batch: int, kind: str):
    """ShapeDtypeStruct stand-ins for every model input of a shape cell."""
    i32 = jnp.int32
    B, S = global_batch, seq_len
    if kind == "decode":
        specs = {"token": jax.ShapeDtypeStruct((B, 1), i32)}
    elif cfg.family == "encoder":
        specs = {"embeds": jax.ShapeDtypeStruct(
            (B, cfg.n_image_tokens or S, cfg.d_frontend or cfg.d_model),
            jnp.float32)}
        return specs
    else:
        specs = {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
    if cfg.family == "vlm" and kind != "decode":
        specs["image_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.n_image_tokens, cfg.d_frontend), jnp.float32)
    if cfg.encoder is not None and kind != "decode":
        e = cfg.encoder
        specs["frames"] = jax.ShapeDtypeStruct(
            (B, e.encoder_seq, e.d_frontend or e.d_model), jnp.float32)
    return specs


def cache_specs(cfg, batch: int, max_seq: int, kv_dtype: str = "fp32"):
    return jax.eval_shape(lambda: cache_init(cfg, batch, max_seq,
                                             kv_dtype=kv_dtype))
