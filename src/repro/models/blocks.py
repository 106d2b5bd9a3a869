"""Transformer blocks with ElastiFormer routing woven in.

Block kinds (cfg.mixer_pattern):
  attn  : [token-route] GQA self-attention [head-route] [LoRA]  + MLP block
  xattn : same + cross-attention to encoder/image context       + MLP block
  ssm   : [token-route] Mamba2 SSD mixer (no MLP)
  rglru : [token-route] RG-LRU recurrent mixer                  + MLP block
  conv  : [token-route] LFM2 gated short convolution            + MLP block

The MLP block is dense, moefied (ElastiFormer experts over the dense MLP)
or the native MoE; a layer's params say which (a native MoE holds
``router``), so leading dense layers of a MoE model need no flag.

Elasticity is split into a static ``ElasticSpec`` (which routers exist —
shapes params and HLO) and a runtime ``ElasticPolicy`` (capacities, head/
expert top-k, decode threshold theta, teacher/student flag) — see
core/policy.py. Policy leaves that are python numbers are trace-time
constants (ragged capacity-bucket or legacy gather routing, real FLOP
savings); traced leaves serve every budget — including per-request (B,)
budgets — from ONE compiled block per ragged bucket (with a static
``bucket`` hint; see core/routing), or from a single full-shape rank-masked
graph without one.

Modes:
  base  : frozen pretrained model (the distillation teacher) — routers off.
  train : student; input-subset selection = top-k (capacity c), Alg. 2.
  infer : student; input-subset selection = threshold theta (§B.1).

Token routing semantics per mixer family:
  attention : top-k tokens attend among themselves (MoD semantics) — the
              ragged/gather paths deliver real FLOP savings in the lowered
              HLO; the masked path computes the same math at full shapes.
  ssm/rglru/conv : skipped tokens leave the recurrent state untouched
              (dt=0 / a=1 exact pass-through; conv: a skipped token never
              enters the convolution's history); dense-masked in both
              train and infer so train/infer semantics coincide.

Routed execution (this PR's hot path): train-mode top-k selection is
planned ONCE per block (core/routing.RoutingPlan — one sort, shared by the
attention and MLP/MoE students; each weights the shared token set with its
own router), full-budget policies compile the identity graph (no routing
work, bit-exact teacher), and ``spec.kernel_backend`` dispatches the block
math through the Pallas kernels (flash attention with scalar-prefetched
kv_count, fused MLP, grouped expert matmul, ring-cache decode
attention) or their jnp twins.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import routing as R
from repro.kernels import ops as OPS
from repro.runtime import sharding as SH
from repro.core.moefy import moefy_mlp
from repro.core.lora import lora_init
from repro.models import attention as A
from repro.models import quant
from repro.models import rglru as G
from repro.models import shortconv as SC
from repro.models import ssm as S
from repro.models.layers import mlp_apply, mlp_init, norm_apply, norm_init
from repro.models.moe import moe_apply, moe_decode, moe_init


def has_mlp(kind: str) -> bool:
    return kind != "ssm"


def is_attn(kind: str) -> bool:
    return kind in ("attn", "xattn")


# ------------------------------ init ---------------------------------------

def is_moe(p) -> bool:
    """Whether a layer's MLP params are a native MoE."""
    return "router" in p["mlp"]


def block_init(key, kind: str, cfg, *, moe: bool):
    """One layer's params; ``moe``: its MLP is the native MoE."""
    ks = jax.random.split(key, 6)
    p = {"norm1": norm_init(cfg.d_model, cfg.norm)}
    if is_attn(kind):
        p["attn"] = A.attn_init(ks[0], cfg)
    elif kind == "ssm":
        p["mixer"] = S.ssm_init(ks[0], cfg)
    elif kind == "rglru":
        p["mixer"] = G.rglru_init(ks[0], cfg)
    elif kind == "conv":
        p["mixer"] = SC.conv_init(ks[0], cfg)
    else:
        raise ValueError(kind)
    if kind == "xattn":
        p["xnorm"] = norm_init(cfg.d_model, cfg.norm)
        p["xattn"] = A.attn_init(ks[1], cfg, cross=True)
    if has_mlp(kind):
        p["norm2"] = norm_init(cfg.d_model, cfg.norm)
        p["mlp"] = moe_init(ks[2], cfg) if moe else mlp_init(ks[2], cfg)
    return p


def block_router_init(key, kind: str, cfg, spec, *, moe: bool):
    """Trainable ElastiFormer params for one layer (tiny; see Table 1).
    ``spec`` is the static ElasticSpec: it alone decides which routers exist.
    A native MoE layer (``moe``) has no expert router of its own: the
    elastic expert knob keeps the top of its native router's choice."""
    D = cfg.d_model
    ks = jax.random.split(key, 6)
    rp = {}
    if spec.depth_routed:
        # per-token whole-layer skip: same scalar-logit router as the
        # token routers, gating the ENTIRE block (mixer + MLP + KV write).
        # fold_in (not a wider split): the 6-way split above must stay
        # byte-identical for specs without depth, or enabling the feature
        # flag would shift EVERY router's init
        rp["depth"] = R.token_router_init(jax.random.fold_in(key, 6), D)
    if spec.mha_token_routed:
        rp["tok_mixer"] = R.token_router_init(ks[0], D)
    if is_attn(kind):
        if spec.mha_head_routed:
            rp["head"] = R.param_router_init(ks[1], D, cfg.n_heads)
        if spec.lora_rank:
            rp["lora"] = {
                "q": lora_init(ks[2], D, cfg.n_heads * cfg.d_head, spec.lora_rank),
                "v": lora_init(ks[3], D, cfg.n_kv_heads * cfg.d_head, spec.lora_rank),
            }
    if has_mlp(kind):
        if spec.mlp_token_routed:
            rp["tok_mlp"] = R.token_router_init(ks[4], D)
        if spec.mlp_n_experts and spec.expert_routed and not moe:
            rp["expert"] = R.param_router_init(ks[5], D, spec.mlp_n_experts)
    return rp


# ------------------------- helpers ------------------------------------------

def _expert_args(pol, n_experts: int) -> dict:
    """moe_apply/moe_decode kwargs for the elastic expert budget: a static
    int keeps the small-k graph; a traced count sizes buffers for all E and
    masks (one graph, any budget)."""
    k = R.gate_topk(pol.mlp_expert_topk, pol.student, n_experts)
    if R.is_static(k):
        return {"top_k": min(int(k), n_experts)}
    return {"top_k": n_experts, "top_k_traced": k}


def _lora_gate(lora, cap, student):
    """Disable the LoRA rescue adapters exactly when there is nothing to
    rescue: mha token budget full, or the policy is in teacher mode — this
    keeps budget-1.0 rows bit-lossless even with trained adapters.
    ``cap`` is the (already student-gated) mha token capacity or None."""
    if lora is None:
        return None
    if cap is not None:
        full = R.is_full(cap)
    elif student is None or R.is_static(student):
        full = student is not None and student <= 0
    else:
        full = jnp.asarray(student) <= 0
    if R.is_static(full):
        return None if full else lora
    return {**lora, "scale": 1.0 - jnp.asarray(full, jnp.float32)}


@jax.named_scope("router")
def _head_weights(rp, h, spec, pol, cfg, auxes, valid=None):
    if rp is None or spec is None or "head" not in rp \
            or not spec.mha_head_routed:
        return None
    k = R.gate_topk(pol.mha_head_topk, pol.student, cfg.n_heads)
    w, m, a = R.param_route_weights(rp["head"], h, k, valid=valid)
    auxes.append(a)
    hw = w * m
    full = R.is_full(k, cfg.n_heads)
    if R.is_static(full):
        return jnp.ones_like(hw) if full else hw
    return jnp.where(R.bcast_to(full, hw.ndim), 1.0, hw)


def _mlp_fn(p, rp, cfg, spec, pol, elastic_on, mode, auxes, backend=None):
    """Returns f(h_sub, pos_sub[, token_valid, dispatch_frac, token_count])
    for the MLP/MoE sub-block. The masked (traced-capacity) token-routing
    path hands in ``token_valid``/``dispatch_frac`` so skipped tokens cannot
    evict kept ones from expert capacity; the ragged bucket path hands in
    ``token_valid``/``token_count`` (prefix buffers) — either way the
    dispatch buffers match what the static gather path would have compiled
    for the same budget. ``backend`` "pallas"/"interpret" executes the
    dense MLP through ``kernels.ops.fused_mlp`` (``token_count`` becomes
    the kernel's scalar-prefetched ``valid_count``) and expert dispatch
    through ``kernels.ops.moe_gmm``."""
    @jax.named_scope("mlp")
    def f(h, _pos, token_valid=None, dispatch_frac=None, token_count=None):
        if is_moe(p):
            # native experts: dropless dispatch, the native router; the
            # elastic expert knob keeps the top of its top-k
            m = cfg.moe
            kn = (_expert_args(pol, m.top_k)
                  if elastic_on and mode != "base" and spec is not None
                  and spec.expert_routed else {"top_k": m.top_k})
            y, a = moe_apply(
                p["mlp"], h, act=cfg.act, seq_chunk=m.seq_chunk,
                token_valid=token_valid, dispatch_frac=dispatch_frac,
                token_count=token_count, backend=backend, router=m.router,
                **kn)
            auxes.append(a)
            return y
        if (elastic_on and rp and "expert" in rp and mode != "base"
                and spec.mlp_n_experts):
            ep = moefy_mlp(p["mlp"], spec.mlp_n_experts)
            # seq_chunk bounds the (B,E,C,D) dispatch buffers: 512 keeps
            # the f32 scatter-upcast live set ~1.3 GB/dev (vs 8.5 GB at a
            # full-sequence chunk) — §Perf H4 (HBM fit).
            y, a = moe_apply(
                ep, h, act=cfg.act,
                router_w=rp["expert"]["w"], normalize_to_m=True,
                seq_chunk=512, token_valid=token_valid,
                dispatch_frac=dispatch_frac, token_count=token_count,
                backend=backend,
                **_expert_args(pol, spec.mlp_n_experts))
            auxes.append(a)
            return y
        if backend in ("pallas", "interpret"):
            mp = p["mlp"]
            return OPS.fused_mlp_sharded(
                h, mp["wi"], mp["wo"], mp.get("wg"), valid_count=token_count,
                wi_scale=mp.get("wi_scale"), wo_scale=mp.get("wo_scale"),
                wg_scale=mp.get("wg_scale"), act=cfg.act, backend=backend)
        return mlp_apply(p["mlp"], h, cfg.act)
    return f


# --------------------- full-sequence block apply ----------------------------

def _combine_caps(cap_a, cap_b):
    """Block-level plan capacity: the elementwise max of the active
    components' (already student-gated) token capacities. The budget
    solver and every policy constructor set them equal; when a caller
    hands diverging per-component capacities the shared plan covers the
    larger one (and the smaller component rides the same token set)."""
    if cap_a is None:
        return cap_b
    if cap_b is None:
        return cap_a
    if R.is_static(cap_a) and R.is_static(cap_b):
        return max(cap_a, cap_b)
    return jnp.maximum(jnp.asarray(cap_a, jnp.float32),
                       jnp.asarray(cap_b, jnp.float32))


def _mul_caps(cap_a, cap_b):
    """Multiplicative capacity composition (the depth axis): the depth
    router skips the WHOLE layer for unselected tokens, so a component's
    effective token fraction is its own capacity x the depth capacity —
    depth 0.75 x token 0.75 runs ~0.56 of the component's tokens, the
    same product the roofline solver's ``_active_fraction`` models. Each
    factor is clamped at 1 first (capacity >= 1 means "full", not "more")."""
    if cap_a is None:
        return cap_b
    if cap_b is None:
        return cap_a
    if R.is_static(cap_a) and R.is_static(cap_b):
        return min(1.0, cap_a) * min(1.0, cap_b)
    return (jnp.minimum(jnp.asarray(cap_a, jnp.float32), 1.0)
            * jnp.minimum(jnp.asarray(cap_b, jnp.float32), 1.0))


def block_apply(
    kind: str, p, rp, x, *, cfg, spec, pol=None, mode: str, elastic_on: bool,
    window: int = 0, positions=None, causal: bool = True,
    enc_kv=None, enc_valid=None, collect_cache: bool = False,
    max_cache_len: int = 0, bucket=None, spmd_auto: bool = True,
):
    """x: (B,S,D) -> (x', aux[, cache]). Pre-norm residual block.

    Train-mode token routing is planned ONCE per block: a single
    ``RoutingPlan`` (one sort — see core/routing) built from the block's
    primary token router (the mixer router when attention is token-routed,
    else the MLP router) is shared by the attention and MLP/MoE students —
    each component weights the shared token set with its OWN router's
    scores (straight-through gradients to both routers) and BCE-trains its
    router against the shared membership. Per-component capacities are
    unified at the block level (``_combine_caps``); the budget solver
    always sets them equal.

    ``bucket``: static plan-buffer hint for traced-capacity routing under
    ``spec.routing_impl == "ragged"`` (see core/policy.ragged_bucket). It
    must cover the largest per-row top-k this graph will see;
    ``routing.IDENTITY_BUCKET`` asserts every row is at full budget and
    compiles the IDENTITY fast path (no partition/gather/scatter — the
    bit-exact teacher math, with router aux losses still emitted); None
    falls back to the dense rank-masked path. ``spec.kernel_backend``
    selects how the hot math executes (Pallas kernels vs jnp twins — see
    kernels/ops.py).

    ``spmd_auto``: True when this trace runs in a GSPMD-auto region (no
    enclosing manual shard_map), where mesh-wide sharding constraints are
    legal — the serving prefill path. ``_run_stack`` sets it False inside
    its manual-over-batch-axes wrap. (The kernel wrappers in
    kernels/ops.py detect that region themselves.)"""
    B, Seq = x.shape[:2]
    auxes = [R.RouteAux.zero()]
    if positions is None:
        positions = jnp.arange(Seq, dtype=jnp.int32)
    routed = elastic_on and mode != "base"
    backend = OPS.resolve_backend(
        spec.kernel_backend if spec is not None else None)
    cache = {}

    # ---- block-level routing plan resolution ----
    cap_mha = cap_mlp = cap_depth = None
    if routed and spec is not None and rp:
        if spec.depth_routed and "depth" in rp:
            cap_depth = R.gate_capacity(pol.depth_capacity, pol.student)
        if spec.mha_token_routed and "tok_mixer" in rp:
            cap_mha = R.gate_capacity(pol.mha_token_capacity, pol.student)
        if has_mlp(kind) and spec.mlp_token_routed and "tok_mlp" in rp:
            cap_mlp = R.gate_capacity(pol.mlp_token_capacity, pol.student)
    # depth composes multiplicatively (it skips the whole layer), so the
    # block plan's capacity is depth x the max of the per-component caps
    cap_plan = _mul_caps(_combine_caps(cap_mha, cap_mlp), cap_depth)
    impl = spec.routing_impl if spec is not None else "gather"
    kb = None
    if mode == "train" and cap_plan is not None and (
            impl == "ragged" or (impl == "gather" and R.is_static(cap_plan)
                                 and R.is_static(pol.theta))):
        kb = R.resolve_bucket(cap_plan, Seq, bucket, impl=impl)
    identity = kb == Seq            # full budget everywhere: skip routing
    k_plan = None if (kb is None or identity) else \
        R.capacity_k(cap_plan, Seq, mxu=True)
    plan = None                     # built lazily by the first consumer
    # mixer-stage routers, OUTERMOST first: the depth router (whole-layer
    # skip) is the block's primary plan router when present, then the
    # mixer token router. The first entry builds the plan; the rest weight
    # the shared token set and BCE-train toward its membership.
    mixer_routers = []
    if cap_depth is not None:
        mixer_routers.append(("depth", cap_depth))
    if cap_mha is not None:
        mixer_routers.append(("tok_mixer", cap_mha))
    plan_on_mixer = bool(mixer_routers)
    depth_scores = None       # depth sigmoid over the full sequence
    depth_w_sel = None        # depth weight on the plan's selected set
    depth_gate = None         # infer-mode depth threshold gate (keep, w)

    @jax.named_scope("router")
    def build_plan(h_src):
        """The block's ONE RoutingPlan sort, from the primary router.
        Under a mesh the plan arrays stay replicated over `model` (batch
        over data), so one plan drives every TP shard of the block."""
        name = mixer_routers[0][0] if mixer_routers else "tok_mlp"
        logits = R.token_logits(rp[name], h_src)
        scores = jax.nn.sigmoid(logits)
        plan = R.make_plan(scores, k_plan, kb)
        if spmd_auto and SH.active_mesh() is not None:
            plan = R.constrain_plan(plan)
        return plan, logits, scores

    def bce_aux(logits, keep, train):
        if train:
            auxes.append(R.RouteAux.of(topk=R.bce_topk_loss(logits, keep),
                                       keep=keep))
        else:
            auxes.append(R.RouteAux.of(keep=keep))

    @jax.named_scope("router")
    def plan_weights(plan, logits, scores, h_src):
        """Mixer-stage weight on the plan's selected set: the primary
        router's scores times every secondary mixer router's, each
        BCE-trained toward the shared membership (straight-through)."""
        nonlocal depth_scores, depth_w_sel
        w_sel = jnp.take_along_axis(scores, plan.idx, 1)
        bce_aux(logits, plan.keep, train=True)
        if mixer_routers and mixer_routers[0][0] == "depth":
            depth_scores = scores
            depth_w_sel = w_sel * plan.valid
        for name, _c in mixer_routers[1:]:
            lg = R.token_logits(rp[name], h_src)
            w_sel = w_sel * jnp.take_along_axis(jax.nn.sigmoid(lg),
                                                plan.idx, 1)
            bce_aux(lg, plan.keep, train=True)
        return w_sel * plan.valid

    @jax.named_scope("router")
    def mixer_gate(h_src):
        """Dense/threshold gate over every mixer-stage router. Train: the
        PRIMARY router rank-masks at the shared plan capacity (secondary
        routers contribute weight only — the plan path's semantics).
        Infer: each router thresholds at theta independently; keeps AND
        and weights multiply (matching the decode gate)."""
        nonlocal depth_scores, depth_gate
        name0, _c0 = mixer_routers[0]
        logits = R.token_logits(rp[name0], h_src)
        scores = jax.nn.sigmoid(logits)
        if name0 == "depth":
            depth_scores = scores
        if mode == "train":
            keep, wtok = R.token_gate(logits, scores, cap_plan, mode,
                                      theta=pol.theta, mxu=True)
            bce_aux(logits, keep, train=True)
            full = R.is_full(cap_plan)
            for name, _c in mixer_routers[1:]:
                lg = R.token_logits(rp[name], h_src)
                sc = jax.nn.sigmoid(lg)
                if R.is_static(full):
                    wtok = wtok if full else wtok * sc
                else:
                    wtok = wtok * jnp.where(R.bcast_to(full, keep.ndim),
                                            1.0, sc)
                bce_aux(lg, keep, train=True)
            return keep, wtok
        keep, wtok = None, None
        for name, c in mixer_routers:
            lg = logits if name == name0 else R.token_logits(rp[name], h_src)
            sc = scores if name == name0 else jax.nn.sigmoid(lg)
            kp, w = R.token_gate(lg, sc, c, mode, theta=pol.theta, mxu=True)
            bce_aux(lg, kp, train=False)
            if name == "depth":
                depth_gate = (kp, w)
            keep = kp if keep is None else keep & kp
            wtok = w if wtok is None else wtok * w
        return keep, wtok

    # ---- temporal mixer ----
    h = norm_apply(p["norm1"], x, cfg.norm, cfg.norm_eps)
    dense_keep = None               # shared keep of the dense fallback

    if is_attn(kind):
        lora = rp.get("lora") if (routed and rp) else None
        lora = _lora_gate(lora, _mul_caps(cap_mha, cap_depth),
                          pol.student if (routed and pol is not None) else None)
        if not mixer_routers:
            hw = _head_weights(rp if routed else None, h, spec, pol, cfg,
                               auxes) if routed else None
            y, k, v = A.attn_apply(p["attn"], h, cfg=cfg, positions=positions,
                                   causal=causal, window=window,
                                   head_weights=hw, lora=lora,
                                   backend=backend)
            delta, keep = y, jnp.ones((B, Seq), bool)
        elif identity:
            # full budget on every row: bit-exact teacher attention, no
            # partition/sort/masking — every mixer-stage router (depth
            # included) still trains (BCE toward keep-everything, exactly
            # what the dense path emits at 1.0)
            keep = jnp.ones((B, Seq), bool)
            for name, _c in mixer_routers:
                bce_aux(R.token_logits(rp[name], h), keep, train=True)
            hw = _head_weights(rp, h, spec, pol, cfg, auxes)
            y, k, v = A.attn_apply(p["attn"], h, cfg=cfg, positions=positions,
                                   causal=causal, window=window,
                                   head_weights=hw, lora=lora,
                                   backend=backend)
            delta = y
        elif kb is not None:
            # shared plan (ragged capacity bucket, or exact static gather):
            # selected tokens gathered valid-first (position-ascending
            # prefix), tail filled + masked. Static caps derive the bucket
            # here (budgets sharing a bucket share the compile); traced
            # caps ride the caller's static bucket hint. With depth routed
            # the plan is the depth router's (outermost) selection —
            # unselected tokens ride the residual through the WHOLE block.
            plan, logits, scores = build_plan(h)
            h_sel = R.plan_gather(h, plan)
            pos_sel = jnp.take_along_axis(
                jnp.broadcast_to(positions, (B, Seq)), plan.idx, 1)
            hw = _head_weights(rp, h_sel, spec, pol, cfg, auxes,
                               valid=plan.valid)
            y_sel, k, v = A.attn_apply(p["attn"], h_sel, cfg=cfg,
                                       positions=pos_sel, causal=causal,
                                       window=window, kv_valid=plan.valid,
                                       kv_count=plan.count, head_weights=hw,
                                       lora=lora, backend=backend,
                                       gathered=True)
            w_sel = plan_weights(plan, logits, scores, h)
            delta = R.plan_scatter(
                plan, x, y_sel * w_sel[..., None].astype(y_sel.dtype))
            keep = plan.keep
            if collect_cache:  # scatter valid k/v back to full positions
                k = _scatter_kv(k, plan.idx, B, Seq)
                v = _scatter_kv(v, plan.idx, B, Seq)
        else:  # threshold (infer/prefill), dense_mask, or traced capacity
            keep, wtok = mixer_gate(h)
            if mode == "train":
                dense_keep = keep
            # head-router stats over the SELECTED tokens only, matching
            # the plan path (whose buffer holds exactly the selected set)
            hw = _head_weights(rp, h, spec, pol, cfg, auxes,
                               valid=keep if mode == "train" else None)
            y, k, v = A.attn_apply(p["attn"], h, cfg=cfg, positions=positions,
                                   causal=causal, window=window,
                                   kv_valid=keep, head_weights=hw, lora=lora,
                                   backend=backend)
            delta = y * wtok[..., None].astype(y.dtype)
        if collect_cache:
            L = max_cache_len or Seq
            cache["attn"] = _pad_cache(
                k, v, keep, L, window,
                kv_dtype=spec.kv_dtype if spec is not None else "fp32")
    else:  # ssm / rglru / conv — dense masked routing (state pass-through)
        keep = None
        if mixer_routers:
            if identity:
                keep, wtok = None, None
                ones = jnp.ones((B, Seq), bool)
                for name, _c in mixer_routers:
                    bce_aux(R.token_logits(rp[name], h), ones, train=True)
            elif kb is not None:
                # recurrent mixers cannot gather (state pass-through): they
                # consume the shared plan's MEMBERSHIP as a dense mask
                plan, logits, scores = build_plan(h)
                keep = plan.keep
                if mixer_routers[0][0] == "depth":
                    depth_scores = scores
                    depth_w_sel = jnp.take_along_axis(
                        scores, plan.idx, 1) * plan.valid
                wtok = keep * scores
                bce_aux(logits, keep, train=True)
                for name, _c in mixer_routers[1:]:
                    lg = R.token_logits(rp[name], h)
                    wtok = wtok * jax.nn.sigmoid(lg)
                    bce_aux(lg, keep, train=True)
            else:
                keep, wtok = mixer_gate(h)
                if mode == "train":
                    dense_keep = keep
        if kind == "ssm":
            y, (st, cv) = S.ssm_apply(p["mixer"], h, cfg, keep_mask=keep)
            if collect_cache:
                cache["ssm"] = {"state": st, "conv": cv}
        elif kind == "conv":
            y, st = SC.conv_apply(p["mixer"], h, cfg, keep_mask=keep)
            if collect_cache:
                cache["conv"] = st
        else:
            y, (st, cv) = G.rglru_apply(p["mixer"], h, cfg, keep_mask=keep)
            if collect_cache:
                cache["rglru"] = {"state": st, "conv": cv}
        if keep is None:
            delta = y
        else:
            delta = y * wtok[..., None].astype(y.dtype)
    x = x + delta

    # ---- cross attention (xattn) ----
    if kind == "xattn":
        hx = norm_apply(p["xnorm"], x, cfg.norm, cfg.norm_eps)
        lora = None
        y, xk, xv = A.attn_apply(
            p["xattn"], hx, cfg=cfg, positions=positions, causal=False,
            kv_x=enc_kv, kv_positions=jnp.arange(enc_kv.shape[1]),
            kv_valid=enc_valid, use_rope=False, backend=backend)
        x = x + y
        if collect_cache:
            ev = (jnp.ones(enc_kv.shape[:2], bool) if enc_valid is None
                  else jnp.broadcast_to(enc_valid, enc_kv.shape[:2]))
            cache["xattn"] = {"k": xk, "v": xv, "valid": ev}

    # ---- MLP ----
    if has_mlp(kind):
        h = norm_apply(p["norm2"], x, cfg.norm, cfg.norm_eps)
        f = _mlp_fn(p, rp, cfg, spec, pol, elastic_on, mode, auxes,
                    backend=backend)
        if cap_mlp is None and cap_depth is None:
            delta = f(h, positions)
        elif identity:
            if cap_mlp is not None:
                bce_aux(R.token_logits(rp["tok_mlp"], h),
                        jnp.ones((B, Seq), bool), train=True)
            delta = f(h, positions)
        elif kb is not None:
            # reuse the block plan (built by the mixer when it is routed;
            # otherwise this IS the block's one sort, on the MLP router).
            # The depth weight (outermost selection) multiplies the MLP's
            # own router weight — the whole-block delta is depth-gated.
            if plan is None:
                plan, logits, scores = build_plan(h)
                w_sel = jnp.take_along_axis(scores, plan.idx, 1) * plan.valid
                bce_aux(logits, plan.keep, train=True)
            else:
                if cap_mlp is not None:
                    logits = R.token_logits(rp["tok_mlp"], h)
                    scores = jax.nn.sigmoid(logits)
                    w_sel = jnp.take_along_axis(
                        scores, plan.idx, 1) * plan.valid
                    bce_aux(logits, plan.keep, train=True)
                else:
                    w_sel = plan.valid.astype(jnp.float32)
                if depth_w_sel is not None:
                    w_sel = w_sel * depth_w_sel
            h_sel = R.plan_gather(h, plan)
            pos_sel = jnp.take_along_axis(
                jnp.broadcast_to(positions, (B, Seq)), plan.idx, 1)
            y_sel = f(h_sel, pos_sel, token_valid=plan.valid,
                      token_count=plan.count)
            delta = R.plan_scatter(
                plan, x, y_sel * w_sel[..., None].astype(y_sel.dtype))
        elif mode == "train":
            # dense fallback (traced capacity without a covering bucket, or
            # dense_mask impl): selection shared with the mixer stage when
            # it ran; expert dispatch is barred from skipped tokens so the
            # one-graph result matches the per-budget plan compile
            logits = scores = None
            if cap_mlp is not None:
                logits = R.token_logits(rp["tok_mlp"], h)
                scores = jax.nn.sigmoid(logits)
            if dense_keep is not None:
                keep = dense_keep
                w = keep.astype(jnp.float32)
                if scores is not None:
                    w = w * scores
                if depth_scores is not None:
                    w = w * depth_scores
                full = R.is_full(cap_plan)
                if R.is_static(full):
                    wtok = jnp.ones_like(w) if full else w
                else:
                    wtok = jnp.where(R.bcast_to(full, keep.ndim), 1.0, w)
            else:
                keep, wtok = R.token_gate(logits, scores, cap_plan, mode,
                                          theta=pol.theta, mxu=True)
            y = f(h, positions, token_valid=keep, dispatch_frac=cap_plan)
            delta = y * wtok[..., None].astype(y.dtype)
            if logits is not None:
                bce_aux(logits, keep, train=True)
        else:
            # inference thresholding (§B.1): per-token, per-router gate;
            # the depth router's threshold gate (already emitted in the
            # mixer stage) multiplies the whole delta
            if cap_mlp is None:
                delta = f(h, positions)
            else:
                delta, a = R.route_tokens(
                    rp["tok_mlp"], h, f, cap_mlp, mode, positions=positions,
                    impl=impl, theta=pol.theta if pol is not None else 0.5,
                    bucket=bucket)
                auxes.append(a)
            if depth_gate is not None:
                _dk, dw = depth_gate
                delta = delta * dw[..., None].astype(delta.dtype)
        x = x + delta

    aux = auxes[0]
    for a in auxes[1:]:
        aux = aux + a
    return (x, aux, cache) if collect_cache else (x, aux)


def _scatter_kv(t, idx, b, s):
    out = jnp.zeros((b, s) + t.shape[2:], t.dtype)
    bi = jnp.arange(b)[:, None]
    return out.at[bi, idx].set(t)


def _pad_cache(k, v, keep, max_len: int, window: int = 0,
               kv_dtype: str = "fp32"):
    """Lay prefill k/v into the ring-cache format (slot = pos % L).

    ``kv_dtype`` "int8" quantizes here — the ring's one-shot-prefill WRITE
    site (docs/quantization.md): decode steps then dequantize the stored
    rows, so the cache row a later decode reads is identical to what a
    decode-time write of the same token would have stored. (The in-flight
    prefill attention above ran on the f32 k/v — that is the documented
    ring-vs-paged bit-stability caveat.) "bf16" narrowing is handled by
    the `.astype` at the `cache_row_insert` splice."""
    B, S = k.shape[:2]
    L = min(max_len, window) if window and window > 0 else max_len
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    quantized = kv_dtype == "int8"
    if quantized:
        k, ks = quant.quantize_kv(k)                     # (B,S,K,Dh),(B,S,K)
        v, vs = quant.quantize_kv(v)
    if S <= L:
        pad = L - S
        pw = [(0, 0), (0, pad), (0, 0), (0, 0)]
        out = {"k": jnp.pad(k, pw), "v": jnp.pad(v, pw),
               "valid": jnp.pad(keep, [(0, 0), (0, pad)]),
               "pos": jnp.pad(pos, [(0, 0), (0, pad)], constant_values=-1)}
        if quantized:
            sw = [(0, 0), (0, pad), (0, 0)]
            out["kscale"] = jnp.pad(ks, sw, constant_values=1.0)
            out["vscale"] = jnp.pad(vs, sw, constant_values=1.0)
        return out
    # keep the last L positions, scattered to their ring slots
    k, v = k[:, -L:], v[:, -L:]
    keep, pos = keep[:, -L:], pos[:, -L:]
    slots = pos % L
    bi = jnp.arange(B)[:, None]
    out = {
        "k": jnp.zeros_like(k).at[bi, slots].set(k),
        "v": jnp.zeros_like(v).at[bi, slots].set(v),
        "valid": jnp.zeros_like(keep).at[bi, slots].set(keep),
        "pos": jnp.full_like(pos, -1).at[bi, slots].set(pos),
    }
    if quantized:
        ks, vs = ks[:, -L:], vs[:, -L:]
        out["kscale"] = jnp.ones_like(ks).at[bi, slots].set(ks)
        out["vscale"] = jnp.ones_like(vs).at[bi, slots].set(vs)
    return out


# ------------------------------ decode --------------------------------------

@jax.named_scope("router")
def _decode_token_gate(rp, name, h, cap, pol):
    """Threshold gate for one decode token: (keep (B,), weight (B,)).
    capacity >= 1 or student off forces (keep all, weight 1) per row."""
    logits = R.token_logits(rp[name], h)[:, 0]               # (B,)
    keep = logits > R.threshold_logit(pol.theta)
    w = keep * jax.nn.sigmoid(logits)
    full = R.is_full(R.gate_capacity(cap, pol.student))
    if R.is_static(full):
        if full:
            return jnp.ones_like(keep, bool), jnp.ones_like(w)
        return keep, w
    full = jnp.broadcast_to(full, keep.shape)
    return keep | full, jnp.where(full, 1.0, w)


def block_decode(kind: str, p, rp, x, cache, t, *, cfg, spec, pol=None,
                 mode: str, elastic_on: bool, window: int = 0,
                 table=None, trash=None):
    """One token. x: (B,1,D); returns (x', new_cache).

    ``table``/``trash``: paged-KV operands (the per-slot page-table rows
    and per-slot trash-page ids — see attention.attn_decode_paged). When
    given and the cache is a page pool ({'kp','vp','pvalid'}), decode
    attention appends through the page table instead of the ring."""
    B = x.shape[0]
    routed = elastic_on and mode != "base" and rp is not None
    backend = OPS.resolve_backend(
        spec.kernel_backend if spec is not None else None)
    new_cache = dict(cache)

    h = norm_apply(p["norm1"], x, cfg.norm, cfg.norm_eps)
    keepd, wd = None, None
    if routed and spec.depth_routed and "depth" in rp:
        # per-(slot, layer) whole-layer skip: the token writes NO KV at
        # this layer (write gate below), the mask leaf records it, and
        # the block delta is depth-weighted — unselected slots ride the
        # residual untouched
        keepd, wd = _decode_token_gate(rp, "depth", h, pol.depth_capacity,
                                       pol)
    keep, w1 = None, None
    if routed and spec.mha_token_routed and "tok_mixer" in rp:
        keep, w1 = _decode_token_gate(rp, "tok_mixer", h,
                                      pol.mha_token_capacity, pol)
    if keepd is not None:
        keep = keepd if keep is None else keep & keepd
        w1 = wd if w1 is None else w1 * wd

    auxes = []
    if is_attn(kind):
        lora = rp.get("lora") if routed else None
        if lora is not None:
            dcap = R.gate_capacity(pol.mha_token_capacity, pol.student) \
                if spec.mha_token_routed else None
            dcap = _mul_caps(
                dcap, R.gate_capacity(pol.depth_capacity, pol.student)
                if spec.depth_routed else None)
            lora = _lora_gate(lora, dcap, pol.student)
        hw = _head_weights(rp if routed else None, h, spec, pol, cfg,
                           auxes) if routed else None
        if table is not None and "kp" in cache["attn"]:
            y, new_cache["attn"] = A.attn_decode_paged(
                p["attn"], h, cache["attn"], t, table, trash, cfg=cfg,
                head_weights=hw, lora=lora, write=keep, backend=backend)
        else:
            y, new_cache["attn"] = A.attn_decode(
                p["attn"], h, cache["attn"], t, cfg=cfg, window=window,
                head_weights=hw, lora=lora, write=keep, backend=backend)
    elif kind == "ssm":
        y, new_cache["ssm"] = S.ssm_decode(p["mixer"], h, cache["ssm"], cfg,
                                           write=keep)
    elif kind == "conv":
        y, new_cache["conv"] = SC.conv_decode(p["mixer"], h, cache["conv"],
                                              cfg, write=keep)
    else:
        y, new_cache["rglru"] = G.rglru_decode(p["mixer"], h, cache["rglru"],
                                               cfg, write=keep)
    if keep is not None:
        y = y * w1[:, None, None].astype(y.dtype)
    x = x + y

    if kind == "xattn":
        hx = norm_apply(p["xnorm"], x, cfg.norm, cfg.norm_eps)
        xc = cache["xattn"]
        pos = jnp.zeros((B, 1), jnp.int32)
        kvp = jnp.broadcast_to(jnp.arange(xc["k"].shape[1], dtype=jnp.int32),
                               xc["k"].shape[:2])
        with jax.named_scope("attention"):
            mask = A._mask(pos, kvp, False, 0, xc["valid"])
            q = A._project_q(p["xattn"], hx, pos, cfg, None, False)
            ctx = A.sdpa(q, xc["k"], xc["v"], mask)
            y = jnp.einsum("bshk,hkd->bsd", ctx,
                           quant.maybe_dequant(p["xattn"], "wo", ctx.dtype))
        x = x + y

    if has_mlp(kind):
        h = norm_apply(p["norm2"], x, cfg.norm, cfg.norm_eps)
        keep2, w2 = None, None
        if routed and spec.mlp_token_routed and "tok_mlp" in rp:
            keep2, w2 = _decode_token_gate(rp, "tok_mlp", h,
                                           pol.mlp_token_capacity, pol)
        if keepd is not None:   # depth gates the MLP delta too
            keep2 = keepd if keep2 is None else keep2 & keepd
            w2 = wd if w2 is None else w2 * wd
        with jax.named_scope("mlp"):
            if is_moe(p):
                m = cfg.moe
                kn = (_expert_args(pol, m.top_k)
                      if routed and spec.expert_routed else {"top_k": m.top_k})
                y, _ = moe_decode(p["mlp"], h, act=cfg.act, router=m.router,
                                  **kn)
            elif routed and "expert" in rp and spec.mlp_n_experts:
                ep = moefy_mlp(p["mlp"], spec.mlp_n_experts)
                y, _ = moe_decode(ep, h, act=cfg.act,
                                  router_w=rp["expert"]["w"],
                                  normalize_to_m=True,
                                  **_expert_args(pol, spec.mlp_n_experts))
            else:
                y = mlp_apply(p["mlp"], h, cfg.act)
        if keep2 is not None:
            y = y * w2[:, None, None].astype(y.dtype)
        x = x + y
    return x, new_cache


def block_chunk(kind: str, p, rp, x, cache, write_page, table_row, pos0,
                plen, *, cfg, spec, pol=None, mode: str, elastic_on: bool):
    """One CHUNK of a paged prefill: x is (1, C, D) with C == page_size,
    covering absolute positions [pos0, pos0 + C) of a plen-token prompt
    (the last chunk arrives zero-padded). Mirrors ``block_apply``'s
    inference-threshold branch EXACTLY — ``token_gate(mode)`` / head
    routing / LoRA gating are all per-token, so streaming a prompt through
    this graph chunk-by-chunk produces the same keep decisions and (up to
    reduction order inside attention) the same activations as the one-shot
    prefill — but writes K/V into ONE pool page (``write_page``) and
    attends through ``table_row`` (see attention.attn_chunk). pos0 / plen /
    write_page / table_row are traced, so ONE compile serves every chunk of
    every prompt length. Paged serving is attention-only with dense MLPs
    (engine-validated): the moefied experts' capacity buffers are sized
    by the sequence chunking, so their dispatch is the one sub-block
    whose one-shot and chunked results can drop different tokens.
    Returns (x', new_cache)."""
    assert mode != "train", "block_chunk is a serving (infer/base) path"
    if not is_attn(kind):
        raise ValueError(f"paged chunk prefill requires attn blocks, "
                         f"got {kind!r}")
    routed = elastic_on and mode != "base" and rp is not None
    backend = OPS.resolve_backend(
        spec.kernel_backend if spec is not None else None)
    impl = spec.routing_impl if spec is not None else "gather"
    new_cache = dict(cache)
    positions = (jnp.asarray(pos0, jnp.int32)
                 + jnp.arange(x.shape[1], dtype=jnp.int32))   # (C,)
    auxes = []                                   # serving: aux discarded

    cap_mha = cap_mlp = cap_depth = None
    if routed and spec is not None and rp:
        if spec.depth_routed and "depth" in rp:
            cap_depth = R.gate_capacity(pol.depth_capacity, pol.student)
        if spec.mha_token_routed and "tok_mixer" in rp:
            cap_mha = R.gate_capacity(pol.mha_token_capacity, pol.student)
        if spec.mlp_token_routed and "tok_mlp" in rp:
            cap_mlp = R.gate_capacity(pol.mlp_token_capacity, pol.student)

    # ---- attention (paged page write + table attend) ----
    h = norm_apply(p["norm1"], x, cfg.norm, cfg.norm_eps)
    lora = rp.get("lora") if routed else None
    lora = _lora_gate(lora, _mul_caps(cap_mha, cap_depth),
                      pol.student if (routed and pol is not None) else None)
    hw = _head_weights(rp if routed else None, h, spec, pol, cfg,
                       auxes) if routed else None
    keep_d, w_d = None, None
    if cap_depth is not None:
        # per-token whole-layer skip, threshold semantics (same decision
        # decode would make): skipped tokens write no KV into the page —
        # the page's occupancy bitmap (pvalid) records the hole
        lg = R.token_logits(rp["depth"], h)
        keep_d, w_d = R.token_gate(lg, jax.nn.sigmoid(lg), cap_depth, mode,
                                   theta=pol.theta, mxu=True)
    keep, wtok = None, None
    if cap_mha is not None:
        logits = R.token_logits(rp["tok_mixer"], h)
        scores = jax.nn.sigmoid(logits)
        keep, wtok = R.token_gate(logits, scores, cap_mha, mode,
                                  theta=pol.theta, mxu=True)
    if keep_d is not None:
        keep = keep_d if keep is None else keep & keep_d
        wtok = w_d if wtok is None else wtok * w_d
    y, new_cache["attn"] = A.attn_chunk(
        p["attn"], h, cache["attn"], write_page, table_row, pos0, plen,
        cfg=cfg, keep=keep, head_weights=hw, lora=lora)
    if wtok is not None:
        y = y * wtok[..., None].astype(y.dtype)
    x = x + y

    # ---- MLP (dense; per-token threshold routing) ----
    if has_mlp(kind):
        h = norm_apply(p["norm2"], x, cfg.norm, cfg.norm_eps)
        f = _mlp_fn(p, rp, cfg, spec, pol, elastic_on, mode, auxes,
                    backend=backend)
        if cap_mlp is None:
            delta = f(h, positions)
        else:
            delta, _ = R.route_tokens(
                rp["tok_mlp"], h, f, cap_mlp, mode, positions=positions,
                impl=impl, theta=pol.theta if pol is not None else 0.5)
        if w_d is not None:     # depth gates the MLP delta too
            delta = delta * w_d[..., None].astype(delta.dtype)
        x = x + delta
    return x, new_cache


def block_paged_cache_init(kind: str, cfg, n_pages: int, page_size: int,
                           kv_dtype: str = "fp32"):
    """Paged twin of ``block_cache_init``: one layer's slice of the global
    page pool (attention-only — the pool replaces the ring, recurrent
    state has no paged form)."""
    if not is_attn(kind) or kind == "xattn":
        raise ValueError(f"paged KV cache requires self-attention blocks, "
                         f"got {kind!r}")
    return {"attn": A.attn_paged_cache_init(cfg, n_pages, page_size,
                                            kv_dtype=kv_dtype)}


def cache_row_insert(full, row, slot, batch_axis: int = 0):
    """Splice a freshly prefilled single-request block cache (batch dim 1)
    into row ``slot`` of a live slot-array cache of the same structure.

    ``slot`` may be traced (dynamic_update_slice), so admitting a request
    into any serving slot reuses ONE compiled insert. Works on any cache
    pytree (attn k/v/valid/pos rings, ssm/rglru state+conv, xattn context);
    ``batch_axis`` selects where the batch dim lives (1 for pattern-scan
    stacked caches with a leading period dim, 0 for tail caches)."""
    def ins(f, r):
        return jax.lax.dynamic_update_slice_in_dim(
            f, r.astype(f.dtype), slot, axis=batch_axis)
    return jax.tree.map(ins, full, row)


def block_cache_init(kind: str, cfg, batch: int, max_seq: int, enc_len: int = 0,
                     window: int = 0, kv_dtype: str = "fp32"):
    c = {}
    if is_attn(kind):
        c["attn"] = A.attn_cache_init(cfg, batch, max_seq, window,
                                      kv_dtype=kv_dtype)
    if kind == "xattn":
        c["xattn"] = {
            "k": jnp.zeros((batch, enc_len, cfg.n_kv_heads, cfg.d_head),
                           jnp.dtype(cfg.dtype)),
            "v": jnp.zeros((batch, enc_len, cfg.n_kv_heads, cfg.d_head),
                           jnp.dtype(cfg.dtype)),
            "valid": jnp.zeros((batch, enc_len), bool),
        }
    if kind == "ssm":
        c["ssm"] = S.ssm_cache_init(cfg, batch)
    if kind == "rglru":
        c["rglru"] = G.rglru_cache_init(cfg, batch)
    if kind == "conv":
        c["conv"] = SC.conv_cache_init(cfg, batch)
    return c
