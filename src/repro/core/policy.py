"""ElasticSpec / ElasticPolicy: one compiled model, many compute budgets.

The elasticity API is split into two objects:

* ``ElasticSpec`` — *static* description of what elastic machinery EXISTS:
  which routers are attached, how many moefied experts, LoRA rank, which
  layers participate. Everything here shapes parameter trees and HLO, so it
  is a frozen, hashable dataclass that is baked into the trace (like
  ``ModelConfig``).

* ``ElasticPolicy`` — *runtime* knobs: token capacities, head/expert top-k,
  the decode threshold theta, and a teacher/student flag. It is a JAX pytree
  passed as a (traced) argument to ``forward`` / ``prefill`` / ``decode_step``
  / ``make_train_step``'s step function, so ONE compilation serves every
  budget: the fig5 capacity sweep, per-request budgets in ``ServingEngine``,
  and capacity annealing during distillation all run with zero re-jits.

Policy leaves may be:
  * python floats/ints — trace-time constants (the legacy ``ElasticConfig``
    path; top-k routing executes on a ragged capacity bucket by default, so
    budgets sharing a bucket share a compile — at most
    ``routing.RAGGED_N_BUCKETS`` graphs — with FLOPs proportional to the
    bucket);
  * jnp scalars ``()`` — traced, one compile for all budgets;
  * ``(B,)`` arrays — per-request budgets inside one batched step;
  * ``(L, 1)`` / ``(L, B)`` arrays — per-layer schedules (L = n_layers).

Budget semantics: any capacity ``>= 1`` (or top-k ``>= n``) short-circuits
to the exact frozen-teacher computation (router weights forced to 1), so
``ElasticPolicy.uniform(1.0)`` reproduces the teacher bit-for-bit — the
paper's losslessness property, now available at runtime.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp

# a top-k value meaning "all submodules" when the real count is unknown
FULL_TOPK = 1 << 30

Scalar = Union[float, int, jnp.ndarray]


# ------------------------------- spec ----------------------------------------

@dataclass(frozen=True)
class ElasticSpec:
    """What elastic machinery exists (shapes params + HLO; trace-static)."""
    mlp_token_routed: bool = True      # token router around the MLP
    mha_token_routed: bool = False     # token router around MHA/mixer
    mha_head_routed: bool = False      # head router over attention heads
    # Depth router: per-token whole-layer skip (docs/elastic_policy.md).
    # Selected tokens run the block (attention AND MLP/MoE, one shared
    # RoutingPlan); unselected tokens ride the residual untouched and
    # write no KV at that layer. Composes multiplicatively with the
    # token/head/expert knobs in the roofline solver.
    depth_routed: bool = False
    mlp_n_experts: Optional[int] = None  # moefy dense MLP into M experts
    expert_routed: bool = False        # elastic expert router (moefied/native)
    vlm_routed: bool = False           # image/context token selection
    vlm_router: str = "linear"         # linear | mlp
    vlm_router_hidden: int = 0
    lora_rank: int = 0                 # LoRA on q/v projections
    layers: str = "all"                # all | even  (paper §5.2)
    router_dtype: str = "float32"
    distill_loss: str = "topk_kl"      # topk_kl|fwd_kl|rev_kl|cosine
    distill_topk: int = 50
    distill_temp: float = 1.0
    lambda_load: float = 1.0
    lambda_topk: float = 1.0
    routing_impl: str = "ragged"       # ragged | gather | dense_mask
    # How the model hot path EXECUTES: "pallas" = real TPU kernels,
    # "interpret" = pallas interpreter (CPU kernel verification), "ref" =
    # jnp references/twins (fast CPU path), "auto" = pallas on TPU, ref
    # elsewhere. Static: changing it recompiles (it swaps the HLO).
    kernel_backend: str = "auto"       # auto | pallas | interpret | ref
    # Serving storage widths (docs/quantization.md). "fp32" = native config
    # dtype (no quantization); "int8" stores symmetric int8 with f32 scale
    # sibling leaves (KV: per (token, kv-head); weights: per output
    # channel), dequantized in-register inside the Pallas kernels. Static:
    # they shape the cache pytree and the HLO, never traced.
    kv_dtype: str = "fp32"             # fp32 | bf16 | int8
    weight_dtype: str = "fp32"         # fp32 | bf16 | int8

    def applies_to_layer(self, idx: int) -> bool:
        return self.layers == "all" or idx % 2 == 0


# ------------------------------- policy --------------------------------------

def _leaf(v, static: bool):
    if static:
        return v
    return jnp.asarray(v, jnp.float32)


@jax.tree_util.register_dataclass
@dataclass
class ElasticPolicy:
    """Runtime compute budget — a pytree of (possibly traced) scalars.

    Capacities are fractions in (0, 1]; top-k values are absolute counts
    (``FULL_TOPK`` means "all"). ``theta`` is the decode-time threshold on
    each token router's sigmoid (paper §B.1 uses 0.5). ``student <= 0``
    disables all routing (exact teacher), per batch row when shaped (B,).
    """
    mlp_token_capacity: Scalar = 1.0
    mha_token_capacity: Scalar = 1.0
    depth_capacity: Scalar = 1.0
    mha_head_topk: Scalar = FULL_TOPK
    mlp_expert_topk: Scalar = FULL_TOPK
    vlm_token_capacity: Scalar = 1.0
    theta: Scalar = 0.5
    student: Scalar = 1.0

    # ---- constructors ----
    @classmethod
    def uniform(cls, budget: float, *, n_heads: Optional[int] = None,
                n_experts: Optional[int] = None, theta: float = 0.5,
                static: bool = False) -> "ElasticPolicy":
        """Same fractional budget on every knob. Head/expert top-k are
        resolved when the counts are given, else left at "all"."""
        topk = lambda n: (max(1, min(n, int(math.ceil(budget * n - 1e-9))))
                          if n else FULL_TOPK)
        return cls(
            mlp_token_capacity=_leaf(budget, static),
            mha_token_capacity=_leaf(budget, static),
            depth_capacity=_leaf(budget, static),
            mha_head_topk=_leaf(topk(n_heads), static),
            mlp_expert_topk=_leaf(topk(n_experts), static),
            vlm_token_capacity=_leaf(budget, static),
            theta=_leaf(theta, static),
            student=_leaf(1.0, static),
        )

    @classmethod
    def teacher(cls, *, static: bool = False) -> "ElasticPolicy":
        """Exact frozen-teacher pass-through (routers bypassed)."""
        p = cls.uniform(1.0, static=static)
        return dataclasses.replace(p, student=_leaf(0.0, static))

    @classmethod
    def stack(cls, policies: Sequence["ElasticPolicy"]) -> "ElasticPolicy":
        """Batch per-request policies into one: every leaf becomes (B,)."""
        return jax.tree.map(
            lambda *ls: jnp.stack([jnp.asarray(l, jnp.float32) for l in ls]),
            *policies)

    # ---- per-request (B,) slot rows ----
    def broadcast_rows(self, batch: int) -> "ElasticPolicy":
        """Materialize every leaf as a (B,) float32 array — the live slot
        policy a continuous-batching engine splices admissions into."""
        return jax.tree.map(
            lambda v: jnp.broadcast_to(
                jnp.asarray(v, jnp.float32), (batch,)) + 0.0, self)

    def clamp_capacities(self, floor: float) -> "ElasticPolicy":
        """Lower-bound every capacity fraction at ``floor`` (in (0, 1]).
        The SLO controller's degradation stages go through this so a
        misconfigured or runaway controller can never drive a live row
        to a vanishing capacity; top-k leaves already floor at 1 in the
        roofline solver and ``theta``/``student`` are not budgets."""
        f = jnp.float32(floor)
        clamp = lambda v: jnp.maximum(jnp.asarray(v, jnp.float32), f)
        return self.replace(
            mlp_token_capacity=clamp(self.mlp_token_capacity),
            mha_token_capacity=clamp(self.mha_token_capacity),
            depth_capacity=clamp(self.depth_capacity),
            vlm_token_capacity=clamp(self.vlm_token_capacity))

    def set_row(self, i, row: "ElasticPolicy", *,
                floor: Optional[float] = None) -> "ElasticPolicy":
        """Splice ``row`` (scalar leaves) into batch row ``i`` of this
        (B,)-leaf policy. ``i`` may be traced (dynamic_update_index), so
        admitting a request into a serving slot NEVER recompiles: the row
        update is part of the one compiled admission graph. ``floor``
        (optional) bounds the spliced row's capacities from below via
        ``clamp_capacities`` — the degradation path's safety rail."""
        if floor is not None:
            row = row.clamp_capacities(floor)
        def upd(live, r):
            live = jnp.asarray(live, jnp.float32)
            return jax.lax.dynamic_update_index_in_dim(
                live, jnp.asarray(r, jnp.float32), i, axis=0)
        return jax.tree.map(upd, self, row)

    # ---- per-layer schedules ----
    @property
    def has_layer_dim(self) -> bool:
        return any(getattr(l, "ndim", 0) >= 2 for l in jax.tree.leaves(self))

    def for_layer(self, i: int) -> "ElasticPolicy":
        """Select layer i from any (L, ...) leaf; scalars/(B,) pass through."""
        def sel(v):
            if getattr(v, "ndim", 0) >= 2:
                return v[i % v.shape[0]]
            return v
        return jax.tree.map(sel, self)

    def replace(self, **kw) -> "ElasticPolicy":
        return dataclasses.replace(self, **kw)


# ------------------------ legacy ElasticConfig shim ---------------------------

def spec_from_config(ecfg) -> ElasticSpec:
    """Map a legacy ``ElasticConfig`` onto the static half of the new API."""
    return ElasticSpec(
        mlp_token_routed=ecfg.mlp_token_capacity is not None,
        mha_token_routed=ecfg.mha_token_capacity is not None,
        mha_head_routed=ecfg.mha_head_topk is not None,
        depth_routed=(getattr(ecfg, "depth_routed", False)
                      or getattr(ecfg, "depth_capacity", None) is not None),
        mlp_n_experts=ecfg.mlp_n_experts,
        expert_routed=bool(ecfg.mlp_expert_topk),
        vlm_routed=ecfg.vlm_token_capacity is not None,
        vlm_router=ecfg.vlm_router,
        vlm_router_hidden=ecfg.vlm_router_hidden,
        lora_rank=ecfg.lora_rank,
        layers=ecfg.layers,
        router_dtype=ecfg.router_dtype,
        distill_loss=ecfg.distill_loss,
        distill_topk=ecfg.distill_topk,
        distill_temp=ecfg.distill_temp,
        lambda_load=ecfg.lambda_load,
        lambda_topk=ecfg.lambda_topk,
        routing_impl=ecfg.routing_impl,
        kernel_backend=getattr(ecfg, "kernel_backend", "auto"),
        kv_dtype=getattr(ecfg, "kv_dtype", "fp32"),
        weight_dtype=getattr(ecfg, "weight_dtype", "fp32"),
    )


def policy_from_config(ecfg) -> ElasticPolicy:
    """Runtime half of the shim. Values stay python floats/ints, so when the
    result is closed over (not passed as a jit argument) the original static
    top-k gather routing — and its per-budget recompile — is preserved."""
    return ElasticPolicy(
        mlp_token_capacity=(1.0 if ecfg.mlp_token_capacity is None
                            else float(ecfg.mlp_token_capacity)),
        mha_token_capacity=(1.0 if ecfg.mha_token_capacity is None
                            else float(ecfg.mha_token_capacity)),
        depth_capacity=(1.0 if getattr(ecfg, "depth_capacity", None) is None
                        else float(ecfg.depth_capacity)),
        mha_head_topk=(FULL_TOPK if ecfg.mha_head_topk is None
                       else int(ecfg.mha_head_topk)),
        mlp_expert_topk=(FULL_TOPK if not ecfg.mlp_expert_topk
                         else int(ecfg.mlp_expert_topk)),
        vlm_token_capacity=(1.0 if ecfg.vlm_token_capacity is None
                            else float(ecfg.vlm_token_capacity)),
        theta=0.5,
        student=1.0,
    )


def as_spec_policy(elastic, policy: Optional[ElasticPolicy] = None):
    """Coerce ``ElasticConfig | ElasticSpec | None`` (+ optional policy)
    into a (spec, policy) pair. The single entry point every model/training/
    serving layer funnels through; ``ElasticConfig`` is deprecated but keeps
    working unchanged through this shim."""
    if elastic is None:
        return None, None
    if isinstance(elastic, ElasticSpec):
        return elastic, (policy if policy is not None
                         else ElasticPolicy.uniform(1.0, static=True))
    # legacy ElasticConfig (duck-typed to avoid importing configs here)
    spec = spec_from_config(elastic)
    return spec, (policy if policy is not None else policy_from_config(elastic))


# ----------------------- ragged bucket resolution ----------------------------

def ragged_bucket(policy: Optional[ElasticPolicy], s: int,
                  *, n_buckets: Optional[int] = None,
                  align: Optional[int] = None,
                  spec: Optional[ElasticSpec] = None) -> Optional[int]:
    """Host-side bucket solver (sits next to the roofline budget solver):
    the smallest static capacity bucket covering the policy's token
    capacities at sequence length ``s``. This is the value to thread — as a
    STATIC argument — into ``forward`` / ``prefill`` / train steps when the
    policy itself is traced: each distinct bucket is one compile, and there
    are at most ``routing.RAGGED_N_BUCKETS`` of them per sequence length
    (plus the identity graph).

    Returns:
      * an int ``b < s`` — the covering capacity bucket;
      * ``routing.IDENTITY_BUCKET`` — the IDENTITY fast path: every row of
        the policy is at full budget (capacity >= 1) or in teacher mode,
        so the compiled graph skips partition + gather + scatter entirely
        and runs the bit-exact teacher math (this is what makes budget-1.0
        rows as fast as the unrouted model — the token routers still emit
        their aux losses). A sentinel, not a size, so it can never collide
        with a real bucket at a different sequence length;
      * ``None`` — no static plan possible: the policy is abstract (tracers
        — the budget is genuinely unknown at trace time), rows MIX full and
        partial budgets, or the covering bucket would be the full sequence
        without every row being full. Dense rank-masked fallback.

    ``spec`` (optional) refines the capacity model: without it the solver
    conservatively assumes both token knobs are live and ignores depth
    (the pre-depth behaviour, still correct for solver-produced policies
    whose leaves are all equal). With a spec, non-routed token knobs are
    dropped and ``depth_capacity`` composes multiplicatively — the block
    plan's capacity is ``depth * max(token caps)``, so depth 0.5 at token
    1.0 still lands on a half-size bucket instead of the identity graph."""
    from repro.core import routing as R
    if policy is None:
        return None
    caps = [policy.mha_token_capacity, policy.mlp_token_capacity,
            policy.student, policy.depth_capacity]
    vals = []
    for c in caps:
        if isinstance(c, jax.core.Tracer):
            return None
        vals.append(jnp.asarray(c, jnp.float32))
    # effective per-row capacity: teacher rows (student <= 0) force 1.0
    if spec is not None:
        one = jnp.float32(1.0)
        cap_rows = jnp.maximum(
            vals[0] if spec.mha_token_routed else one,
            vals[1] if spec.mlp_token_routed else one)
        if spec.depth_routed:
            cap_rows = cap_rows * jnp.minimum(vals[3], 1.0)
    else:
        cap_rows = jnp.maximum(vals[0], vals[1])
    eff = jnp.where(vals[2] <= 0.0, 1.0, cap_rows)
    if float(jnp.min(eff)) >= 1.0:
        return R.IDENTITY_BUCKET                # identity: all rows full
    if float(jnp.max(eff)) >= 1.0:
        return None                             # mixed full/partial rows
    kw = {}
    if n_buckets is not None:
        kw["n_buckets"] = n_buckets
    if align is not None:
        kw["align"] = align
    cap = float(jnp.max(eff))
    b = R.bucket_for(R.capacity_k(cap, s, mxu=True), s, **kw)
    return b if b < s else None


# ------------------------- budget -> capacity solver --------------------------

def stack_flops_per_token(cfg, spec: ElasticSpec, *, ctx: int = 1024):
    """Analytic per-token forward FLOPs, split into (fixed, routed) parts.

    Parameter matmuls at 2 FLOPs/MAC plus the quadratic attention term at
    average context ``ctx``, decomposed per elastic knob so a budget can be
    solved for.

    ``routed`` maps knob name -> FLOPs that scale with that knob's fraction.
    Token capacities and head/expert fractions COMPOSE multiplicatively on
    the module they share (handled in ``_active_fraction``).
    """
    D, F = cfg.d_model, cfg.d_ff
    H, K, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    fixed = 2 * cfg.padded_vocab * D * (1 if cfg.tie_embeddings else 2)
    attn_head = attn_kv = mlp = mixer = 0.0
    n_gate = 3 if cfg.act in ("swiglu", "geglu") else 2
    for i, kind in enumerate(cfg.layer_kinds):
        elastic_l = spec.applies_to_layer(i)
        if kind in ("attn", "xattn"):
            w = cfg.layer_windows[i]
            c = min(ctx, w) if (w and w > 0) else ctx
            qo = 2 * 2 * D * H * Dh          # q + o projections
            kv = 2 * 2 * D * K * Dh          # k + v projections
            quad = 2 * 2 * c * H * Dh        # QK^T + PV
            if kind == "xattn":
                qo, kv, quad = 2 * qo, 2 * kv, 2 * quad
            if elastic_l:
                attn_head += qo + quad
                attn_kv += kv
            else:
                fixed += qo + kv + quad
        elif kind == "ssm" and cfg.ssm_state:
            di = cfg.d_inner
            c_ssm = 2 * D * (2 * di + 2 * cfg.ssm_state) + 2 * di * D
            (mixer, fixed) = (mixer + c_ssm, fixed) if elastic_l \
                else (mixer, fixed + c_ssm)
        elif kind == "rglru" and cfg.lru_width:
            w = cfg.lru_width
            c_lru = 2 * D * 2 * w + 2 * w * D + 2 * 2 * w * w
            (mixer, fixed) = (mixer + c_lru, fixed) if elastic_l \
                else (mixer, fixed + c_lru)
        elif kind == "conv":
            c_conv = 2 * D * 3 * D + 2 * D * D + 2 * cfg.conv_kernel * D
            (mixer, fixed) = (mixer + c_conv, fixed) if elastic_l \
                else (mixer, fixed + c_conv)
        if kind != "ssm":
            if cfg.layer_moe[i]:
                m = cfg.moe
                c_mlp = m.top_k * n_gate * 2 * D * m.d_expert
                if m.n_shared_experts:
                    fixed += n_gate * 2 * D * m.d_shared
            else:
                c_mlp = n_gate * 2 * D * F
            if elastic_l:
                mlp += c_mlp
            else:
                fixed += c_mlp
    routed = {"attn_head": attn_head, "attn_kv": attn_kv,
              "mlp": mlp, "mixer": mixer}
    return fixed, routed


def _expert_count(cfg, spec: ElasticSpec):
    """What the elastic expert knob counts: the native MoE's top-k (the
    knob keeps the top of the router's choice), else the moefied experts."""
    return cfg.moe.top_k if cfg.moe is not None else spec.mlp_n_experts


def _active_fraction(cfg, spec: ElasticSpec, s: float, *, ctx: int) -> float:
    """FLOP fraction of the full model when every enabled knob is set to
    fraction ``s`` (top-k values rounded to real integer counts)."""
    fixed, routed = stack_flops_per_token(cfg, spec, ctx=ctx)
    # Depth skip removes the WHOLE layer for unselected tokens, so its
    # fraction multiplies every routed term (attention, KV writes, mixer,
    # MLP) — depth 0.75 x token 0.75 composes to ~0.56 of routed FLOPs.
    frac_depth = s if spec.depth_routed else 1.0
    cap_tok_mha = (s if spec.mha_token_routed else 1.0) * frac_depth
    cap_tok_mlp = (s if spec.mlp_token_routed else 1.0) * frac_depth
    frac_head = 1.0
    if spec.mha_head_routed:
        frac_head = max(1, math.ceil(s * cfg.n_heads - 1e-9)) / cfg.n_heads
    frac_exp = 1.0
    if spec.expert_routed:
        n_e = _expert_count(cfg, spec)
        if n_e:
            frac_exp = max(1, math.ceil(s * n_e - 1e-9)) / n_e
    active = (fixed
              + routed["attn_head"] * cap_tok_mha * frac_head
              + routed["attn_kv"] * cap_tok_mha
              + routed["mixer"] * cap_tok_mha
              + routed["mlp"] * cap_tok_mlp * frac_exp)
    total = fixed + sum(routed.values())
    return active / max(total, 1.0)


def solve_budget(cfg, spec: ElasticSpec, budget: float, *, ctx: int = 1024,
                 theta: float = 0.5, static: bool = False,
                 iters: int = 40) -> ElasticPolicy:
    """Bisect the shared knob fraction ``s`` so the model's active-FLOP
    fraction (roofline cost model) hits ``budget``; returns the policy.

    budget >= the model's fixed-compute floor collapses gracefully: at
    budget >= 1 the policy is exactly the lossless teacher."""
    if budget >= 1.0:
        return ElasticPolicy.uniform(1.0, theta=theta, static=static)
    lo, hi = 1e-3, 1.0
    for _ in range(iters):
        mid = 0.5 * (lo + hi)
        if _active_fraction(cfg, spec, mid, ctx=ctx) > budget:
            hi = mid
        else:
            lo = mid
    s = 0.5 * (lo + hi)
    n_e = _expert_count(cfg, spec)
    return ElasticPolicy.uniform(
        s, n_heads=cfg.n_heads if spec.mha_head_routed else None,
        n_experts=n_e if spec.expert_routed else None,
        theta=theta, static=static)


# ------------------------------ schedules ------------------------------------

def capacity_anneal(start: float, end: float, steps: int):
    """Linear budget schedule for distillation: start at (near-)teacher
    capacity, anneal down to the target budget. Returns step -> budget."""
    def at(step: int) -> float:
        if steps <= 0:
            return end
        t = min(1.0, max(0.0, step / steps))
        return start + (end - start) * t
    return at
