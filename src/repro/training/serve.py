"""Continuous-batching serving engine over ONE compiled elastic decode.

Request lifecycle API (the serving contract the paper's input-dependent
compute implies — per-request budgets are a *scheduling* signal):

    engine = ServingEngine(params, rp, cfg, spec, mode="infer")
    h = engine.submit(GenRequest(prompt, 64, budget=0.5))
    for tok in h.tokens():         # streams; drives engine.step()
        ...
    engine.cancel(h)               # frees the slot mid-flight

``engine.step()`` runs ONE compiled decode over a fixed array of B slots:
finished/empty slots are masked, newly admitted requests are prefilled into
their slot (``models.prefill_into_slot``: single-request prefill + traced
cache-row insert), and each admission splices its solved per-request policy
row into the live (B,)-leaf ``ElasticPolicy`` (``ElasticPolicy.set_row``) —
all inside two jitted entry points whose cache sizes ``compile_counts()``
reports, so admissions at any mix of budgets never recompile. Admission is
packed by ``runtime.scheduler.SlotScheduler`` against a per-step FLOP budget
(roofline cost = the request's budget fraction), so low-budget requests
co-schedule more densely.

Decode runs the ElastiFormer threshold path (§B.1): per token, each router
decides with theta whether the token enters each module — variable inference
compute on a static graph. Sampling (per-request temperature / top-k /
PRNG seed) is traced inside the compiled step; the default temperature 0.0
is exact greedy argmax and bit-matches the legacy lockstep engine.

``generate(List[GenRequest])`` remains as a thin synchronous wrapper over
submit/step (legacy API).

SPMD serving: pass ``mesh=`` to run the same two compiled entry points
across a `(data, model)` mesh — params by the name-based TP rules, KV
caches kv-head-sharded, slots data-sharded into replicas the scheduler
packs independently — and ``engine.reshard(new_mesh)`` to scale the
replica axis up/down live (in-flight requests resume bitwise).

Under the JAX profiler ``step()`` records its phases as host spans
(``serve.step`` around ``serve.schedule``, ``serve.admit`` with its
``serve.admit.prefix``/``.call``/``.sync``, ``serve.pages``,
``serve.upload``, ``serve.decode`` with its ``live`` slots and, paged,
the ``pages`` of their tables the decode kernel visits, ``serve.sync``,
``serve.emit``), and
every compiled operation carries the model's named scope (``attention``,
``conv``, ``mlp``, ``router``, ``lm_head``, ``sample``) in its metadata.
With the profiler off a span costs about a microsecond and scopes cost
nothing.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from contextlib import nullcontext
from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.policy import (ElasticPolicy, ElasticSpec, as_spec_policy,
                               ragged_bucket, solve_budget)
from repro.models import (cache_init, decode_step, paged_cache_init,
                          prefill_chunk_step, prefill_into_slot)
from repro.models.quant import (check_kv_dtype, check_weight_dtype,
                                quantize_params_tree)
from repro.runtime.pagedkv import (PagePool, copy_page_in_tree, n_pages_for,
                                   prefix_keys)
from repro.runtime.scheduler import RequestHandle, SlotScheduler


class EntryPoint(NamedTuple):
    """One jitted serving graph + representative traced args, as handed to
    ``repro.analysis`` (retrace/sharding/host-sync/donation passes lower
    and inspect exactly what the engine runs)."""
    fn: object           # the jitted callable
    args: tuple          # traced example args (shapes/dtypes of a live call)
    static: dict         # static kwargs (e.g. the ragged bucket)
    donated: tuple = ()  # argnums whose buffers each call consumes


@dataclasses.dataclass
class GenRequest:
    prompt: np.ndarray          # (T,) int32
    max_new_tokens: int = 32
    budget: Optional[float] = None   # compute budget in (0, 1]; None = engine default
    eos_id: Optional[int] = None     # stop token; None = engine/config default
    temperature: float = 0.0         # 0.0 = greedy (bit-matches legacy argmax)
    top_k: int = 0                   # sample from the top-k logits; 0 = all
    seed: int = 0                    # per-request PRNG seed (traced)
    slo_class: str = "default"       # tenant SLO class (see runtime/controller.py)
    deadline_ms: Optional[float] = None  # queue deadline; None = class default


# ------------------------------ sampling -------------------------------------

@jax.named_scope("sample")
def sample_tokens(logits, temperature, top_k, seeds, positions):
    """Per-row sampling inside the compiled step — everything is traced, so
    one compilation serves every (temperature, top_k, seed) mix.

    logits: (B, V); temperature/top_k/seeds/positions: (B,). Rows with
    temperature <= 0 take the exact greedy argmax. Sampling is gumbel-max
    over the top-k logits (rank masking, traced k) at the given temperature;
    the PRNG key is fold_in(PRNGKey(seed), position-of-the-new-token), so a
    request's sample stream depends only on its own seed and positions —
    staggered admission reproduces a solo run exactly.
    """
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    lg = logits.astype(jnp.float32)
    V = lg.shape[-1]

    def sample_branch():
        # value-threshold top-k (one sort; ties all kept — fine for sampling)
        k = jnp.clip(jnp.where(top_k <= 0, V, top_k), 1, V)
        srt = jnp.sort(lg, axis=-1)                      # ascending
        kth = jnp.take_along_axis(srt, (V - k)[:, None], axis=-1)
        mask = lg >= kth
        keys = jax.vmap(
            lambda s, t: jax.random.fold_in(jax.random.PRNGKey(s), t)
        )(seeds.astype(jnp.uint32), positions.astype(jnp.int32))
        g = jax.vmap(lambda kk: jax.random.gumbel(kk, (V,), jnp.float32))(keys)
        z = jnp.where(mask, lg / jnp.maximum(temperature, 1e-6)[..., None] + g,
                      -jnp.inf)
        sampled = jnp.argmax(z, axis=-1).astype(jnp.int32)
        return jnp.where(temperature > 0, sampled, greedy)

    # all-greedy steps (the default) skip the sort + gumbel work at runtime
    return jax.lax.cond(jnp.any(temperature > 0), sample_branch,
                        lambda: greedy)


def _make_admit_fn(cfg, spec, mode, max_seq):
    """Admission graph: single-request prefill -> traced cache-row insert ->
    policy row splice -> sample the first token. One compile per (prompt
    length, capacity bucket); slot index, budgets, and sampling knobs are
    all traced. ``bucket`` is static and only non-None for top-k (train
    mode) prefill under ragged routing, where it caps the compile count at
    routing.RAGGED_N_BUCKETS per prompt length while the prefill FLOPs
    track the budget."""
    def admit(params, rp, batch, caches, slot, policy, live_policy,
              temperature, top_k, seed, t0, bucket=None):
        logits, caches, live_policy = prefill_into_slot(
            params, rp, batch, caches, slot, cfg, spec, mode=mode,
            max_cache_len=max_seq, policy=policy, live_policy=live_policy,
            bucket=bucket)
        tok = sample_tokens(logits, temperature[None], top_k[None],
                            seed[None], t0[None])[0]
        return tok, caches, live_policy
    return admit


def _make_step_fn(cfg, spec, mode):
    """One decode step over the whole slot array. ``t`` is the (B,) vector
    of per-slot positions; inactive rows are masked to token 0."""
    def step(params, rp, tok, caches, t, policy, active,
             temperature, top_k, seeds):
        logits, caches = decode_step(params, rp, tok[:, None], caches, t,
                                     cfg, spec, mode=mode, policy=policy)
        nxt = sample_tokens(logits, temperature, top_k, seeds, t + 1)
        return jnp.where(active, nxt, 0).astype(jnp.int32), caches
    return step


def _make_chunk_admit_fn(cfg, spec, mode):
    """Paged admission graph: ONE chunk of a chunked prefill (see
    ``models.prefill_chunk_step``) + policy-row splice + sampling. Every
    operand that varies per admission — the chunk tokens, page-table row,
    write page, chunk offset, prompt length, slot, budgets, sampling knobs
    — is traced, so this compiles EXACTLY ONCE for any mix of prompt
    lengths (the per-length prefill buckets of the ring engine collapse to
    one graph). The sampled token is only meaningful on the final chunk."""
    def admit(params, rp, tokens, caches, table_row, write_page, pos0, plen,
              slot, policy, live_policy, temperature, top_k, seed):
        logits, caches = prefill_chunk_step(
            params, rp, tokens, caches, write_page, table_row, pos0, plen,
            cfg, spec, mode=mode, policy=policy)
        if live_policy is not None and policy is not None:
            live_policy = live_policy.set_row(slot, policy)
        tok = sample_tokens(logits, temperature[None], top_k[None],
                            seed[None], jnp.asarray(plen)[None])[0]
        return tok, caches, live_policy
    return admit


def _make_paged_step_fn(cfg, spec, mode):
    """Paged decode step: same as ``_make_step_fn`` plus the (B, P) page
    table and (B,) per-slot trash-page ids (host-authoritative, passed as
    traced operands — table updates never recompile)."""
    def step(params, rp, tok, caches, t, policy, active,
             temperature, top_k, seeds, table, trash):
        logits, caches = decode_step(params, rp, tok[:, None], caches, t,
                                     cfg, spec, mode=mode, policy=policy,
                                     table=table, trash=trash)
        nxt = sample_tokens(logits, temperature, top_k, seeds, t + 1)
        return jnp.where(active, nxt, 0).astype(jnp.int32), caches
    return step


class ServingEngine:
    """Continuous-batching generation over a frozen base model + routers.

    ``elastic``: legacy ElasticConfig or new ElasticSpec. Budgets are
    resolved to per-request policies by the roofline budget solver and
    spliced into the live (B,)-leaf ElasticPolicy at admission.

    ``step_flop_budget``: per-replica, per-step FLOP budget for admission
    packing, in units of full-budget rows (None = slots-per-replica:
    limited by slots only).
    ``eos_id``: default stop token (falls back to ``cfg.eos_id``).

    ``mesh``: optional ``jax.sharding.Mesh`` with a `model` axis (TP) and
    data axes (`data`/`pod`, the replica axis). The engine then runs SPMD:
    base params follow the Megatron-style name rules in
    ``runtime/sharding.py``, routers replicate, the ring KV caches shard
    kv-heads over `model` and slots over the data axes, and the slot array
    gains a data-parallel replica axis for the scheduler (flat slot i lives
    on data shard i // slots_per_replica). The compiled admission/decode
    graphs are the same two jitted entry points — budgets, slots, and
    sampling knobs still never recompile — and their outputs are
    token-for-token identical to the single-device engine.
    ``n_replicas`` overrides the scheduler's replica count without a mesh
    (placement-policy testing); with a mesh it must match the data axes.
    """

    def __init__(self, params, router_params, cfg, elastic=None,
                 mode: str = "infer", batch_size: int = 8,
                 max_seq: int = 256, default_budget: Optional[float] = None,
                 theta: float = 0.5, eos_id: Optional[int] = None,
                 step_flop_budget: Optional[float] = None, mesh=None,
                 n_replicas: Optional[int] = None, kv_layout: str = "ring",
                 page_size: int = 16, n_pages: Optional[int] = None,
                 kv_dtype: str = "fp32", weight_dtype: str = "fp32",
                 controller=None, clock=None):
        # SLO controller (runtime/controller.py) + injectable clock: every
        # engine timestamp (handle t_submit/t_tokens, controller evals)
        # reads this one clock, so tests drive a fully deterministic time.
        self.controller = controller
        self._clock = clock if clock is not None else time.perf_counter
        self.kv_dtype = check_kv_dtype(kv_dtype)
        self.weight_dtype = check_weight_dtype(weight_dtype)
        # quantize base weights ONCE, before any sharding/jit sees the tree
        # (scale leaves must exist when param specs are derived)
        params = quantize_params_tree(params, self.weight_dtype)
        self.params, self.rp = params, router_params
        self.cfg, self.mode = cfg, mode
        # base policy = the elastic config's own knobs (threshold routing
        # with its head/expert top-k); explicit budgets go through the
        # roofline solver instead. default_budget=None keeps legacy behavior.
        self.spec, self._base_policy = as_spec_policy(elastic)
        if self._base_policy is not None:
            self._base_policy = self._base_policy.replace(theta=theta)
        if (self.kv_dtype, self.weight_dtype) != ("fp32", "fp32"):
            # the spec is what the traced graphs consult for cache writes,
            # so it must carry the dtypes even when no elastic config was
            # given (plain dense serving of a quantized model)
            base_spec = self.spec if self.spec is not None else ElasticSpec()
            self.spec = dataclasses.replace(
                base_spec, kv_dtype=self.kv_dtype,
                weight_dtype=self.weight_dtype)
            if self._base_policy is None:   # keep spec => policy invariant
                self._base_policy = ElasticPolicy.uniform(1.0, static=True)
        self.B, self.max_seq = batch_size, max_seq
        self.default_budget, self.theta = default_budget, theta
        self.eos_id = eos_id if eos_id is not None else cfg.eos_id
        self._policy_cache: dict = {}
        self._use_policy = self.spec is not None and mode != "base"

        # ---- live slot-array state ----
        B = batch_size
        self.scheduler = SlotScheduler(
            B, step_flop_budget, self._replicas_for(mesh, n_replicas))
        if kv_layout not in ("ring", "paged"):
            raise ValueError(f"kv_layout must be 'ring' or 'paged', "
                             f"got {kv_layout!r}")
        self.kv_layout, self.page_size = kv_layout, int(page_size)
        self.pool: Optional[PagePool] = None
        if kv_layout == "paged":
            self._validate_paged(mode)
            R_ = self.scheduler.n_replicas
            self.pages_per_slot = n_pages_for(max_seq, self.page_size)
            if n_pages is None:
                # ring-equivalent HBM: usable pages = B slots * full-length
                # rows, plus one trash page per replica for masked writes
                n_pages = B * self.pages_per_slot + R_
            self.pool = PagePool(n_pages, self.page_size, n_replicas=R_)
            self._caches = paged_cache_init(cfg, n_pages, self.page_size,
                                            kv_dtype=self.kv_dtype)
            # host-authoritative page table, mirrored into every compiled
            # call as a traced operand (same precedent as self._t)
            self._table = np.full((B, self.pages_per_slot), -1, np.int32)
            self._trash = np.array(
                [self.pool.trash_page(self.scheduler.replica_of(s))
                 for s in range(B)], np.int32)
            self._admit_counter = itertools.count()
            self._admit_seq = np.full((B,), -1, np.int64)
        else:
            self._caches = cache_init(cfg, B, max_seq,
                                      kv_dtype=self.kv_dtype)
        self._live_policy = (self._base_policy.broadcast_rows(B)
                             if self._use_policy else None)
        self._tok = jnp.zeros((B,), jnp.int32)
        self._t = np.zeros((B,), np.int32)        # per-slot decode position
        self._active = np.zeros((B,), bool)
        self._temp = np.zeros((B,), np.float32)
        self._topk = np.zeros((B,), np.int32)
        self._seeds = np.zeros((B,), np.uint32)
        self._ngen = np.zeros((B,), np.int64)
        self._extras: dict = {}                   # handle.id -> extra inputs
        # per-slot budget bookkeeping for in-flight degradation: the budget
        # the slot was ADMITTED at (None = engine default / base policy),
        # the budget currently APPLIED to its live policy row, and the
        # controller depth cap applied to it (None = undegraded)
        self._slot_budget_key: list = [None] * B
        self._slot_applied_key: list = [None] * B
        self._slot_applied_depth: list = [None] * B
        self.n_rejected = 0                       # shed under overload
        self.n_expired = 0                        # queue deadline passed

        # shard state + build the jitted entry points (compile_counts)
        self.mesh = None
        self.remeshed_at: Optional[float] = None  # last reshard() wall time
        self._install_mesh(mesh)

    # ---------------------------- paged KV mode ------------------------------

    def _validate_paged(self, mode: str) -> None:
        """The paged subsystem serves the elastic decoder hot path: global
        self-attention layers with dense MLPs. Windows would need
        page-eviction semantics, and recurrent mixers (``ssm``, ``rglru``,
        ``conv``) carry per-slot state that has no paged form. Moefied
        expert dispatch sizes its capacity buffers by the sequence
        chunking — the one sub-block whose chunked and one-shot prefills
        can drop different tokens, which would break the paged == ring
        token-parity contract. Native experts are dropless, but no paged
        == ring test covers them yet, so they stay refused too."""
        if mode not in ("infer", "base"):
            raise ValueError(f"kv_layout='paged' serves infer/base modes, "
                             f"got mode={mode!r}")
        bad = [k for k in self.cfg.layer_kinds if k != "attn"]
        if bad:
            raise ValueError(f"kv_layout='paged' requires all-'attn' layer "
                             f"kinds; recurrent mixers (ssm, rglru, conv) "
                             f"have no paged state; got {sorted(set(bad))}")
        if any(w and w > 0 for w in self.cfg.layer_windows):
            raise ValueError("kv_layout='paged' does not support sliding-"
                             "window layers")
        if self.cfg.encoder is not None or self.cfg.family in ("vlm",
                                                               "encoder"):
            raise ValueError("kv_layout='paged' serves decoder-only LMs")
        if self.cfg.moe is not None or (self.spec is not None
                                        and self.spec.mlp_n_experts):
            raise ValueError("kv_layout='paged' requires a dense MLP (no "
                             "MoE / moefied experts): moefied expert-"
                             "capacity buffers depend on the prefill "
                             "chunking, and native experts have no paged "
                             "== ring test yet")
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {self.page_size}")

    def _prefix_namespace(self, req: GenRequest) -> tuple:
        """Prefix-sharing hash namespace: pages hold post-gate K/V, so two
        requests may share a page only when every knob that shapes the
        written values agrees — mode, solved budget, theta, and the KV
        storage dtype (sampling knobs don't touch K/V)."""
        b = self._effective_budget(req)
        d = self._depth_cap()
        return (self.mode, None if b is None else round(float(b), 6),
                round(float(self.theta), 6), self.kv_dtype,
                None if d is None else round(float(d), 6))

    def paged_stats(self) -> dict:
        """Pool stats plus live-token page efficiency (host-side only)."""
        st = self.pool.stats()
        live_tok = int(self._t[self._active].sum())
        held = int(sum((self._table[s] >= 0).sum()
                       for s in range(self.B) if self._active[s]))
        st["live_tokens"] = live_tok
        st["pages_held_by_active"] = held
        st["pages_per_token"] = (held / live_tok) if live_tok else 0.0
        return st

    # ------------------------------ SPMD mesh --------------------------------

    def _replicas_for(self, mesh, n_replicas: Optional[int]) -> int:
        """Replica count = product of the mesh's data axes (`pod`, `data`);
        explicit ``n_replicas`` must agree with the mesh when both given."""
        from repro.runtime import sharding as SH
        r = SH.data_axis_size(mesh)
        if n_replicas is not None:
            if mesh is not None and n_replicas != r:
                raise ValueError(f"n_replicas={n_replicas} does not match "
                                 f"the mesh's data axes (= {r})")
            r = n_replicas
        if self.B % r:
            raise ValueError(f"batch_size={self.B} must be a multiple of "
                             f"the replica count {r}")
        return r

    def _install_mesh(self, mesh) -> None:
        """device_put all live state onto ``mesh`` (None = default single
        device) and rebuild the two jitted entry points against it."""
        from repro.runtime import sharding as SH
        from repro.runtime.elastic import rescale_serving_state
        prev, self.mesh = self.mesh, mesh
        if mesh is not None or prev is not None:   # mesh-less init: no move
            self.params, self.rp, self._caches = rescale_serving_state(
                self.params, self.rp, self._caches, self.cfg, mesh)
            rep = ((lambda t: jax.tree.map(
                        lambda x: jax.device_put(x, SH.replicated(mesh)), t))
                   if mesh is not None else
                   (lambda t: jax.tree.map(
                        lambda x: jax.device_put(x, jax.devices()[0]), t)))
            self._tok = rep(self._tok)
            if self._live_policy is not None:
                self._live_policy = rep(self._live_policy)
        # fresh jit wrappers: compile_counts tracks the CURRENT mesh only.
        # Under a mesh the slot-state OUTPUTS (caches, next token, live
        # policy) are pinned to the same shardings the next call's inputs
        # carry — without this the compiler picks its own output layout and
        # the second admit/decode call recompiles against it, breaking the
        # {prefill: 1, decode: 1} contract.
        # Donation: each call consumes the slot-state buffers it replaces —
        # admit donates (caches, live_policy), decode donates (tok, caches)
        # — so XLA aliases the ring caches in place instead of copying the
        # whole slot array every step (the analysis `donation` pass gates
        # on these aliases). The per-request policy ROW (admit arg 5) is
        # NOT donated: solved rows are cached in `_policy_cache` and reused
        # across admissions.
        paged = self.kv_layout == "paged"
        if paged:
            admit_raw = _make_chunk_admit_fn(self.cfg, self.spec, self.mode)
            step_raw = _make_paged_step_fn(self.cfg, self.spec, self.mode)
            admit_static, admit_donate = (), (3, 10)
            fork_raw = lambda caches, src, dst, n_keep: copy_page_in_tree(
                caches, src, dst, n_keep, page_size=self.page_size,
                cfg=self.cfg)
        else:
            admit_raw = _make_admit_fn(self.cfg, self.spec, self.mode,
                                       self.max_seq)
            step_raw = _make_step_fn(self.cfg, self.spec, self.mode)
            admit_static, admit_donate = ("bucket",), (3, 6)
        if mesh is None:
            self._admit_fn = jax.jit(admit_raw, static_argnames=admit_static,
                                     donate_argnums=admit_donate)
            self._step_fn = jax.jit(step_raw, donate_argnums=(2, 3))
            if paged:
                self._fork_fn = jax.jit(fork_raw, donate_argnums=(0,))
        else:
            rsh = SH.replicated(mesh)
            cache_sh = SH.cache_shardings(self._caches, self.cfg, mesh)
            pol_sh = (jax.tree.map(lambda _: rsh, self._live_policy)
                      if self._live_policy is not None else None)
            self._admit_fn = jax.jit(admit_raw, static_argnames=admit_static,
                                     donate_argnums=admit_donate,
                                     out_shardings=(rsh, cache_sh, pol_sh))
            self._step_fn = jax.jit(step_raw, donate_argnums=(2, 3),
                                    out_shardings=(rsh, cache_sh))
            if paged:
                self._fork_fn = jax.jit(fork_raw, donate_argnums=(0,),
                                        out_shardings=cache_sh)

    def _mesh_ctx(self):
        """Trace/execute under the mesh so `active_mesh()`-gated sharding
        constraints inside the model apply."""
        return self.mesh if self.mesh is not None else nullcontext()

    def reshard(self, mesh) -> None:
        """LIVE re-mesh: move the engine — base params, routers, the slot
        caches holding every in-flight request, live policy rows — onto a
        new mesh shape (None = back to one device) without a restart.
        In-flight requests resume with identical (bitwise, greedy) tokens:
        the compiled math is the same, only its partitioning changes.
        The queue and slot assignments survive; the scheduler re-derives
        its replica axis from the new data axes (see
        ``SlotScheduler.set_replicas``). The two entry points recompile
        once against the new shardings (``compile_counts`` restarts)."""
        if self.kv_layout == "paged":
            raise NotImplementedError(
                "live reshard of a paged engine is not supported: page ids "
                "are replica-local (the pool freelists and trash pages are "
                "derived from the data-axis size at construction)")
        jax.block_until_ready(self._caches)       # drain the in-flight step
        self.scheduler.set_replicas(self._replicas_for(mesh, None))
        self._install_mesh(mesh)
        self.remeshed_at = self._clock()          # stats-window boundary

    # ---- budgets -> per-request policy rows ----
    def _effective_budget(self, req: GenRequest) -> Optional[float]:
        """Resolve a request's serving budget: its own (or the engine
        default), capped by the controller's degraded admission budget
        (stage-1 graceful degradation). A user-requested budget BELOW the
        controller cap is honored as-is — the cap only degrades, never
        upgrades."""
        b = req.budget if req.budget is not None else self.default_budget
        if self.controller is not None:
            cap = self.controller.admission_cap()
            if cap is not None:
                b = cap if b is None else min(float(b), cap)
        return b

    def _depth_cap(self) -> Optional[float]:
        """The controller's depth-stage cap (stage-2 graceful degradation:
        whole-layer skips), honored only when the spec routes depth —
        otherwise the knob has nothing to act on and is ignored."""
        if (self.controller is None or self.spec is None
                or not self.spec.depth_routed):
            return None
        return self.controller.depth_cap()

    def _policy_for(self, budget: Optional[float],
                    depth: Optional[float] = None) -> Optional[ElasticPolicy]:
        """Solved policy row for (budget, depth-cap). ``depth`` further
        caps ``depth_capacity`` below what the roofline solver chose for
        the budget (the controller's depth degrade stage); rows are cached
        per (budget, depth) key so repeat admissions never re-solve."""
        if not self._use_policy:
            return None
        if budget is None and depth is None:
            pol = self._base_policy
        else:
            key = (None if budget is None else round(float(budget), 6),
                   None if depth is None else round(float(depth), 6))
            if key not in self._policy_cache:
                pol = (self._base_policy if budget is None else solve_budget(
                    self.cfg, self.spec, key[0], theta=self.theta,
                    static=True))
                if depth is not None:
                    cur = pol.depth_capacity
                    dc = (min(float(cur), float(depth))
                          if isinstance(cur, (int, float))
                          else jnp.minimum(jnp.asarray(cur, jnp.float32),
                                           jnp.float32(depth)))
                    pol = pol.replace(depth_capacity=dc)
                self._policy_cache[key] = pol
            pol = self._policy_cache[key]
        # f32 leaves: stable jit avals (no weak-type retraces)
        return jax.tree.map(lambda v: jnp.asarray(v, jnp.float32), pol)

    @staticmethod
    def _composed_cost(budget: Optional[float],
                       depth: Optional[float]) -> float:
        """Scheduler cost of a (budget, depth-cap) pair: the budget
        fraction times the depth fraction — depth skips whole layers, so
        the two compose multiplicatively, exactly like the roofline
        solver's active-FLOP model."""
        return min(1.0, (1.0 if budget is None else float(budget))
                   * (1.0 if depth is None else float(depth)))

    def compile_counts(self) -> dict:
        """Jit-cache sizes — admissions at any mix of budgets, slots,
        temperatures, or seeds must NOT add entries (asserted by tests and
        benchmarks); only a new prompt length compiles (and, for top-k
        train-mode prefill under ragged routing, a new capacity bucket —
        at most routing.RAGGED_N_BUCKETS per length)."""
        return {"prefill": self._admit_fn._cache_size(),
                "decode": self._step_fn._cache_size()}

    def entry_points(self, plen: int = 8,
                     budget: Optional[float] = 0.5,
                     depth: Optional[float] = None) -> dict:
        """The two jitted serving graphs with example args shaped exactly
        like a live admission/decode call — the contract surface
        ``repro.analysis`` lints (a pass that lowers these sees the same
        jaxpr/HLO a production call compiles). Args are built by the same
        code paths ``_admit_one``/``step`` use, so the lint can never
        drift from the real call signature."""
        prompt = np.arange(1, plen + 1, dtype=np.int32) \
            % max(2, self.cfg.vocab_size)
        pol_row = self._policy_for(budget if self._use_policy else None,
                                   depth=depth)
        if self.kv_layout == "paged":
            ck = np.zeros((self.page_size,), np.int32)
            ck[:min(plen, self.page_size)] = prompt[:self.page_size]
            admit = EntryPoint(
                self._admit_fn,
                (self.params, self.rp, jnp.asarray(ck[None]), self._caches,
                 jnp.asarray(self._table[0]), jnp.int32(0), jnp.int32(0),
                 jnp.int32(min(plen, self.page_size)), jnp.int32(0),
                 pol_row, self._live_policy, jnp.float32(0.0), jnp.int32(0),
                 jnp.uint32(0)),
                {}, donated=(3, 10))
            step = EntryPoint(
                self._step_fn,
                (self.params, self.rp, self._tok, self._caches,
                 jnp.asarray(self._t), self._live_policy,
                 jnp.asarray(self._active), jnp.asarray(self._temp),
                 jnp.asarray(self._topk), jnp.asarray(self._seeds),
                 jnp.asarray(self._table), jnp.asarray(self._trash)),
                {}, donated=(2, 3))
            return {"admit": admit, "decode": step}
        batch = {"tokens": jnp.asarray(prompt[None])}
        bucket = None
        if (self._use_policy and self.mode == "train"
                and self.spec.routing_impl == "ragged"):
            bucket = ragged_bucket(pol_row, plen, spec=self.spec)
        admit = EntryPoint(
            self._admit_fn,
            (self.params, self.rp, batch, self._caches, jnp.int32(0),
             pol_row, self._live_policy, jnp.float32(0.0), jnp.int32(0),
             jnp.uint32(0), jnp.int32(plen)),
            {"bucket": bucket}, donated=(3, 6))
        step = EntryPoint(
            self._step_fn,
            (self.params, self.rp, self._tok, self._caches,
             jnp.asarray(self._t), self._live_policy,
             jnp.asarray(self._active), jnp.asarray(self._temp),
             jnp.asarray(self._topk), jnp.asarray(self._seeds)),
            {}, donated=(2, 3))
        return {"admit": admit, "decode": step}

    # ------------------------- request lifecycle -----------------------------

    def submit(self, request: GenRequest,
               extra_inputs: Optional[dict] = None) -> RequestHandle:
        """Queue a request; returns its lifecycle handle. ``extra_inputs``:
        per-request model inputs with a leading dim of 1 (e.g. one image's
        ``image_embeds`` row for a VLM)."""
        prompt = np.asarray(request.prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if prompt.size + request.max_new_tokens > self.max_seq:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens "
                f"({request.max_new_tokens}) exceeds max_seq={self.max_seq}")
        b = request.budget
        if b is not None and not 0.0 < b <= 1.0:
            raise ValueError(f"budget must be in (0, 1], got {b}")
        if self.kv_layout == "paged":
            need = n_pages_for(prompt.size + request.max_new_tokens,
                               self.page_size)
            if need > self.pool.usable_per_replica:
                raise ValueError(
                    f"request needs {need} pages but a replica only has "
                    f"{self.pool.usable_per_replica} usable pages")
        handle = RequestHandle(request, engine=self, clock=self._clock)
        handle.tenant = getattr(request, "slo_class", None) or "default"
        dl_ms = getattr(request, "deadline_ms", None)
        if dl_ms is None and self.controller is not None:
            dl_ms = self.controller.target_for(handle.tenant).deadline_ms
        if dl_ms is not None:
            handle.deadline = handle.t_submit + float(dl_ms) / 1e3
        if extra_inputs:
            self._extras[handle.id] = {
                k: jnp.asarray(v) for k, v in extra_inputs.items()}
        cost = b if b is not None else (self.default_budget or 1.0)
        self.scheduler.enqueue(handle, cost=min(1.0, float(cost)))
        return handle

    def cancel(self, handle: RequestHandle) -> bool:
        """Cancel a queued or running request; frees its slot immediately.
        Returns False if the request had already finished."""
        if handle.done:
            return False
        if handle.status == "running" and handle.slot is not None:
            if self.kv_layout == "paged":
                self._free_slot_pages(handle.slot)
            self.scheduler.free(handle.slot)
            self._active[handle.slot] = False
        else:
            self.scheduler.drop_queued(handle)
        self._extras.pop(handle.id, None)
        handle.finish("cancelled")
        return True

    @property
    def has_work(self) -> bool:
        return self.scheduler.active > 0 or self.scheduler.pending > 0

    @property
    def occupancy(self) -> float:
        return self.scheduler.occupancy

    @property
    def replica_occupancy(self) -> List[float]:
        """Per-replica mean active-slot fraction (trivially [occupancy]
        when running unsharded)."""
        return self.scheduler.replica_occupancy

    # ------------------------------ stepping ---------------------------------

    def _admit_one(self, slot: int, handle: RequestHandle) -> bool:
        req = handle.request
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        plen = prompt.size
        batch = {"tokens": jnp.asarray(prompt[None])}
        batch.update(self._extras.pop(handle.id, {}))
        b_eff = self._effective_budget(req)
        d_eff = self._depth_cap()
        pol_row = self._policy_for(b_eff, depth=d_eff)
        # ragged capacity bucket: static, resolved per admission from the
        # (host-concrete) policy row. Only top-k routing (train mode) uses
        # it — threshold (infer) prefill stays dense, so infer engines keep
        # exactly one prefill compile per prompt length. Full-budget rows
        # resolve the IDENTITY sentinel bucket: their prefill
        # compiles the no-routing teacher graph instead of paying the
        # rank-masking sorts.
        bucket = None
        if (self._use_policy and self.mode == "train"
                and self.spec.routing_impl == "ragged"):
            bucket = ragged_bucket(pol_row, plen, spec=self.spec)
        seed = int(req.seed) & 0xFFFFFFFF        # any python int -> uint32
        with self._mesh_ctx(), TraceAnnotation("serve.admit.call", chunk=0):
            tok0, self._caches, self._live_policy = self._admit_fn(
                self.params, self.rp, batch, self._caches, jnp.int32(slot),
                pol_row, self._live_policy,
                jnp.float32(req.temperature), jnp.int32(req.top_k),
                jnp.uint32(seed), jnp.int32(plen), bucket=bucket)
        self._tok = self._tok.at[slot].set(tok0)
        self._t[slot] = plen
        self._active[slot] = True
        self._temp[slot] = req.temperature
        self._topk[slot] = req.top_k
        self._seeds[slot] = seed
        self._ngen[slot] = 0
        with TraceAnnotation("serve.admit.sync"):
            tok0 = int(tok0)
        self._append(slot, handle, tok0)
        self._note_admitted(slot, handle, b_eff, d_eff)
        return True

    def _note_admitted(self, slot: int, handle: RequestHandle,
                       b_eff: Optional[float],
                       d_eff: Optional[float] = None) -> None:
        """Record the admitted budget (and depth cap) for in-flight
        degradation/restore, the served-budget weight for goodput
        accounting, and the TTFT sample for the controller. The slot's
        scheduler cost is re-priced to the COMPOSED budget x depth
        fraction, so a depth-degraded replica's admission headroom grows
        to match the FLOPs it actually spends."""
        self._slot_budget_key[slot] = b_eff
        self._slot_applied_key[slot] = b_eff
        self._slot_applied_depth[slot] = d_eff
        cost = self._composed_cost(b_eff, d_eff)
        handle.budget_served = cost
        if d_eff is not None:
            self.scheduler.reprice(slot, cost)
        if self.controller is not None and handle.ttft is not None:
            self.controller.record_ttft(
                handle.tenant, self.scheduler.replica_of(slot),
                handle.ttft * 1e3, t=handle.t_first)

    # ----------------------- paged admission / decode ------------------------

    def _page_check(self, handle: RequestHandle, replica: int) -> bool:
        """Joint-packing hook for ``SlotScheduler.admit``: a replica is an
        admission candidate only when its freelist covers the prompt's full
        page count (conservative: prefix sharing can only reduce it)."""
        plen = np.asarray(handle.request.prompt).size
        return self.pool.can_alloc(replica, n_pages_for(plen, self.page_size))

    def _free_slot_pages(self, slot: int) -> None:
        """Return a slot's page-table row to the pool (refcounted — shared
        prefix pages survive until their last holder frees) and clear it."""
        pages = [int(p) for p in self._table[slot] if p >= 0]
        if pages:
            self.pool.free(pages)
        self._table[slot] = -1

    def _admit_one_paged(self, slot: int, handle: RequestHandle) -> bool:
        """Paged admission: match shared prefix pages, allocate the rest,
        then stream the prompt through the ONE compiled chunk graph
        (page_size tokens per call). Fully-shared chunks are skipped —
        except the FINAL chunk, which always runs (its activations feed the
        first sampled token); when that chunk's page is shared the write is
        aimed at the replica's trash page while attention gathers the real
        shared page. Returns False when the pool cannot back the prompt
        right now (caller re-queues; never raises mid-admission)."""
        req = handle.request
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        plen, ps = prompt.size, self.page_size
        n_chunks = n_pages_for(plen, ps)
        n_full = plen // ps                  # full pages eligible to share
        r = self.scheduler.replica_of(slot)
        with TraceAnnotation("serve.admit.prefix"):
            keys = prefix_keys(tuple(int(x) for x in prompt), ps,
                               namespace=self._prefix_namespace(req))
            row = np.full(self.pages_per_slot, -1, np.int32)
            matched = 0
            for i in range(n_full):
                pg = self.pool.lookup_prefix(keys[i], r)
                if pg is None:
                    break
                self.pool.incref(pg)
                row[i] = pg
                matched += 1
            fresh = self.pool.alloc(r, n_chunks - matched) \
                if n_chunks > matched else []
        if fresh is None:                    # raced out inside this batch
            shared = [int(p) for p in row[:matched]]
            if shared:
                self.pool.free(shared)
            return False
        for j, pg in enumerate(fresh):
            row[matched + j] = pg
        self._table[slot] = row
        b_eff = self._effective_budget(req)
        d_eff = self._depth_cap()
        pol_row = self._policy_for(b_eff, depth=d_eff)
        seed = int(req.seed) & 0xFFFFFFFF
        trash = self.pool.trash_page(r)
        chunk_ids = list(range(matched, n_chunks)) or [n_chunks - 1]
        with self._mesh_ctx():
            for c in chunk_ids:
                with TraceAnnotation("serve.admit.call", chunk=c):
                    lo = c * ps
                    ck = np.zeros((ps,), np.int32)
                    ck[:min(ps, plen - lo)] = \
                        prompt[lo:lo + min(ps, plen - lo)]
                    wp = int(row[c]) if c >= matched else trash
                    tok0, self._caches, self._live_policy = self._admit_fn(
                        self.params, self.rp, jnp.asarray(ck[None]),
                        self._caches, jnp.asarray(row), jnp.int32(wp),
                        jnp.int32(lo), jnp.int32(plen), jnp.int32(slot),
                        pol_row, self._live_policy,
                        jnp.float32(req.temperature), jnp.int32(req.top_k),
                        jnp.uint32(seed))
        for i in range(matched, n_full):     # freshly written full pages
            self.pool.register_prefix(keys[i], int(row[i]))
        self._tok = self._tok.at[slot].set(tok0)
        self._t[slot] = plen
        self._active[slot] = True
        self._temp[slot] = req.temperature
        self._topk[slot] = req.top_k
        self._seeds[slot] = seed
        self._ngen[slot] = 0
        self._admit_seq[slot] = next(self._admit_counter)
        with TraceAnnotation("serve.admit.sync"):
            tok0 = int(tok0)
        self._append(slot, handle, tok0)
        self._note_admitted(slot, handle, b_eff, d_eff)
        return True

    def _pick_victim(self, replica: int) -> Optional[int]:
        """Preemption order: the LATEST-admitted active slot of the replica
        (FIFO priority — the request that has waited longest keeps its
        pages)."""
        spr = self.scheduler.slots_per_replica
        cands = [s for s in range(replica * spr, (replica + 1) * spr)
                 if self._active[s]]
        return max(cands, key=lambda s: self._admit_seq[s]) if cands else None

    def _preempt(self, slot: int) -> None:
        """Evict a running request under page pressure: recycle its pages,
        free the slot, and re-queue it AT THE FRONT as a continuation
        (prompt := original + generated so far). Sampling is keyed by
        fold_in(seed, absolute position), so the re-admitted run continues
        token-for-token as if never interrupted."""
        handle = self.scheduler.slots[slot]
        cost = self.scheduler.costs[slot]
        self._free_slot_pages(slot)
        self._active[slot] = False
        self.scheduler.free(slot)
        req = handle.request
        prompt = np.concatenate([
            np.asarray(req.prompt, np.int32).reshape(-1),
            np.asarray(handle.output, np.int32)])
        handle.request = dataclasses.replace(
            req, prompt=prompt,
            max_new_tokens=req.max_new_tokens - len(handle.output))
        self.scheduler.requeue_front(handle, cost)

    def _ensure_decode_pages(self) -> None:
        """Host-side pre-alloc before the compiled decode step: every
        active slot whose next write position crosses into an unbacked
        page-table entry gets a fresh page — preempting the lowest-priority
        slot of the SAME replica when the freelist is dry (possibly the
        requester itself)."""
        for slot in np.nonzero(self._active)[0]:
            if not self._active[slot]:    # preempted by an earlier iteration
                continue
            pi = int(self._t[slot]) // self.page_size
            if pi >= self.pages_per_slot or self._table[slot, pi] >= 0:
                continue
            r = self.scheduler.replica_of(int(slot))
            while True:
                pg = self.pool.alloc(r, 1)
                if pg is not None:
                    self._table[slot, pi] = pg[0]
                    break
                victim = self._pick_victim(r)
                if victim is None:           # pragma: no cover - can't happen
                    raise RuntimeError("page pool exhausted with no "
                                       "preemptible slot")
                self._preempt(victim)
                if victim == slot:           # requester evicted itself
                    break

    def _append(self, slot: int, handle: RequestHandle, tok: int):
        handle.append(tok)
        self._ngen[slot] += 1
        eos = (handle.request.eos_id if handle.request.eos_id is not None
               else self.eos_id)
        if self._ngen[slot] >= handle.request.max_new_tokens:
            self._finish(slot, handle, "length")
        elif eos is not None and tok == int(eos):
            self._finish(slot, handle, "eos")

    def _finish(self, slot: int, handle: RequestHandle, reason: str):
        handle.finish(reason)
        if self.kv_layout == "paged":
            self._free_slot_pages(slot)
        self.scheduler.free(slot)
        self._active[slot] = False

    def _expire(self) -> int:
        """Drop queued requests whose deadline has passed — BEFORE they
        burn a prefill (scheduler sweep; reason ``deadline_exceeded``)."""
        expired = self.scheduler.expire_deadlines(self._clock())
        for h in expired:
            self._extras.pop(h.id, None)
        self.n_expired += len(expired)
        return len(expired)

    def _apply_inflight(self) -> None:
        """Stage-2/3 degradation: splice the controller's depth cap and
        in-flight budget into every active slot's live policy row
        (``set_row`` at a traced index — the SAME compiled graphs, zero
        recompiles, floored by the controller's floor) and re-price the
        slot's scheduler cost to the composed budget x depth fraction so
        the freed FLOP headroom admits more requests. Restores splice the
        ADMITTED row back when the controller releases."""
        c = self.controller
        if c is None or self._live_policy is None:
            return
        tgt = c.inflight_budget
        dcap = self._depth_cap()
        for s in np.nonzero(self._active)[0]:
            s = int(s)
            adm = self._slot_budget_key[s]
            if tgt < 1.0:
                want = tgt if adm is None else min(float(adm), tgt)
            else:
                want = adm
            if (want == self._slot_applied_key[s]
                    and dcap == self._slot_applied_depth[s]):
                continue
            row = self._policy_for(want, depth=dcap)
            with self._mesh_ctx():
                self._live_policy = self._live_policy.set_row(
                    jnp.int32(s), row, floor=c.floor)
            self._slot_applied_key[s] = want
            self._slot_applied_depth[s] = dcap
            cost = self._composed_cost(want, dcap)
            self.scheduler.reprice(s, cost)
            handle = self.scheduler.slots[s]
            if handle is not None:
                handle.budget_served = min(handle.budget_served, cost)

    def _control(self) -> int:
        """One controller evaluation (rate-limited inside ``update``):
        apply in-flight budget moves and shed queued requests with a
        Retry-After hint. Returns the number of shed requests (they are
        terminally resolved — progress events)."""
        c = self.controller
        if c is None:
            return 0
        dec = c.update(self._clock(), queue_depth=self.scheduler.pending,
                       capacity=self.B)
        if not dec["evaluated"]:
            return 0
        self._apply_inflight()
        if not dec["shed"]:
            return 0
        victims = self.scheduler.shed(
            dec["shed"],
            priority=lambda h: c.target_for(h.tenant).shed_order)
        for h in victims:
            h.retry_after = c.retry_after(dec["ratio"])
            self._extras.pop(h.id, None)
        self.n_rejected += len(victims)
        return len(victims)

    def step(self) -> int:
        """Admit queued requests into free slots, then run ONE compiled
        decode over the slot array. Returns the number of progress events
        (admissions + slots that advanced + expired/shed resolutions) —
        admissions count, so a request finishing on its very first
        (prefill) token is not mistaken for an idle engine. 0 = the
        engine is truly idle.

        Paged mode: admission packs jointly on free pages AND the FLOP
        budget (``_page_check``); an admission that races out of pages
        inside the batch is re-queued at the front; decode pre-allocates
        crossing-page slots, preempting by page pressure when dry.

        With an ``SLOController``: expired queue deadlines are dropped
        before admission, admissions are capped at the degraded budget
        (cost AND policy row), and the control loop evaluates at the end
        of the step — see ``runtime/controller.py``.

        Under the JAX profiler each phase is a host span (``serve.step``
        and the ``serve.*`` spans inside it, on the profiler's clock)."""
        with TraceAnnotation("serve.step"):
            return self._step()

    def _step(self) -> int:
        paged = self.kv_layout == "paged"
        with TraceAnnotation("serve.schedule"):
            expired = self._expire()
            cap = (self.controller.admission_cap()
                   if self.controller is not None else None)
            dcap = self._depth_cap()
            picked = self.scheduler.admit(
                page_check=self._page_check if paged else None,
                cost_cap=cap, cost_scale=dcap)
        admitted = []
        for slot, handle in picked:
            with TraceAnnotation("serve.admit", request_id=handle.id,
                                 slot=slot,
                                 prompt_len=np.size(handle.request.prompt)):
                ok = (self._admit_one_paged(slot, handle) if paged
                      else self._admit_one(slot, handle))
            if ok:
                admitted.append((slot, handle))
            else:
                cost = self.scheduler.costs[slot]
                self.scheduler.free(slot)
                self.scheduler.requeue_front(handle, cost)
        if paged:
            with TraceAnnotation("serve.pages"):
                self._ensure_decode_pages()   # may preempt: before `live`
        if not self._active.any():
            with TraceAnnotation("serve.emit"):
                n_ctl = self._control()
            return len(admitted) + expired + n_ctl
        live = [(s, h) for s, h in enumerate(self.scheduler.slots)
                if h is not None and self._active[s]]
        with self._mesh_ctx():
            with TraceAnnotation("serve.upload"):
                ops = (jnp.asarray(self._t), self._live_policy,
                       jnp.asarray(self._active), jnp.asarray(self._temp),
                       jnp.asarray(self._topk), jnp.asarray(self._seeds))
                if paged:
                    ops += (jnp.asarray(self._table),
                            jnp.asarray(self._trash))
            # table entries the paged kernel visits: 0 .. t // ps a slot
            pages = (int((self._t[self._active] // self.page_size + 1).sum())
                     if paged else 0)
            with TraceAnnotation("serve.decode", live=len(live),
                                 pages=pages):
                self._tok, self._caches = self._step_fn(
                    self.params, self.rp, self._tok, self._caches, *ops)
        with TraceAnnotation("serve.sync"):
            toks = np.asarray(self._tok)
        with TraceAnnotation("serve.emit"):
            self.scheduler.tick()
            for slot, handle in live:
                self._t[slot] += 1
                self._append(slot, handle, int(toks[slot]))
            if self.controller is not None:
                for slot, handle in live:
                    if len(handle.t_tokens) >= 2:
                        self.controller.record_itl(
                            handle.tenant, self.scheduler.replica_of(slot),
                            (handle.t_tokens[-1] - handle.t_tokens[-2])
                            * 1e3, t=handle.t_tokens[-1])
            n_ctl = self._control()
        return len(admitted) + len(live) + expired + n_ctl

    # ------------------------------- fork ------------------------------------

    def fork(self, handle: RequestHandle,
             max_new_tokens: Optional[int] = None,
             seed: Optional[int] = None) -> RequestHandle:
        """Copy-on-write fork of a RUNNING paged request: the child claims
        a free slot on the parent's replica, shares every FULL page of the
        parent's history by refcount, and deep-copies only the partial tail
        page (one compiled ``copy_page_in_tree`` call — n_keep lanes kept).
        The child continues from the parent's exact decode state: with the
        same seed and greedy sampling its tokens bit-match an independent
        run fed prompt + parent-output-so-far. Parent and child then
        diverge freely — each appends into its OWN tail page."""
        if self.kv_layout != "paged":
            raise ValueError("fork() requires kv_layout='paged'")
        if handle.status != "running" or handle.slot is None:
            raise ValueError("fork() requires a running request")
        s = handle.slot
        r = self.scheduler.replica_of(s)
        free = self.scheduler.free_slots_in(r)
        if not free:
            raise RuntimeError(f"no free slot on replica {r} to fork into")
        req = handle.request
        remaining = (req.max_new_tokens - len(handle.output)
                     if max_new_tokens is None else int(max_new_tokens))
        if remaining <= 0:
            raise ValueError("nothing left to generate for the fork")
        dst = self.pool.alloc(r, 1)
        if dst is None:
            raise RuntimeError(f"no free page on replica {r} to fork")
        dst = dst[0]
        cs = free[0]
        t = int(self._t[s])
        n_full, rem = t // self.page_size, t % self.page_size
        row = np.full(self.pages_per_slot, -1, np.int32)
        for i in range(n_full):
            row[i] = self._table[s, i]
            self.pool.incref(int(row[i]))
        # the child's tail/append page: a copy of the parent's partial tail
        # (rem lanes kept), or a blank pre-alloc when the tail is page-
        # aligned (n_keep=0 masks every lane; src=dst is a no-op copy)
        row[n_full] = dst
        src = int(self._table[s, n_full]) if rem else dst
        with self._mesh_ctx():
            self._caches = self._fork_fn(self._caches, jnp.int32(src),
                                         jnp.int32(dst), jnp.int32(rem))
        self._table[cs] = row
        prompt = np.concatenate([np.asarray(req.prompt, np.int32).reshape(-1),
                                 np.asarray(handle.output, np.int32)])
        creq = dataclasses.replace(
            req, prompt=prompt, max_new_tokens=remaining,
            seed=req.seed if seed is None else seed)
        child = RequestHandle(creq, engine=self, clock=self._clock)
        child.tenant = handle.tenant
        child.slot, child.status = cs, "running"
        self.scheduler.slots[cs] = child
        self.scheduler.costs[cs] = self.scheduler.costs[s]
        self._tok = self._tok.at[cs].set(self._tok[s])
        self._t[cs] = t
        self._active[cs] = True
        self._temp[cs] = creq.temperature
        self._topk[cs] = creq.top_k
        self._seeds[cs] = int(creq.seed) & 0xFFFFFFFF
        self._ngen[cs] = 0
        self._admit_seq[cs] = next(self._admit_counter)
        if self._live_policy is not None:
            pol_row = self._policy_for(req.budget if req.budget is not None
                                       else self.default_budget)
            with self._mesh_ctx():
                self._live_policy = self._live_policy.set_row(
                    jnp.int32(cs), pol_row)
        return child

    # --------------------------- legacy wrapper ------------------------------

    def generate(self, requests: List[GenRequest],
                 extra_inputs: Optional[dict] = None,
                 budget: Optional[float] = None) -> List[np.ndarray]:
        """Synchronous batch API (legacy): submit everything, step until
        done. ``budget`` overrides every request's budget for this call.
        ``extra_inputs`` leaves carry a leading dim indexed per request."""
        handles = []
        for i, r in enumerate(requests):
            if budget is not None:
                r = dataclasses.replace(r, budget=budget)
            extra = None
            if extra_inputs:
                extra = {k: np.asarray(v)[i:i + 1]
                         for k, v in extra_inputs.items()}
            handles.append(self.submit(r, extra_inputs=extra))
        while not all(h.done for h in handles):
            if self.step() == 0 and not all(h.done for h in handles):
                raise RuntimeError("serving engine stalled")  # pragma: no cover
        return [np.asarray(h.output, np.int32) for h in handles]
