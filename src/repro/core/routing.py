"""ElastiFormer routing primitives (the paper's Alg. 1 & 2 + §B).

Two schemes:
  * input subset selection  — scalar sigmoid router per token, top-k (k=c*T)
    during training, threshold theta at causal inference (§B.1), BCE aux loss.
  * parameter subset selection — M-way router, w = M*softmax(W_r x), top-k
    submodules, straight-through via output scaling, load-balance aux (§B.2).

Capacities and top-k counts come in two flavors (see core/policy.py):
  * python numbers — trace-time constants; top-k executes on a *ragged
    capacity bucket* (default) or exact *gather* buffer with real FLOP
    savings in the lowered HLO;
  * traced jnp scalars / (B,) arrays — one compiled graph serves every
    budget (and mixed per-request budgets inside one batch): with a static
    ``bucket`` hint the ragged path keeps the FLOP savings (one graph per
    bucket, <= RAGGED_N_BUCKETS total), without one it falls back to
    rank-based validity *masking* at full shapes. Any capacity >= 1 (or
    top-k >= M, or ``student <= 0``) short-circuits to the exact unrouted
    module: router weights are forced to 1, the paper's losslessness
    property.

The ragged machinery (``capacity_buckets`` / ``bucket_for`` /
``make_plan`` / ``resolve_bucket``) stably partitions the sequence
valid-first: the selected tokens form a position-ascending prefix of a
static bucket-sized buffer, the true count rides along as a traced scalar
that the Pallas kernels use to skip trailing tiles. A block's full routing
decision is one ``RoutingPlan`` — gather indices, inverse scatter
permutation, validity, count, membership — derived from a SINGLE sort and
shared by every student in the block; ``resolve_bucket`` returning the
full sequence length is the identity fast path (full budget: skip the
partition entirely).

All router math is float32 regardless of backbone dtype.
"""
from __future__ import annotations

import inspect
import math
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp



def _z():
    return jnp.zeros((), jnp.float32)


class RouteAux(NamedTuple):
    load: jnp.ndarray   # load-balance loss contribution (scalar)
    topk: jnp.ndarray   # BCE top-k consistency loss contribution (scalar)
    sel: jnp.ndarray    # sum over routers of selected-token fraction
    cnt: jnp.ndarray    # number of routers contributing to `sel`

    @staticmethod
    def zero():
        return RouteAux(_z(), _z(), _z(), _z())

    @staticmethod
    def of(load=None, topk=None, keep=None):
        """keep: bool selection mask -> records its mean as a sel-rate."""
        sel = cnt = None
        if keep is not None:
            sel = jnp.mean(keep.astype(jnp.float32))
            cnt = jnp.ones((), jnp.float32)
        return RouteAux(load if load is not None else _z(),
                        topk if topk is not None else _z(),
                        sel if sel is not None else _z(),
                        cnt if cnt is not None else _z())

    def __add__(self, o):
        return RouteAux(self.load + o.load, self.topk + o.topk,
                        self.sel + o.sel, self.cnt + o.cnt)

    @property
    def sel_rate(self):
        """Mean fraction of tokens processed across token routers."""
        return self.sel / jnp.maximum(self.cnt, 1.0)


# ----------------------- input subset selection -----------------------------

def token_router_init(key, d: int):
    w = jax.random.normal(key, (d,), jnp.float32) * (1.0 / math.sqrt(d))
    return {"w": w, "b": jnp.zeros((), jnp.float32)}


@jax.named_scope("router")
def token_logits(rp, x):
    """Scalar routing logits per token. x: (..., D) -> (...,) f32."""
    return x.astype(jnp.float32) @ rp["w"] + rp["b"]


def topk_indices(scores, k: int):
    """Top-k indices along the last axis, sorted ascending (causal order)."""
    _, idx = jax.lax.top_k(scores, k)
    return jnp.sort(idx, axis=-1)


def topk_mask(scores, k: int):
    """Boolean membership mask of the top-k entries along the last axis."""
    kth = jax.lax.top_k(scores, k)[0][..., -1:]
    return scores >= kth


# ----------------- static/traced scalar plumbing (policy leaves) -------------

def is_static(v) -> bool:
    """True for python numbers (trace-time constants from the legacy
    ``ElasticConfig`` path); traced policy leaves are jnp arrays/tracers."""
    return isinstance(v, (int, float))


def bcast_to(v, ndim: int):
    """Right-pad a leading-dims value ((), (B,), ...) with singleton axes so
    it broadcasts against an (B, ..., n) tensor of rank ``ndim``."""
    if is_static(v):
        return v
    v = jnp.asarray(v)
    return v.reshape(v.shape + (1,) * (ndim - v.ndim))


# Trace-time counter over the sorts issued by the routing machinery (the
# test hook behind the "one RoutingPlan sort per block" invariant). Every
# argsort in this module MUST go through _argsort so the counter is honest.
PLAN_SORT_COUNT = 0


def _argsort(x, axis: int = -1):
    global PLAN_SORT_COUNT
    PLAN_SORT_COUNT += 1
    return jnp.argsort(x, axis=axis)


def invert_permutation(perm):
    """Inverse of a batched permutation along the last axis WITHOUT a second
    sort: inv[..., perm[..., i]] = i via an int32 scatter (O(S) vs the
    O(S log S) argsort-of-argsort it replaces)."""
    s = perm.shape[-1]
    flat = perm.reshape(-1, s)
    b = jnp.arange(flat.shape[0])[:, None]
    ar = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), flat.shape)
    inv = jnp.zeros_like(flat).at[b, flat].set(ar)
    return inv.reshape(perm.shape)


def token_ranks(scores):
    """Descending rank of each entry along the last axis (0 = largest).
    ONE sort: the inverse permutation is derived by scatter, not by the
    legacy argsort(argsort(-scores)) double sort (bit-identical: jnp.argsort
    is stable, so ties still break by ascending position)."""
    return invert_permutation(_argsort(-scores, axis=-1))


def topk_mask_dyn(scores, k):
    """topk_mask with a *traced* k ((), or any leading-dims shape): an entry
    is kept iff its descending rank is < k. Ties broken by position."""
    return token_ranks(scores) < bcast_to(k, scores.ndim)


def topk_mask_any(scores, k):
    if is_static(k):
        return topk_mask(scores, int(k))
    return topk_mask_dyn(scores, k)


def capacity_k(capacity, s: int, mxu: bool = False):
    """ceil(capacity * s) clipped to [1, s]; python int when static.

    ``mxu``: on long sequences (s >= 1024) round the count up to a multiple
    of 128 (MXU-friendly gather sizes) — the traced path applies the SAME
    rule so one-graph masking selects exactly the tokens the static gather
    compile would have. Every call site (gather, dense mask, ragged bucket
    selection) must pass the same ``mxu`` so all three execution paths pick
    the exact same token set."""
    if is_static(capacity):
        k = int(math.ceil(capacity * s))
        if mxu and s >= 1024:
            k = min(s, -(-k // 128) * 128)
        return max(1, min(s, k))
    k = jnp.ceil(capacity * s)
    if mxu and s >= 1024:
        k = jnp.minimum(s, jnp.ceil(k / 128) * 128)
    return jnp.clip(k, 1, s)


# --------------------- ragged capacity buckets ------------------------------

RAGGED_N_BUCKETS = 4     # static graphs per sequence length, max
RAGGED_ALIGN = 128       # MXU lane alignment of bucket sizes

# Sentinel bucket hint meaning "every row is at FULL budget": compile the
# identity graph (no partition/gather/scatter — bit-exact teacher math).
# Deliberately not a valid buffer size, so a real bucket solved for one
# sequence length can never be mistaken for the identity assertion when a
# shorter batch happens to match it.
IDENTITY_BUCKET = -1


def capacity_buckets(s: int, *, n_buckets: int = RAGGED_N_BUCKETS,
                     align: int = RAGGED_ALIGN):
    """Static ragged buffer sizes for sequence length ``s``: ``n_buckets``
    evenly spaced fractions of s, each rounded up to a multiple of ``align``
    (shrunk on short sequences so buckets stay distinct), capped at s.
    Every budget maps onto one of these, so the one-compile-per-budget
    blow-up of the legacy gather path collapses to <= n_buckets graphs."""
    align = max(1, min(align, -(-s // n_buckets)))
    out = []
    for i in range(1, n_buckets + 1):
        b = -(-s * i // n_buckets)            # ceil(s*i/n)
        b = min(s, -(-b // align) * align)    # round up to align
        if not out or b > out[-1]:
            out.append(b)
    return tuple(out)


def bucket_for(k: int, s: int, *, n_buckets: int = RAGGED_N_BUCKETS,
               align: int = RAGGED_ALIGN) -> int:
    """Smallest static bucket >= k tokens (k <= s)."""
    for b in capacity_buckets(s, n_buckets=n_buckets, align=align):
        if b >= k:
            return b
    return s


class RoutingPlan(NamedTuple):
    """One block's token-routing decision, derived from a SINGLE sort.

    The plan is the shared currency of the routed-execution layer: the
    attention and MLP/MoE students of a block consume the same plan instead
    of each re-deriving ranks (a double argsort), the valid-first partition
    (another argsort), and a scatter permutation per component.

    idx   : (..., bucket) i32 — gather indices; the selected tokens form a
            position-ascending prefix (causal attention over the prefix IS
            causal attention over the selected tokens), the tail holds the
            remaining tokens (position-ascending) and is masked by `valid`.
    inv   : (..., S) i32 — inverse scatter permutation: token position ->
            buffer slot (>= bucket: the token was dropped entirely). Turns
            the scatter-back into a cheap gather (`plan_scatter`).
    valid : (..., bucket) bool — prefix validity of the buffer rows.
    count : python int (static k) or (...,) i32 — true selected count; the
            scalar-prefetched ragged argument of the Pallas kernels.
    keep  : (..., S) bool — membership mask (BCE aux target / kv validity).
    bucket: static buffer size. bucket == S with every row kept is the
            identity plan — callers fast-path it and skip gather/scatter.
    """
    idx: jnp.ndarray
    inv: jnp.ndarray
    valid: jnp.ndarray
    count: object
    keep: jnp.ndarray
    bucket: int


@jax.named_scope("router")
def make_plan(scores, k, bucket: int) -> RoutingPlan:
    """Build a RoutingPlan from router scores with ONE sort.

    scores: (..., S); k: top-k count — python int, traced scalar, or
    per-row (B,); bucket: static buffer size (k is clamped to it).

    Derivation: one stable argsort of -scores gives the descending order;
    ranks are its inverse permutation (scatter, not a second sort); the
    valid-first destination of every token is a cumsum over the keep mask;
    the gather permutation is that destination's inverse (another scatter).
    Total: 1 sort + 2 int32 scatters + 2 cumsums, replacing the legacy
    3-sort chain (token_ranks x2 + ragged_select's partition argsort)."""
    s = scores.shape[-1]
    ranks = token_ranks(scores)                       # ONE sort (counted)
    if is_static(k):
        kk = max(1, min(int(k), bucket))
        keep = ranks < kk
        count = kk
    else:
        kk = jnp.minimum(k, bucket)
        keep = ranks < bcast_to(kk, scores.ndim)
        count = jnp.sum(keep, axis=-1).astype(jnp.int32)
    nk = jnp.cumsum(keep.astype(jnp.int32), axis=-1)
    n_keep = nk[..., -1:]
    dest = jnp.where(keep, nk - 1,
                     n_keep + jnp.cumsum((~keep).astype(jnp.int32), -1) - 1)
    perm = invert_permutation(dest)                   # scatter, not a sort
    idx = perm[..., :bucket].astype(jnp.int32)
    if is_static(k):
        valid = jnp.broadcast_to(jnp.arange(bucket) < count, idx.shape)
    else:
        valid = jnp.arange(bucket) < count[..., None]
    return RoutingPlan(idx, dest.astype(jnp.int32), valid, count, keep,
                       bucket)


def constrain_plan(plan: RoutingPlan) -> RoutingPlan:
    """Pin the plan's token-dim arrays to batch-over-data / REPLICATED over
    `model` under the active mesh (no-op outside one, or inside a manual
    shard_map region — callers gate on that): the plan is built once per
    block from full-(B, T) router scores, and every TP shard of the block
    must consume the SAME gather/scatter permutation — a model-sharded
    plan would route different tokens through different weight shards.
    Tiny int/bool arrays, so replication costs nothing; what it buys is
    that GSPMD never re-partitions the sort/cumsum chain (one sort per
    block stays one sort under the mesh)."""
    from repro.runtime import sharding as SH
    c = lambda a: (SH.constrain_batch(a)
                   if getattr(a, "ndim", 0) >= 1 else a)
    return plan._replace(idx=c(plan.idx), inv=c(plan.inv),
                         valid=c(plan.valid), count=c(plan.count),
                         keep=c(plan.keep))


def plan_gather(x, plan: RoutingPlan):
    """x: (B, S, ...) -> (B, bucket, ...) selected-first buffer."""
    return gather_tokens(x, plan.idx)


def plan_scatter(plan: RoutingPlan, shape_like, vals):
    """Inverse of plan_gather as a GATHER by the plan's inverse permutation
    (no scatter-add: XLA lowers batched scatter-adds to f32 upcasts plus
    full-buffer copies). vals: (B, bucket, ...) already weighted; rows the
    plan dropped (inv >= bucket) and the masked tail contribute zeros."""
    b = plan.bucket
    safe = jnp.minimum(plan.inv, b - 1)
    expand = (slice(None), slice(None)) + (None,) * (vals.ndim - 2)
    out = jnp.take_along_axis(vals, safe[expand], axis=1)
    live = (plan.inv < b) & plan.keep
    return jnp.where(live[expand], out, 0).astype(shape_like.dtype)


def ragged_select(scores, k, bucket: int):
    """Stable valid-first partition for ragged capacity-bucket routing.

    Legacy entry point, now a thin view over ``make_plan`` (one sort instead
    of three). Returns (idx (..., bucket) i32, valid (..., bucket) bool,
    count): ``idx[..., :k]`` are the top-k tokens in ascending POSITION
    order (the exact token set of ``topk_mask_dyn``, ties by position), the
    tail is filled with the remaining tokens and masked out by ``valid``;
    ``count`` is the number of valid prefix rows (python int when k is
    static) — the traced scalar the Pallas kernels take to skip trailing
    tiles.

    ``k`` is clamped to ``bucket``: callers must pass a covering bucket
    (``resolve_bucket`` / ``policy.ragged_bucket`` guarantee it); an
    undersized one degrades to a well-defined truncation — the top-bucket
    tokens — with ``keep``/``count``/``valid`` all agreeing on the executed
    set, never an all-valid mask over silently dropped tokens."""
    plan = make_plan(scores, k, bucket)
    return plan.idx, plan.valid, plan.count


def threshold_logit(theta):
    """Router-logit threshold equivalent to sigmoid(logit) > theta."""
    if is_static(theta):
        return math.log(theta / (1.0 - theta)) if 0.0 < theta < 1.0 \
            else (-jnp.inf if theta <= 0.0 else jnp.inf)
    theta = jnp.clip(jnp.asarray(theta, jnp.float32), 1e-6, 1.0 - 1e-6)
    return jnp.log(theta) - jnp.log1p(-theta)


def gate_capacity(capacity, student):
    """Teacher gating: ``student <= 0`` forces full capacity (exact teacher)."""
    if student is None:
        return capacity
    if is_static(student):
        return capacity if student > 0 else 1.0
    cap = capacity if not is_static(capacity) else jnp.asarray(
        capacity, jnp.float32)
    return jnp.where(jnp.asarray(student) > 0, cap, 1.0)


def gate_topk(k, student, n: int):
    """Teacher gating for parameter-subset top-k: student off -> all n."""
    if student is None:
        return k
    if is_static(student):
        return k if student > 0 else n
    kk = k if not is_static(k) else jnp.asarray(k, jnp.float32)
    return jnp.where(jnp.asarray(student) > 0, kk, n)


def is_full(v, limit=1.0):
    """capacity >= 1 (or top-k >= M): the knob requests the exact teacher.
    python bool when static, else a traced bool array."""
    if is_static(v):
        return v >= limit
    return jnp.asarray(v) >= limit


@jax.named_scope("router")
def token_gate(logits, scores, capacity, mode: str, *, theta=0.5,
               mxu: bool = False):
    """Unified keep-mask + router weight for input subset selection.

    Train: top-k by capacity (static fast path or traced rank masking; both
    use the same rounding — see ``capacity_k``'s ``mxu``).
    Infer: threshold theta on the router sigmoid (§B.1).
    Any capacity >= 1 forces (keep=all, weight=1) — exact teacher.
    Returns (keep bool (B,S), weight f32 (B,S)).
    """
    S = scores.shape[-1]
    if mode == "train":
        keep = topk_mask_any(scores, capacity_k(capacity, S, mxu=mxu))
    else:
        keep = logits > bcast_to(threshold_logit(theta), logits.ndim)
    full = is_full(capacity)
    if is_static(full):
        if full:
            return jnp.ones_like(keep, bool), jnp.ones_like(scores)
        return keep, keep * scores
    full = bcast_to(full, keep.ndim)
    keep = keep | full
    return keep, jnp.where(full, 1.0, keep * scores)


@jax.named_scope("router")
def bce_topk_loss(logits, in_topk):
    """§B.1 auxiliary loss: router sigmoid should predict top-k membership."""
    y = in_topk.astype(jnp.float32)
    return jnp.mean(
        jnp.maximum(logits, 0) - logits * y + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def gather_tokens(x, idx):
    """x: (B,S,...) idx: (B,k) -> (B,k,...)."""
    expand = (slice(None), slice(None)) + (None,) * (x.ndim - 2)
    return jnp.take_along_axis(x, idx[expand], axis=1)


def scatter_add_tokens(shape_like, idx, vals):
    """Inverse of gather_tokens: zeros.at[b, idx].add(vals)."""
    y = jnp.zeros_like(shape_like)
    b = jnp.arange(y.shape[0])[:, None]
    return y.at[b, idx].add(vals.astype(y.dtype))


def _accepts_token_valid(f) -> bool:
    """True when f's signature exposes the ragged prefix contract
    (a ``token_valid`` parameter or ``**kwargs``)."""
    try:
        params = inspect.signature(f).parameters
    except (TypeError, ValueError):
        return False
    return "token_valid" in params or any(
        p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values())


def resolve_bucket(capacity, s: int, bucket=None, impl: str = "ragged"):
    """Static plan buffer size for this trace. Returns one of:

      * ``None``  — no static plan possible: dense rank-masked fallback
        (traced capacity without a bucket hint, or a bucket that would
        round up to the full sequence without being full-budget);
      * ``s``     — the IDENTITY fast path: the caller asserts every row is
        at full budget (static capacity >= 1, or ``policy.ragged_bucket``
        returned ``s`` after checking the concrete policy host-side), so
        partition + gather + scatter are skipped entirely and the block
        runs the bit-exact teacher math;
      * ``0 < b < s`` — plan buffer size: the ragged capacity bucket, or
        the exact MXU-rounded top-k count under ``impl == "gather"``.

    Static capacities derive the size inline; traced capacities ride the
    caller's static ``bucket`` hint (which must cover the largest per-row
    top-k this graph will see). The identity assertion travels as the
    distinct ``IDENTITY_BUCKET`` sentinel (what ``policy.ragged_bucket``
    returns after checking the concrete policy host-side) — an ordinary
    hint that merely reaches ``s`` (solved for a longer sequence, applied
    to a shorter batch) degrades to the dense fallback like the pre-plan
    code, never to the unrouted graph."""
    if capacity is None:
        return None
    if is_static(capacity):
        if capacity >= 1.0:
            return s
        k = capacity_k(capacity, s, mxu=True)
        kb = min(s, k if impl == "gather" else bucket_for(k, s))
        return kb if kb < s else None
    if bucket is None:
        return None
    kb = int(bucket)
    if kb == IDENTITY_BUCKET:
        return s
    return kb if kb < s else None


def route_tokens(
    rp,
    x,                      # (B, S, D)
    f: Callable,            # f(x_sub, positions_sub) -> (B, k(or S), D)
    capacity,               # None | python float (static) | traced scalar/(B,)
    mode: str,              # base | train | infer
    positions=None,         # (S,) int32 positions (for RoPE/causal inside f)
    impl: str = "ragged",
    theta=0.5,              # inference threshold (policy.theta)
    student=None,           # policy.student: <=0 bypasses routing entirely
    bucket=None,            # static ragged buffer size (traced capacities)
    mxu: bool = True,       # capacity_k rounding — same flag on EVERY path
):
    """Input subset selection around a module f (residual added by caller).

    This is the standalone single-component API (and the model's inference
    thresholding path). The model's train-mode hot path does NOT come
    through here: ``models/blocks.block_apply`` inlines the same
    plan/identity semantics so one RoutingPlan can be SHARED across a
    block's components — keep the two in sync (tests/test_routing.py
    pins this function, tests/test_backend.py pins the block-level grid).

    Returns (delta, aux). delta is f's (router-weighted) contribution.
    Three implementations of the train-mode top-k:
      * ragged (default): gather into a capacity-bucket buffer (static
        bucket size, traced true count) — FLOPs proportional to the bucket,
        <= RAGGED_N_BUCKETS compiles per sequence length;
      * gather: legacy static top-k gather — smallest HLO, one compile PER
        budget; static capacities only;
      * dense_mask: full-shape compute with rank masking — one compile for
        every budget, no FLOP savings (reference/fallback; also serves
        inference thresholding and traced capacities without a bucket).
    """
    B, S, D = x.shape
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)
    if capacity is None or mode == "base":
        return f(x, positions), RouteAux.zero()

    capacity = gate_capacity(capacity, student)
    logits = token_logits(rp, x)            # (B, S)
    scores = jax.nn.sigmoid(logits)

    kb = None
    if mode == "train" and impl in ("ragged", "gather"):
        if impl == "ragged" or (is_static(capacity) and is_static(theta)):
            kb = resolve_bucket(capacity, S, bucket, impl=impl)
    if kb == S:
        # identity fast path: full budget on every row — skip partition,
        # gather, and scatter entirely (bit-exact: weights would be 1.0)
        keep = jnp.ones((B, S), bool)
        return f(x, positions), RouteAux.of(
            topk=bce_topk_loss(logits, keep), keep=keep)
    if kb is not None:
        k = capacity_k(capacity, S, mxu=mxu)
        plan = make_plan(scores, k, kb)      # the ONE sort of this call
        x_sel = plan_gather(x, plan)
        pos_sel = positions[plan.idx] if positions.ndim == 1 \
            else jnp.take_along_axis(positions, plan.idx, 1)
        # Modules that understand the ragged prefix contract (e.g. MoE
        # dispatch, where masked tail rows must not consume expert
        # capacity) get the validity mask and true count. Awareness is
        # declared by the SIGNATURE: expose a ``token_valid`` kwarg (or
        # **kwargs) — a wrapper that hides it opts its module out, so
        # wrap ragged-aware modules with functools.wraps or forward the
        # kwargs explicitly.
        if _accepts_token_valid(f):
            y_sel = f(x_sel, pos_sel, token_valid=plan.valid,
                      token_count=plan.count)
        else:
            y_sel = f(x_sel, pos_sel)
        w_sel = jnp.take_along_axis(scores, plan.idx, axis=1) * plan.valid
        delta = plan_scatter(plan, x,
                             y_sel * w_sel[..., None].astype(y_sel.dtype))
        return delta, RouteAux.of(topk=bce_topk_loss(logits, plan.keep),
                                  keep=plan.keep)

    # dense path: full-shape compute, rank/threshold masking (train w/
    # dense_mask impl, inference, and traced capacities without a bucket)
    keep, w = token_gate(logits, scores, capacity, mode, theta=theta, mxu=mxu)
    y = f(x, positions)
    delta = y * w[..., None].astype(y.dtype)
    if mode == "train":
        return delta, RouteAux.of(topk=bce_topk_loss(logits, keep), keep=keep)
    return delta, RouteAux.of(keep=keep)


# --------------------- parameter subset selection ---------------------------

def param_router_init(key, d: int, m: int):
    w = jax.random.normal(key, (d, m), jnp.float32) * (1.0 / math.sqrt(d))
    return {"w": w}


@jax.named_scope("router")
def param_route_weights(rp, x, top_k, normalize_to_m: bool = True,
                        valid=None):
    """Alg. 1: w = M * softmax(W_r x); top-k selection mask.

    ``top_k`` may be a python int (static) or a traced scalar/(B,) array
    (rank masking; one compiled graph for every k). ``valid`` (x's leading
    dims) excludes rows from the load-balance statistics — ragged bucket
    buffers pass their prefix mask so the padded tail (whose outputs are
    weighted to zero anyway) cannot skew the aux loss.
    Returns (weights (...,M) f32, mask (...,M) bool, aux RouteAux).
    With k == M and a uniform router this reproduces the base module exactly
    (weights == 1 everywhere) — the paper's losslessness property.
    """
    m = rp["w"].shape[-1]
    logits = x.astype(jnp.float32) @ rp["w"]            # (..., M)
    probs = jax.nn.softmax(logits, axis=-1)
    w = probs * m if normalize_to_m else probs
    k = min(int(top_k), m) if is_static(top_k) else jnp.clip(top_k, 1, m)
    mask = topk_mask_any(w, k)
    # §B.2 load-balance: E_m[frac_selected(m) * mean_prob(m)] * M
    red = tuple(range(probs.ndim - 1))
    if valid is None:
        frac = jnp.mean(mask.astype(jnp.float32), axis=red)
        mean_p = jnp.mean(probs, axis=red)
    else:
        vw = valid.astype(jnp.float32)[..., None]
        denom = jnp.maximum(jnp.sum(vw), 1.0)
        frac = jnp.sum(mask * vw, axis=red) / denom
        mean_p = jnp.sum(probs * vw, axis=red) / denom
    load = m * jnp.sum(frac * mean_p)
    return w, mask, RouteAux.of(load=load)
