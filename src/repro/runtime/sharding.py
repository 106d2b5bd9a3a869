"""Logical->physical sharding rules (Megatron-style TP on the `model` axis,
DP over (`pod`,`data`)).

Rules are name+rank based over the parameter pytree, so one table covers all
ten architectures. A dim that does not divide its mesh axis is replicated
(``_fit_spec``) — documented in DESIGN.md §4.

KV caches shard kv-heads over `model` when divisible, else fall back to
sharding head_dim (always 128 | 64) — the fallback's extra collectives are a
§Perf target.
"""
from __future__ import annotations

import re
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def batch_axes(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def data_axis_size(mesh: Optional[Mesh]) -> int:
    """Product of the data axes' sizes — the data-parallel replica count.
    THE definition shared by the serving scheduler's replica axis and the
    kernel wrappers' batch-shard predicates (they must agree: the scheduler
    packs per replica exactly what one batch shard decodes)."""
    if mesh is None:
        return 1
    n = 1
    for a in batch_axes(mesh):
        n *= mesh.shape.get(a, 1)
    return n


def active_mesh() -> Optional[Mesh]:
    """The mesh installed by `with mesh:` at trace time (None outside)."""
    from jax._src.mesh import thread_resources
    m = thread_resources.env.physical_mesh
    return m if m.axis_names else None


def constrain_batch(x):
    """Pin an activation's leading (batch) dim to the data axes — GSPMD
    loses batch parallelism through batch-indexed gather/scatter (§Perf H2:
    the MoE combine scatter was replicated to the full global batch).
    No-op outside a mesh context or when batch doesn't divide."""
    m = active_mesh()
    if m is None:
        return x
    spec = _fit_spec(batch_spec(m, x.ndim - 1), x.shape, m)
    return jax.lax.with_sharding_constraint(x, spec)


def model_axis_size(mesh: Mesh) -> int:
    return mesh.shape.get("model", 1)


# ----------------------------- parameters -----------------------------------

_RULES = [
    # (regex on keystr tail, rank, PartitionSpec)
    (r"\['embed'\]$", 2, P("model", None)),            # (V, D) vocab-sharded
    (r"\['lm_head'\]$", 2, P(None, "model")),          # (D, V)
    (r"\['w[qkv]'\]$", 3, P(None, "model", None)),     # (D, H, Dh) heads
    (r"\['b[qkv]'\]$", 2, P("model", None)),           # (H, Dh)
    (r"\['mlp'\].*\['w[ig]'\]$", 3, P(None, None, "model")),  # MoE (E,D,Fe) TP-on-F
    (r"\['mlp'\].*\['wo'\]$", 3, P(None, "model", None)),     # MoE (E,Fe,D)
    (r"\['mlp'\].*\['w[ig]'\]$", 2, P(None, "model")),   # dense (D, F)
    (r"\['mlp'\].*\['wo'\]$", 2, P("model", None)),      # dense (F, D)
    (r"\['wo'\]$", 3, P("model", None, None)),         # attn out (H, Dh, D)
    (r"\['router'\]$", 2, P()),                        # tiny, replicated
    # mamba2
    (r"\['in_[zx]'\]$", 2, P(None, "model")),          # (D, d_inner)
    (r"\['in_dt'\]$", 2, P(None, "model")),            # (D, H)
    (r"\['in_[bc]'\]$", 2, P()),                       # group-shared, small
    (r"\['conv_x'\]$", 2, P(None, "model")),
    (r"\['(a_log|d_skip|dt_bias)'\]$", 1, P("model")),
    (r"\['norm_scale'\]$", 1, P("model")),
    (r"\['out_proj'\]$", 2, P("model", None)),         # (d_inner, D)
    # rg-lru
    (r"\['w_[yx]'\]$", 2, P(None, "model")),           # (D, W)
    (r"\['conv_w'\]$", 2, P(None, "model")),
    (r"\['conv_b'\]$", 1, P("model")),
    (r"\['w_[ai]'\]$", 2, P(None, "model")),           # (W, W) col-sharded
    (r"\['(b_a|b_i|lam)'\]$", 1, P("model")),
    (r"\['w_out'\]$", 2, P("model", None)),            # (W, D)
    # lfm2 short conv: in_proj (D, 3, D) -> B | C | x, each split over D;
    # conv_w (L, D) by the rg-lru rule; out_proj (D, D) by the mamba2 rule
    (r"\['mixer'\]\['in_proj'\]$", 3, P(None, None, "model")),
    # frontends
    (r"\['in_proj'\]$", 2, P()),
]


def _spec_for(key: str, leaf) -> P:
    """Rules match the UNSTACKED rank; each ['scan'] level adds one leading
    stacked-layer dim which gets a None prepended."""
    n_lead = key.count("['scan']")
    rank = getattr(leaf, "ndim", 0) - n_lead
    for pat, r, spec in _RULES:
        if r == rank and re.search(pat, key):
            return P(*([None] * n_lead + list(spec)))
    return P()  # norms, routers, LoRA, scalars: replicated


def _fit_spec(spec: P, shape, mesh: Optional[Mesh],
              relocate: bool = False) -> P:
    """pjit in_shardings require every sharded dim to divide the axis size
    (GSPMD implicit padding applies to intermediates, not arguments).

    For each axis whose dim does not divide: REPLICATE it by default —
    relocating a sharding onto a contraction dim (e.g. qwen2 kv weights
    (D, 4, 128) -> head_dim) turns every matmul into partial sums plus a
    giant all-reduce (§Perf H1 found 178 GB/layer of score all-reduces).
    Weights that cannot shard are small (kv heads); q-heads are padded to
    divisibility at init instead. `relocate=True` keeps the move-to-another-
    dim behaviour for KV caches, where memory capacity (not collectives)
    is the binding constraint."""
    if mesh is None:
        return spec
    dims = list(spec) + [None] * (len(shape) - len(spec))
    for i, ax in enumerate(dims):
        if ax is None:
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        size = 1
        for a in axes:
            size *= mesh.shape.get(a, 1)
        if shape[i] % size == 0:
            continue
        dims[i] = None
        if relocate:
            cands = [j for j, d in enumerate(dims)
                     if dims[j] is None and j != i and shape[j] % size == 0]
            if cands:
                dims[max(cands, key=lambda j: shape[j])] = ax
    return P(*dims)


def param_specs(params, mesh: Optional[Mesh] = None) -> dict:
    """PartitionSpec pytree matching `params` (divisibility-checked when a
    mesh is given)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = [_fit_spec(_spec_for(jax.tree_util.keystr(path), leaf),
                       getattr(leaf, "shape", ()), mesh)
             for path, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


def param_shardings(params, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(params, mesh))


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


# ----------------------------- activations ----------------------------------

def batch_spec(mesh: Mesh, extra_dims: int = 1) -> P:
    return P(batch_axes(mesh), *([None] * extra_dims))


def input_shardings(specs: dict, mesh: Mesh):
    """Shard every model input on its batch (leading) dim (replicating when
    batch < mesh axis, e.g. long_500k's global_batch=1)."""
    return {k: NamedSharding(mesh, _fit_spec(batch_spec(mesh, v.ndim - 1),
                                             v.shape, mesh))
            for k, v in specs.items()}


def fitted(spec: P, shape, mesh: Mesh) -> NamedSharding:
    """NamedSharding for `spec` with divisibility fallback."""
    return NamedSharding(mesh, _fit_spec(spec, shape, mesh))


# ------------------------------- caches -------------------------------------

def attn_kv_spec(cfg, mesh: Mesh, lead: int = 0) -> P:
    """The ONE placement rule for a (B, L, K, Dh) attention-cache tensor:
    kv-heads over `model` when divisible, else head_dim (always 128 | 64).
    Shared by `cache_specs_tree` (the jit out_shardings pin) and
    `constrain_kv_cache` (the decode write-site pin) — the two MUST agree
    or every compiled decode step pays a cache re-layout copy."""
    kv_div = cfg.n_kv_heads and cfg.n_kv_heads % model_axis_size(mesh) == 0
    tail = (None, "model", None) if kv_div else (None, None, "model")
    return P(*([None] * lead), batch_axes(mesh), *tail)


def page_pool_spec(cfg, mesh: Mesh, lead: int = 0) -> P:
    """The ONE placement rule for an (N, page_size, K, Dh) paged KV POOL
    tensor (`runtime/pagedkv.py`): the page axis shards over the data axes
    — replica locality of page ids makes pool-shard == scheduler-replica —
    and kv-heads over `model` when divisible, else head_dim. Shared by
    `cache_specs_tree` (the jit out_shardings pin) and
    `constrain_page_pool` (the page-write pins) — they MUST agree or every
    compiled step pays a pool re-layout copy."""
    kv_div = cfg.n_kv_heads and cfg.n_kv_heads % model_axis_size(mesh) == 0
    tail = (None, "model", None) if kv_div else (None, None, "model")
    return P(*([None] * lead), batch_axes(mesh), *tail)


def kv_scale_spec(cfg, mesh: Mesh, lead: int = 0) -> P:
    """Placement for an int8-KV dequant-scale leaf (docs/quantization.md):
    ring (B, L, K) and paged (N, page_size, K) share one layout — leading
    axis over the data axes, kv-heads over `model` when divisible. The
    head_dim fallback of `attn_kv_spec`/`page_pool_spec` has no analogue
    here (scales carry no Dh axis), so the K axis replicates instead."""
    kv_div = cfg.n_kv_heads and cfg.n_kv_heads % model_axis_size(mesh) == 0
    return P(*([None] * lead), batch_axes(mesh), None,
             "model" if kv_div else None)


def constrain_kv_scale(x, cfg):
    """Pin a (B, L, K) ring-cache scale leaf at its write sites — the
    scale twin of `constrain_kv_cache`, sharing `kv_scale_spec` with the
    jit out_shardings pin. No-op outside a mesh context."""
    m = active_mesh()
    if m is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, _fit_spec(kv_scale_spec(cfg, m), x.shape, m, relocate=True))


def constrain_page_pool(x, cfg, scale: bool = False):
    """Pin a page-pool leaf at its WRITE sites (chunked-prefill page
    writes, decode per-slot appends, fork's CoW page copy) under the
    active mesh — the paged twin of `constrain_kv_cache`: the writes are
    page-indexed scatters GSPMD would otherwise resolve by replicating the
    whole pool every step. Rank >= 4 is a K/V pool (page axis at
    ndim - 4); rank 3 is an int8 dequant-scale pool (N, page_size, K);
    rank 2 is a per-lane validity pool (page axis at ndim - 2). Pass
    ``scale=True`` for a scale pool with extra leading (pattern-scan)
    dims, where rank alone cannot tell it from a K/V pool. No-op outside
    a mesh context."""
    m = active_mesh()
    if m is None:
        return x
    if scale or x.ndim == 3:
        spec = kv_scale_spec(cfg, m, lead=x.ndim - 3)
    elif x.ndim >= 4:
        spec = page_pool_spec(cfg, m, lead=x.ndim - 4)
    else:
        spec = P(*([None] * (x.ndim - 2)), batch_axes(m), None)
    return jax.lax.with_sharding_constraint(
        x, _fit_spec(spec, x.shape, m, relocate=True))


def cache_specs_tree(cache_shapes, cfg, mesh: Mesh):
    """PartitionSpecs for a cache pytree (from models.cache_specs)."""
    ba = batch_axes(mesh)

    def spec(path, leaf):
        key = jax.tree_util.keystr(path)
        nscan = key.count("['scan']")
        lead = [None] * nscan
        if key.endswith("['kp']") or key.endswith("['vp']"):
            return page_pool_spec(cfg, mesh, lead=nscan)
        if key.endswith("['pvalid']"):
            return P(*lead, ba, None)
        if key.endswith("['kscale']") or key.endswith("['vscale']"):
            # int8 dequant scales: ring (B, L, K) and paged (N, ps, K)
            # share kv_scale_spec — MUST precede the ['attn'] fallback
            # (which assumes the rank-4 K/V layout)
            return kv_scale_spec(cfg, mesh, lead=nscan)
        if "['attn']" in key or "['xattn']" in key:
            if key.endswith("['valid']") or key.endswith("['pos']"):
                return P(*lead, ba, None)
            return attn_kv_spec(cfg, mesh, lead=nscan)
        if key.endswith("['state']") and leaf.ndim - nscan == 4:   # ssm
            return P(*lead, ba, "model", None, None)
        if key.endswith("['state']"):                               # rglru
            return P(*lead, ba, "model")
        if key.endswith("['conv']"):
            return P(*lead, ba, None, None)
        if key.endswith("['bx']"):                  # lfm2 short-conv rows
            return P(*lead, ba, None, "model")
        return P(*lead, ba)

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shapes)
    return jax.tree_util.tree_unflatten(
        treedef, [_fit_spec(spec(p, l), l.shape, mesh, relocate=True)
                  for p, l in flat])


def cache_shardings(cache_shapes, cfg, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        cache_specs_tree(cache_shapes, cfg, mesh))


def constrain_kv_cache(x, cfg):
    """Pin a (B, L, K, Dh) ring-cache tensor to the serving cache rules
    (kv-heads over `model` when divisible, else head_dim; batch over the
    data axes) under the active mesh. Applied at the two cache WRITE sites
    — `prefill_into_slot`'s row splice and `attn_decode`'s per-row scatter
    — where GSPMD would otherwise replicate the batch-indexed update to the
    full global cache. No-op outside a mesh context."""
    m = active_mesh()
    if m is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, _fit_spec(attn_kv_spec(cfg, m), x.shape, m, relocate=True))


def constrain_kv_mask(x, cfg):
    """Pin a (B, L) ring-cache mask leaf (``valid`` / ``pos``) at its
    decode WRITE sites — the per-layer KV-validity mask the elastic depth
    router drives: a (slot, layer) the router skips writes no KV there, so
    ``valid`` stays False and attention masks the lane branch-free. The
    write is the same batch-indexed scatter as the K/V one, so GSPMD would
    otherwise replicate the mask to the full global batch every decode
    step. Shares ``cache_specs_tree``'s P(batch_axes, None) placement.
    No-op outside a mesh context."""
    m = active_mesh()
    if m is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, _fit_spec(P(batch_axes(m), *([None] * (x.ndim - 1))),
                     x.shape, m))


def constrain_cache_tree(caches, cfg):
    """with_sharding_constraint every leaf of a serving cache pytree to its
    `cache_specs_tree` spec under the active mesh (no-op outside one) — the
    row-splice twin of `constrain_kv_cache`, covering all cache kinds
    (attn/xattn k/v rings, ssm/rglru state, valid/pos)."""
    m = active_mesh()
    if m is None:
        return caches
    specs = cache_specs_tree(caches, cfg, m)
    return jax.tree.map(jax.lax.with_sharding_constraint, caches, specs)
