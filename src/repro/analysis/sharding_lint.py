"""SHARD — mesh-placement invariants of the serving/training graphs.

* ``SHARD-CACHE-WRITE``: a batch-indexed ``dynamic_update_slice`` /
  ``scatter`` into a long-lived buffer (one threaded in through the
  jaxpr's invars) whose result is NOT pinned by a
  ``with_sharding_constraint`` within a few transparent ops. Unpinned,
  GSPMD is free to all-gather the cache around the write — the exact
  regression runtime/sharding.constrain_kv_cache exists to prevent.
  Covered buffers: rank>=3 *floating-point* tensors (the KV caches,
  policy state) and rank-2 *boolean* bitmaps (the per-layer KV-validity
  masks the depth router scatters every decode step — ring ``valid``,
  paged ``pvalid``; pinned by runtime/sharding.constrain_kv_mask and the
  rank-2 branch of constrain_page_pool). Integer bookkeeping scatters
  (pos rings, page tables, the MoE dispatch-index inversion) are
  deliberately below the radar: replicating those is cheap and pinning
  them would add collectives.
* ``SHARD-OUT-PIN``: a donated input that enters the graph sharded but
  whose aliased output compiles to a different sharding — the entry point
  is missing its ``out_shardings`` pin, so every call inserts a reshard
  (and donation degrades to copy-on-alias). Vacuous on a 1x1 mesh; the
  8-fake-device CI variant exercises it for real.
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend import core as jax_core

from repro.analysis.framework import (Finding, constrained_downstream,
                                      derives_from_invar, eqn_site, walk_eqns)

PASS_NAME = "sharding"

_WRITE_PRIMS = ("dynamic_update_slice", "scatter", "scatter-add", "scatter_add")


def _cache_writes(bundle, name: str) -> List[Finding]:
    finds = []
    closed = bundle.jaxpr(name)
    for owner, eqn in walk_eqns(closed):
        if eqn.primitive.name not in _WRITE_PRIMS:
            continue
        operand = eqn.invars[0]
        aval = operand.aval
        is_cache = aval.ndim >= 3 and np.issubdtype(aval.dtype, np.floating)
        # rank-2 bool = KV-validity bitmap (ring valid / paged pvalid): the
        # depth router rewrites it per step, so an unpinned scatter
        # replicates the whole leaf per step. Integer bookkeeping (pos
        # rings, page tables, dispatch-index inversion) stays exempt.
        is_mask = aval.ndim == 2 and aval.dtype == np.bool_
        if not (is_cache or is_mask):
            continue
        if not derives_from_invar(operand, owner):
            continue                     # scratch value, not a live buffer
        idx = eqn.invars[1:] if eqn.primitive.name.startswith("scatter") \
            else eqn.invars[2:]
        if all(isinstance(v, jax_core.Literal) for v in idx):
            continue                     # static write: XLA sees through it
        out = eqn.outvars[0]
        if constrained_downstream(out, owner):
            continue
        finds.append(Finding(
            "SHARD-CACHE-WRITE", f"serve.{name}",
            f"{eqn.primitive.name} into {aval.str_short()} buffer at "
            f"{eqn_site(eqn)} has no with_sharding_constraint pin — GSPMD "
            "may all-gather the cache around the write"))
    return finds


def _equiv(a, b, ndim: int) -> bool:
    try:
        return a.is_equivalent_to(b, ndim)
    except Exception:
        return a == b


def _out_pins(bundle, name: str) -> List[Finding]:
    if bundle.mesh is None or bundle.mesh.size <= 1:
        return []
    ep = bundle.entries()[name]
    if not ep.donated:
        return []
    compiled = bundle.compiled(name)
    try:
        arg_sh = compiled.input_shardings[0]
        out_sh = jax.tree.leaves(compiled.output_shardings)
        out_avals = bundle.jaxpr(name).out_avals
    except Exception:
        return []
    outs = [(a.str_short(short_dtypes=True), a.ndim, s)
            for a, s in zip(out_avals, out_sh)]
    finds = []
    for argnum in ep.donated:
        if argnum >= len(arg_sh):
            continue
        leaves = jax.tree.leaves(ep.args[argnum])
        shardings = jax.tree.leaves(arg_sh[argnum])
        if len(shardings) != len(leaves):
            continue
        for leaf, ish in zip(leaves, shardings):
            aval = jax.core.ShapedArray(jnp.shape(leaf),
                                        jnp.asarray(leaf).dtype)
            key = aval.str_short(short_dtypes=True)
            if any(k == key and nd == aval.ndim and _equiv(ish, osh, nd)
                   for k, nd, osh in outs):
                continue
            finds.append(Finding(
                "SHARD-OUT-PIN", f"serve.{name}",
                f"donated arg {argnum} leaf {key} enters sharded "
                f"{getattr(ish, 'spec', ish)} but no same-aval output "
                "compiles to that sharding — the entry point is missing "
                "an out_shardings pin, so each call pays a reshard "
                "instead of aliasing in place"))
    return finds


def run(bundle) -> List[Finding]:
    if bundle.mesh is None:
        return []     # unsharded graphs place no constraints to lint
    finds: List[Finding] = []
    for name in bundle.entries():
        finds += _cache_writes(bundle, name)
        finds += _out_pins(bundle, name)
    return finds
