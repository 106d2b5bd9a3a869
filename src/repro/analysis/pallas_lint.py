"""PAL — static verification of every Pallas kernel's launch geometry.

``kernels.analyzable_kernels()`` enumerates one representative call per
kernel; this pass intercepts ``pl.pallas_call`` (recording the grid spec
and concrete operands, returning zeros so the wrapper completes without
compiling anything) and then *statically evaluates* the launch:

* ``PAL-OOB``: every ``BlockSpec.index_map`` is enumerated over the full
  grid (with the real scalar-prefetch operands bound) and each returned
  block index must satisfy ``0 <= bi < cdiv(dim, block)`` — the proof
  that no tile reads or writes outside its operand. (A partial last block
  inside that range is legal; its padding holds garbage on the chip, so
  the kernel must mask it — the kernels' partial-tile tests cover that.)
* ``PAL-ALIGN``: the TPU lowering's tiling rule — a block's last dim
  must be a multiple of 128 and its second-to-last a multiple of 8, each
  unless it covers the whole axis. Mosaic refuses any other block, so a
  kernel that breaks the rule only ever ran in interpret mode.
* ``PAL-PREFETCH``: small integer control vectors (per-slot offsets,
  ragged counts) must ride ``num_scalar_prefetch`` — as blocked operands
  they'd serialize the grid on VMEM loads the indexing depends on; and
  prefetch operands must actually be small integer arrays.
"""
from __future__ import annotations

import contextlib
import itertools
import math
from typing import List

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.analysis.framework import Finding

PASS_NAME = "pallas"

_MAX_GRID_POINTS = 65536


@contextlib.contextmanager
def record_pallas_calls():
    """Swap ``pl.pallas_call`` for a recorder: each launch appends
    ``{"kwargs": ..., "args": ...}`` and yields zeros of ``out_shape``."""
    records = []
    orig = pl.pallas_call

    def fake(kernel, **kw):
        def runner(*call_args):
            records.append({"kwargs": kw, "args": call_args})
            return jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), kw.get("out_shape"),
                is_leaf=lambda x: hasattr(x, "shape") and hasattr(x, "dtype"))
        return runner

    pl.pallas_call = fake
    try:
        yield records
    finally:
        pl.pallas_call = orig


def _launch_geometry(rec):
    """-> (grid, nsp, prefetch_args, [(kind, spec, shape), ...])."""
    kw, args = rec["kwargs"], rec["args"]
    gs = kw.get("grid_spec")
    if gs is not None:
        nsp = int(getattr(gs, "num_scalar_prefetch", 0) or 0)
        grid, in_specs, out_specs = gs.grid, list(gs.in_specs), gs.out_specs
    else:
        nsp = 0
        grid = kw.get("grid") or ()
        in_specs = list(kw.get("in_specs") or [])
        out_specs = kw.get("out_specs")
    grid = (grid,) if isinstance(grid, int) else tuple(grid)
    prefetch = tuple(np.asarray(a) for a in args[:nsp])
    operands = list(args[nsp:])
    triples = [("in", s, tuple(np.shape(o)))
               for s, o in zip(in_specs, operands)]
    outs = jax.tree.leaves(
        kw.get("out_shape"),
        is_leaf=lambda x: hasattr(x, "shape") and hasattr(x, "dtype"))
    out_specs = out_specs if isinstance(out_specs, (list, tuple)) \
        else [out_specs] * len(outs)
    triples += [("out", s, tuple(o.shape))
                for s, o in zip(out_specs, outs) if s is not None]
    return grid, nsp, prefetch, triples, operands


def verify_record(name: str, rec) -> List[Finding]:
    """All three gates over one recorded launch (exposed so tests can feed
    synthetic bad launches)."""
    finds = []
    grid, nsp, prefetch, triples, operands = _launch_geometry(rec)
    tgt = f"kernels.{name}"

    # ---- PAL-PREFETCH ----
    for i, p in enumerate(prefetch):
        if not np.issubdtype(p.dtype, np.integer) or p.ndim > 2:
            finds.append(Finding(
                "PAL-PREFETCH", tgt,
                f"scalar-prefetch operand {i} is {p.dtype}{list(p.shape)} — "
                "prefetch lane is for small integer control arrays"))
    for i, o in enumerate(operands):
        if hasattr(o, "dtype") and np.issubdtype(o.dtype, np.integer) \
                and getattr(o, "ndim", 99) <= 1:
            finds.append(Finding(
                "PAL-PREFETCH", tgt,
                f"integer control vector operand {nsp + i} "
                f"({o.dtype}{list(o.shape)}) is a blocked input — "
                "move it to num_scalar_prefetch so index maps can use it"))

    # ---- PAL-ALIGN ----
    for kind, spec, shape in triples:
        bs = tuple(getattr(spec, "block_shape", None) or ())
        if not bs or len(bs) != len(shape):
            continue
        concrete = [d if b is None else b for b, d in zip(bs, shape)]
        last, ldim = concrete[-1], shape[-1]
        if last % 128 != 0 and last != ldim:
            finds.append(Finding(
                "PAL-ALIGN", tgt,
                f"{kind}_spec block {concrete} on {list(shape)}: last dim "
                f"{last} is neither lane-aligned (x128) nor the full axis"))
        if len(concrete) >= 2:
            sub, sdim = concrete[-2], shape[-2]
            if sub % 8 != 0 and sub != sdim:
                finds.append(Finding(
                    "PAL-ALIGN", tgt,
                    f"{kind}_spec block {concrete} on {list(shape)}: "
                    f"sublane dim {sub} is not a multiple of 8"))

    # ---- PAL-OOB ----
    n_points = math.prod(grid) if grid else 0
    if n_points and n_points <= _MAX_GRID_POINTS:
        ranges = [range(g) for g in grid]
        for kind, spec, shape in triples:
            imap = getattr(spec, "index_map", None)
            bs = tuple(getattr(spec, "block_shape", None) or ())
            if imap is None or len(bs) != len(shape):
                continue
            limits = [math.ceil(d / (b or d)) for b, d in zip(bs, shape)]
            bad = None
            for idx in itertools.product(*ranges):
                try:
                    bi = imap(*idx, *prefetch)
                except Exception as e:              # map itself blew up
                    bad = (idx, f"index_map raised {type(e).__name__}: {e}")
                    break
                bi = tuple(int(x) for x in (bi if isinstance(bi, tuple)
                                            else (bi,)))
                if len(bi) != len(limits) or any(
                        not 0 <= b < lim for b, lim in zip(bi, limits)):
                    bad = (idx, f"block index {bi} outside "
                                f"{[f'[0,{l})' for l in limits]}")
                    break
            if bad:
                finds.append(Finding(
                    "PAL-OOB", tgt,
                    f"{kind}_spec block {list(bs)} on {list(shape)} at grid "
                    f"point {bad[0]}: {bad[1]}"))
    elif n_points:
        finds.append(Finding(
            "PAL-OOB", tgt,
            f"grid has {n_points} points (> {_MAX_GRID_POINTS}); in-bounds "
            "enumeration skipped — shrink the analysis example",
            severity="warning"))
    return finds


def run(bundle=None) -> List[Finding]:
    """bundle is unused (kernel launches are self-contained) but accepted
    so the pass registry has one signature."""
    from repro.kernels import analyzable_kernels
    finds: List[Finding] = []
    for name, builder in analyzable_kernels().items():
        fn, args, kwargs = builder()
        with record_pallas_calls() as records:
            try:
                fn(*args, **kwargs)
            except Exception as e:
                finds.append(Finding(
                    "PAL-OOB", f"kernels.{name}",
                    f"analysis example failed under the recorder: "
                    f"{type(e).__name__}: {e}"))
                continue
        if not records:
            finds.append(Finding(
                "PAL-OOB", f"kernels.{name}",
                "analysis example never reached pl.pallas_call"))
        for rec in records:
            finds += verify_record(name, rec)
    return finds
