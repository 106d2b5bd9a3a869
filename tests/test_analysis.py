"""repro.analysis: each rule fires on a golden *violating* fixture, stays
silent on the fixed twin, and the real repo graphs lint clean end-to-end."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.analysis import (Finding, Report, Waiver, build_bundle, donation,
                            dtype_lint, host_sync, pallas_lint, retrace,
                            run_all, sharding_lint)
from repro.analysis.graphs import GraphBundle
from repro.training.serve import EntryPoint


def _mini(entries: dict) -> GraphBundle:
    """A bundle whose entry points are injected test fixtures."""
    return GraphBundle(None, None, None, None, None, _entries=dict(entries))


def _rules(finds):
    return {f.rule for f in finds}


# ------------------------------ retrace --------------------------------------

def test_retrace_flags_value_baked_static_scalar():
    bad = EntryPoint(jax.jit(lambda c, x: x * c, static_argnums=0),
                     (2, jnp.ones((4,), jnp.float32)), {})
    b = _mini({"bad": bad})
    assert _rules(retrace._value_dep(b, "bad")) == {"RETRACE-VALUE-DEP"}

    ok = EntryPoint(jax.jit(lambda c, x: x * c),
                    (jnp.float32(2), jnp.ones((4,), jnp.float32)), {})
    assert retrace._value_dep(_mini({"ok": ok}), "ok") == []


def test_retrace_arg_hygiene_rules():
    ep = EntryPoint(None, (jnp.asarray(0.5),        # weak-typed leaf
                           3,                        # raw Python scalar
                           jnp.int32(1)), {"bucket": [1, 2]})  # unhashable
    rules = _rules(retrace._lint_args("x", ep))
    assert rules == {"RETRACE-WEAK-TYPE", "RETRACE-PY-SCALAR",
                     "RETRACE-STATIC-UNHASHABLE"}
    clean = EntryPoint(None, (jnp.float32(0.5), jnp.int32(3)), {"bucket": 2})
    assert retrace._lint_args("x", clean) == []


# ------------------------------ sharding -------------------------------------

def _scatter_fixture(pin: bool):
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))

    def write(cache, rows, new):
        out = cache.at[jnp.arange(2)[:, None], rows].set(new)
        if pin:
            out = jax.lax.with_sharding_constraint(
                out, NamedSharding(mesh, P()))
        return out

    return EntryPoint(write, (jnp.zeros((2, 16, 8), jnp.float32),
                              jnp.zeros((2, 3), jnp.int32),
                              jnp.ones((2, 3, 8), jnp.float32)), {})


def test_sharding_flags_unpinned_cache_scatter():
    finds = sharding_lint._cache_writes(
        _mini({"w": _scatter_fixture(pin=False)}), "w")
    assert _rules(finds) == {"SHARD-CACHE-WRITE"}
    assert sharding_lint._cache_writes(
        _mini({"w": _scatter_fixture(pin=True)}), "w") == []


def _paged_write_fixture(pin: bool):
    """A paged-pool append: per-slot scatter of one (K, Dh) row into the
    (n_pages, page_size, K, Dh) float pool at a dynamic (page, offset).
    The bool pvalid occupancy write rides along and — since the depth
    router made it a per-step scatter target — needs its own pin (the
    rank-2 branch of constrain_page_pool). Only the int32 page-TABLE
    update stays below SHARD-CACHE-WRITE's radar (integer bookkeeping;
    replication is cheap, pinning would add collectives)."""
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))

    def append(pool, pvalid, table, pages, offs, new, ent):
        out = pool.at[pages, offs].set(new)
        pv = pvalid.at[pages, offs].set(True)
        if pin:
            out = jax.lax.with_sharding_constraint(
                out, NamedSharding(mesh, P(("data",), None, "model", None)))
            pv = jax.lax.with_sharding_constraint(
                pv, NamedSharding(mesh, P(("data",), None)))
        tb = table.at[jnp.arange(2), 1].set(ent)   # int32 table: exempt
        return out, pv, tb

    return EntryPoint(append, (jnp.zeros((16, 8, 4, 32), jnp.float32),
                               jnp.zeros((16, 8), bool),
                               jnp.full((2, 4), -1, jnp.int32),
                               jnp.zeros((2,), jnp.int32),
                               jnp.zeros((2,), jnp.int32),
                               jnp.ones((2, 4, 32), jnp.float32),
                               jnp.zeros((2,), jnp.int32)), {})


def test_sharding_flags_unpinned_page_pool_write():
    """The paged-KV append pattern: the FLOAT pool scatter and the bool
    pvalid occupancy scatter must both be pinned (two findings when they
    are not); the int32 page-table scatter never fires regardless."""
    finds = sharding_lint._cache_writes(
        _mini({"w": _paged_write_fixture(pin=False)}), "w")
    assert _rules(finds) == {"SHARD-CACHE-WRITE"}
    assert len(finds) == 2               # pool + pvalid; table stays silent
    assert sharding_lint._cache_writes(
        _mini({"w": _paged_write_fixture(pin=True)}), "w") == []


def _mask_scatter_fixture(pin: bool):
    """The ring KV-validity mask write the depth router performs each
    decode step: a batch-indexed scatter of per-slot bits into the
    long-lived (B, S) bool `valid` ring. Unpinned, GSPMD replicates the
    whole bitmap per step — constrain_kv_mask exists to prevent exactly
    this. The int32 `pos` ring update rides along (rank-1: exempt)."""
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))

    def write(valid, pos, bits):
        bi = jnp.arange(2)
        out = valid.at[bi, pos].set(bits)
        if pin:
            out = jax.lax.with_sharding_constraint(
                out, NamedSharding(mesh, P(("data",), None)))
        np_ = pos.at[bi].set(pos + 1)              # int32 rank-1: exempt
        return out, np_

    return EntryPoint(write, (jnp.zeros((2, 16), bool),
                              jnp.zeros((2,), jnp.int32),
                              jnp.ones((2,), bool)), {})


def test_sharding_flags_unpinned_mask_scatter():
    """Golden fixture for the depth router's mask-leaf write sites: an
    unpinned batch-indexed scatter into the (B, S) bool validity ring is
    flagged; the constrain_kv_mask-style pinned twin is silent, and the
    pos bookkeeping write never fires."""
    finds = sharding_lint._cache_writes(
        _mini({"w": _mask_scatter_fixture(pin=False)}), "w")
    assert _rules(finds) == {"SHARD-CACHE-WRITE"}
    assert len(finds) == 1               # pos stays silent
    assert sharding_lint._cache_writes(
        _mini({"w": _mask_scatter_fixture(pin=True)}), "w") == []


# ------------------------------ host sync ------------------------------------

def test_host_sync_flags_callbacks_and_numpy_operands():
    def f(x):
        jax.debug.callback(lambda v: None, x)
        return x + 1

    ep = EntryPoint(f, (jnp.ones((4,)),), {})
    assert _rules(host_sync._callbacks(_mini({"f": ep}), "f")) \
        == {"HOST-CALLBACK"}

    np_ep = EntryPoint(None, (np.zeros((3,), np.float32),), {})
    assert _rules(host_sync._host_operands("g", np_ep)) == {"HOST-OPERAND"}
    dev_ep = EntryPoint(None, (jnp.zeros((3,), jnp.float32),), {})
    assert host_sync._host_operands("g", dev_ep) == []


# ------------------------------ donation -------------------------------------

def test_donation_flags_undonated_buffer():
    args = (jnp.ones((8,), jnp.float32), jnp.ones((8,), jnp.float32))
    # "train" name: GraphBundle.fresh_entry serves it straight from entries
    bad = EntryPoint(jax.jit(lambda a, b: (a + 1.0, b)), args, {},
                     donated=(0,))
    b = _mini({"train": bad})
    assert _rules(donation._static_check(b, "train")) == {"DONATE-MISSING"}
    assert _rules(donation._functional_check(b, "train")) == {"DONATE-DEAD"}

    good = EntryPoint(jax.jit(lambda a, b: (a + 1.0, b), donate_argnums=(0,)),
                      args, {}, donated=(0,))
    g = _mini({"train": good})
    assert donation._static_check(g, "train") == []
    assert donation._functional_check(g, "train") == []


# ------------------------------ dtype ----------------------------------------

def test_dtype_flags_large_bf16_upcast():
    def f(x):
        return x.astype(jnp.float32) + 1.0

    ep = EntryPoint(f, (jnp.zeros((512, 512), jnp.bfloat16),), {})
    assert _rules(dtype_lint._findings_for(_mini({"f": ep}), "f")) \
        == {"DTYPE-UPCAST"}
    # small upcasts (kernel-style scalars/reductions) stay silent
    small = EntryPoint(f, (jnp.zeros((8, 8), jnp.bfloat16),), {})
    assert dtype_lint._findings_for(_mini({"f": small}), "f") == []


def test_dtype_flags_quantized_hbm_dequant():
    """DTYPE-QUANT-HBM: a LARGE int8 -> f32 convert in a serve graph means
    a quantized cache/weight was dequantized OUTSIDE the kernels — HBM sees
    the f32 copy, forfeiting the bandwidth win. Small converts stay silent,
    train is exempt (fp32 masters), and the same convert INSIDE a
    pallas_call body (the fused-dequant pattern) never fires: the walker
    skips kernel sub-jaxprs, which IS the allowlist."""
    def f(q, s):
        return q.astype(jnp.float32) * s

    big = (jnp.zeros((512, 512), jnp.int8), jnp.ones((), jnp.float32))
    ep = EntryPoint(f, big, {})
    assert _rules(dtype_lint._findings_for(_mini({"f": ep}), "f")) \
        == {"DTYPE-QUANT-HBM"}
    assert dtype_lint._findings_for(_mini({"train": ep}), "train") == []
    small = EntryPoint(
        f, (jnp.zeros((8, 8), jnp.int8), jnp.ones((), jnp.float32)), {})
    assert dtype_lint._findings_for(_mini({"f": small}), "f") == []

    import jax.experimental.pallas as pl

    def kern(q_ref, s_ref, o_ref):
        o_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]

    def fused(q, s):
        return pl.pallas_call(kern, out_shape=jax.ShapeDtypeStruct(
            (512, 512), jnp.float32), interpret=True)(q, s)

    inside = EntryPoint(fused, (jnp.zeros((512, 512), jnp.int8),
                                jnp.ones((512, 512), jnp.float32)), {})
    assert dtype_lint._findings_for(_mini({"k": inside}), "k") == []


# ------------------------------ pallas ---------------------------------------

def _rec(grid, block, shape, index_map, args=(), nsp_spec=None):
    import jax.experimental.pallas as pl
    kw = {"grid": grid,
          "in_specs": [pl.BlockSpec(block, index_map)],
          "out_specs": None,
          "out_shape": jax.ShapeDtypeStruct(shape, jnp.float32)}
    return {"kwargs": kw, "args": args or (jnp.zeros(shape, jnp.float32),)}


def test_pallas_flags_out_of_bounds_index_map():
    # grid runs to 4 but a (256,) operand only has cdiv(256,128)=2 blocks
    finds = pallas_lint.verify_record(
        "k", _rec((4,), (128,), (256,), lambda i: (i,)))
    assert "PAL-OOB" in _rules(finds)
    assert pallas_lint.verify_record(
        "k", _rec((2,), (128,), (256,), lambda i: (i,))) == []


def test_pallas_flags_misaligned_tile():
    finds = pallas_lint.verify_record(
        "k", _rec((2,), (100,), (200,), lambda i: (i,)))
    assert "PAL-ALIGN" in _rules(finds)
    # a one-row block of a many-row array is refused by the TPU lowering
    # (it is neither a multiple of 8 nor the full axis) ...
    finds = pallas_lint.verify_record(
        "k", _rec((8,), (1, 128), (8, 128), lambda i: (i, 0)))
    assert "PAL-ALIGN" in _rules(finds)
    # ... while the same row through a unit axis is the full (1, 128) tail
    assert pallas_lint.verify_record(
        "k", _rec((8,), (1, 1, 128), (8, 1, 128), lambda i: (i, 0, 0))) == []


def test_pallas_flags_unprefetched_control_vector():
    finds = pallas_lint.verify_record(
        "k", _rec((2,), (1, 128), (2, 128), lambda i: (i, 0),
                  args=(jnp.zeros((2,), jnp.int32),)))
    assert "PAL-PREFETCH" in _rules(finds)


# ------------------------------ waivers / report -----------------------------

def test_waivers_silence_but_still_report():
    r = Report()
    finds = [Finding("RULE-A", "serve.decode", "boom"),
             Finding("RULE-B", "kernels.moe_gmm", "bang")]
    r.extend("p", finds, [Waiver.parse("RULE-A:serve.*")])
    assert [f.rule for f in r.findings] == ["RULE-B"]
    assert [f.rule for f in r.waived] == ["RULE-A"]
    assert not r.ok
    r2 = Report()
    r2.extend("p", finds, [Waiver("RULE-A"), Waiver("RULE-B")])
    assert r2.ok and len(r2.waived) == 2
    assert "2 waived" in r2.table()


# ------------------------------ the real repo --------------------------------

@pytest.mark.slow
def test_repo_graphs_lint_clean():
    """The shipped serving/training graphs and kernels produce ZERO
    findings — the exact gate the lint-graphs CI job enforces."""
    report = run_all(build_bundle(mesh_shape=(1, 1)))  # the CLI default
    assert report.ok and not report.findings, report.table(verbose=True)
    assert set(report.passes) == {"retrace", "sharding", "host_sync",
                                  "donation", "dtype", "pallas"}
