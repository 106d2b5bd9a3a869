"""Sharding rule table: divisibility fitting, cache specs, input specs.

Uses AbstractMesh so (16, 16) axis sizes are exercised without 256
devices."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config
from repro.runtime.sharding import (_fit_spec, batch_spec,
                                    cache_specs_tree, param_specs)

MESH = AbstractMesh((16, 16), ("data", "model"))
POD_MESH = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_fit_spec_keeps_divisible():
    assert _fit_spec(P("model", None), (256, 64), MESH) == P("model", None)


def test_fit_spec_replicates_indivisible_param_dims():
    # 4 kv heads cannot shard over a 16-wide axis -> replicate (NOT relocate
    # to a contraction dim, which would force partial-sum all-reduces)
    assert _fit_spec(P(None, "model", None), (28, 4, 128), MESH) \
        == P(None, None, None)


def test_fit_spec_relocates_for_caches():
    # caches opt into relocation (HBM capacity over collectives)
    assert _fit_spec(P(None, "model", None), (28, 4, 128), MESH,
                     relocate=True) == P(None, None, "model")


def test_fit_spec_replicates_when_nothing_fits():
    assert _fit_spec(P(("data",), None), (1, 1), MESH) == P(None, None)


def test_fit_spec_tuple_axis():
    # ("pod","data") = 32-way; batch 256 divides, batch 8 does not
    assert _fit_spec(P(("pod", "data"), None), (256, 128), POD_MESH) \
        == P(("pod", "data"), None)
    assert _fit_spec(P(("pod", "data"), None), (8, 64), POD_MESH) \
        == P(None, None)


@pytest.mark.parametrize("arch", ["phi3-medium-14b", "qwen2-7b",
                                  "granite-34b", "recurrentgemma-2b"])
def test_param_specs_divisible_on_production_mesh(arch):
    """Every param sharding must divide its dim (pjit argument contract)."""
    cfg = get_config(arch)
    from repro.models import model_init
    params = jax.eval_shape(
        lambda: model_init(jax.random.PRNGKey(0), cfg, None))
    specs = param_specs(params, MESH)
    flat_p = jax.tree.leaves(params)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_p) == len(flat_s)
    for leaf, spec in zip(flat_p, flat_s):
        for dim, ax in zip(leaf.shape, tuple(spec)):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            size = 1
            for a in axes:
                size *= MESH.shape[a]
            assert dim % size == 0, (leaf.shape, spec)


def test_cache_specs_pos_and_valid_are_rank_matched():
    cfg = get_config("phi3-medium-14b")
    from repro.models import cache_specs
    caches = cache_specs(cfg, 128, 1024)
    specs = cache_specs_tree(caches, cfg, MESH)
    flat_c = jax.tree.leaves(caches)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    for leaf, spec in zip(flat_c, flat_s):
        assert len(tuple(spec)) <= leaf.ndim, (leaf.shape, spec)


def test_batch_spec_uses_all_batch_axes():
    assert batch_spec(POD_MESH, 1) == P(("pod", "data"), None)
    assert batch_spec(MESH, 2) == P(("data",), None, None)
