"""Compile-only guards: every Pallas kernel family, at qwen2-7b's published
widths, through the TPU compiler for a described (not attached) v5e chip.

Interpret mode cannot see what the chip's compiler refuses — blocks that
break the (8, 128) tiling rule, more scoped VMEM than a kernel may use,
in-kernel relayouts Mosaic has no lowering for. These compiles can, and
they need no chip: the TPU compiler ships with libtpu. Nothing runs, so
they say nothing about results or speed.

The topology is described inside a module fixture, never while a module
is imported: only one process at a time may load the TPU library, and
every test worker imports every test file. All such compiles stay in this
one file, so one worker loads the library.
"""
import importlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config, get_elastic

FA, DA, PA, FM, MG = (importlib.import_module(f"repro.kernels.{m}") for m in (
    "flash_attention", "decode_attention", "paged_decode_attention",
    "fused_mlp", "moe_gmm"))

CFG = get_config("qwen2-7b")
D, H, K, Dh, F = (CFG.d_model, CFG.n_heads, CFG.n_kv_heads, CFG.d_head,
                  CFG.d_ff)
E = get_elastic("qwen2-7b", CFG).mlp_n_experts      # moefied experts
FE = F // E                                          # 1184: not a x128 tile
B, L, T = 8, 1024, 256            # serving slots, ring length, prompt rows
N_PAGES, PS = 513, 16             # page pool, page size
SB, SP, SN = 32, 128, 4097          # paged serving: slots, table, pool
BF, F32, I32, I8, BOOL = (jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8,
                          jnp.bool_)


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compilation
    cache off: a compile for a described chip is written to the cache but
    cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


# name -> (kernel call, operand (shape, dtype) list)
CASES = {
    "flash_attention": (
        lambda q, k, v, ok, n: FA.flash_attention(
            q, k, v, kv_valid=ok, kv_count=n, causal=True),
        [((2, T, H, Dh), BF), ((2, T, K, Dh), BF), ((2, T, K, Dh), BF),
         ((2, T), BOOL), ((2,), I32)]),
    "decode_attention": (
        lambda q, k, v, pos, t, ok: DA.decode_attention(
            q, k, v, pos, t, kv_valid=ok),
        [((B, 1, H, Dh), BF), ((B, L, K, Dh), BF), ((B, L, K, Dh), BF),
         ((B, L), I32), ((B,), I32), ((B, L), BOOL)]),
    "decode_attention_int8": (
        lambda q, k, v, pos, t, ok, ks, vs: DA.decode_attention(
            q, k, v, pos, t, kv_valid=ok, kscale=ks, vscale=vs),
        [((B, 1, H, Dh), BF), ((B, L, K, Dh), I8), ((B, L, K, Dh), I8),
         ((B, L), I32), ((B,), I32), ((B, L), BOOL), ((B, L, K), F32),
         ((B, L, K), F32)]),
    "paged_decode_attention": (
        lambda q, kp, vp, tb, t, pv: PA.paged_decode_attention(
            q, kp, vp, tb, t, pv),
        [((B, 1, H, Dh), BF), ((N_PAGES, PS, K, Dh), BF),
         ((N_PAGES, PS, K, Dh), BF), ((B, L // PS), I32), ((B,), I32),
         ((N_PAGES, PS), BOOL)]),
    "paged_decode_attention_int8": (
        lambda q, kp, vp, tb, t, pv, ks, vs: PA.paged_decode_attention(
            q, kp, vp, tb, t, pv, kscale=ks, vscale=vs),
        [((B, 1, H, Dh), BF), ((N_PAGES, PS, K, Dh), I8),
         ((N_PAGES, PS, K, Dh), I8), ((B, L // PS), I32), ((B,), I32),
         ((N_PAGES, PS), BOOL), ((N_PAGES, PS, K), F32),
         ((N_PAGES, PS, K), F32)]),
    # the paged serving cell's own shapes: 32 slots, 128-entry tables
    # (2048 positions), the 4097-page int8 pool with its f32 scale pools
    "paged_decode_attention_int8_serving": (
        lambda q, kp, vp, tb, t, pv, ks, vs: PA.paged_decode_attention(
            q, kp, vp, tb, t, pv, kscale=ks, vscale=vs),
        [((SB, 1, H, Dh), BF), ((SN, PS, K, Dh), I8), ((SN, PS, K, Dh), I8),
         ((SB, SP), I32), ((SB,), I32), ((SN, PS), BOOL),
         ((SN, PS, K), F32), ((SN, PS, K), F32)]),
    "fused_mlp": (
        lambda x, wi, wo, wg, n: FM.fused_mlp(x, wi, wo, wg, valid_count=n),
        [((2, T, D), BF), ((D, F), BF), ((F, D), BF), ((D, F), BF),
         ((2,), I32)]),
    "fused_mlp_int8": (
        lambda x, wi, wo, wg, si, so, sg: FM.fused_mlp(
            x, wi, wo, wg, wi_scale=si, wo_scale=so, wg_scale=sg),
        [((1, PS, D), BF), ((D, F), I8), ((F, D), I8), ((D, F), I8),
         ((F,), F32), ((D,), F32), ((F,), F32)]),
    "fused_mlp_partial_f": (          # an F that is no multiple of the tile
        lambda x, wi, wo, wg, n: FM.fused_mlp(x, wi, wo, wg, valid_count=n),
        [((2, T, D), BF), ((D, FE), BF), ((FE, D), BF), ((D, FE), BF),
         ((2,), I32)]),
    "moe_gmm": (
        lambda x, wi, wo, wg, w, n: MG.moe_gmm(x, wi, wo, wg, w,
                                               group_counts=n),
        [((1, E, T, D), BF), ((E, D, FE), BF), ((E, FE, D), BF),
         ((E, D, FE), BF), ((1, E, T), F32), ((1, E), I32)]),
    "moe_gmm_int8": (
        lambda x, wi, wo, wg, si, so, sg: MG.moe_gmm(
            x, wi, wo, wg, wi_scale=si, wo_scale=so, wg_scale=sg),
        [((1, E, T, D), BF), ((E, D, FE), I8), ((E, FE, D), I8),
         ((E, D, FE), I8), ((E, FE), F32), ((E, D), F32), ((E, FE), F32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e_at_qwen2_7b_widths(name, one_chip):
    fn, operands = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in operands]
    compiled = jax.jit(fn).lower(*args).compile()   # raises what Mosaic does
    assert "tpu_custom_call" in compiled.as_text(), f"{name}: no kernel"
    assert compiled.memory_analysis().temp_size_in_bytes < 16e9
