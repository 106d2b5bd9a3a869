#!/usr/bin/env python3
"""Chip smoke test: serve qwen2-7b at its published widths on a TPU.

Drives the serving main path — ``ServingEngine.submit()``/``step()`` with
its ``SlotScheduler``, as ``repro.launch.serve`` does — on random weights
from a seed, and checks what comes out:

    python3 chip_smoke.py              # one chip: phase A (ring), B (paged)
    python3 chip_smoke.py --chips 4    # TP serving on a (data=1, model=4)
                                       # mesh vs the one-device path

Phase A serves the ring KV layout under the config's ElastiFormer routers
(token, head and moefied-expert) at budgets 1.0 and 0.5; phase B serves the
block-paged layout with an int8 KV cache and a dense MLP, the setting
``launch/serve.py --kv-layout paged`` uses. Each phase compiles its serving
programs, lists the Pallas kernels found in them by the name of their
``tpu_custom_call``, fails if a kernel its config routes through is
missing, serves every request to its length, and compares the kernel
path's prefill logits and the logits of one teacher-forced decode step
through the phase's cache layout with a reference path computed with the
same weights on the chip (on four chips: the sharded path with the
one-device path).

What is cut: depth only. qwen2-7b has 28 layers, about 15.2 GB of bf16
weights — more than a 16 GB v5e chip holds beside a KV cache. ``LAYERS``
(8, about 5.9 GB with the embedding and LM head) keeps every width as
published: d_model 3584, 28 query and 4 KV heads of 128, d_ff 18944,
vocab 152064. The layers left out would sit on further chips, as pipeline
stages of the same deployment. Weights and prompts are made from ``SEED``.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failure exits non-zero before it is printed; without a TPU the script
exits non-zero before it loads the model.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.metadata
import json
import re
import sys
import time
from contextlib import nullcontext
from pathlib import Path

ARCH = "qwen2-7b"
LAYERS = 8
SEED = 0
# Relative Frobenius error allowed between two ways of computing the same
# logits in the configuration's dtype (bf16 operands, f32 accumulation).
# They round intermediates at different points — the kernels keep the
# attention probabilities and the MLP's gate and up projections in f32
# and round only the MLP hidden, as the down projection's operand, where
# the jnp path rounds each of these to bf16 (2^-8 relative per rounding);
# int8 KV bytes quantized from the two paths' K/V may land one step
# apart; sharding reorders reductions — so
# each sits about 1.2% from a float32 reference after 8 layers, and two
# such paths differ by about 1.4% (CPU calibration at d_model 512, 8
# layers). A kernel that reads the wrong rows, heads or experts, or
# contracts block padding, is off by O(1); a path computing in 8-bit
# floats (2^-4 relative per rounding) is off by about 20%.
REL_TOL = 5e-2

KERNEL_RE = re.compile(
    r"%(\w+?)(?:\.\d+)? = (\S+) [^\n]*custom_call_target=\"tpu_custom_call\"")


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def pallas_kernels(hlo_text: str) -> dict:
    """{kernel name: output shape} of every Pallas call in compiled HLO
    (a ``pallas_call``'s ``name`` becomes its custom call's name)."""
    return {name: shape for name, shape in KERNEL_RE.findall(hlo_text)}


def compile_programs(engine, plens):
    """AOT-compile the engine's admit program (per prompt length when the
    layout compiles per length) and its decode program, exactly as a live
    call would — under the engine's mesh, if it has one — so the
    persistent cache then serves the engine's own dispatch. Returns
    ({program: {kernel: shape}}, seconds)."""
    t0 = time.perf_counter()
    found = {}
    with engine.mesh if engine.mesh is not None else nullcontext():
        for plen in plens:
            for kind, ep in engine.entry_points(plen=plen).items():
                key = f"admit[{plen}]" if kind == "admit" else kind
                if key in found:
                    continue
                compiled = ep.fn.lower(*ep.args, **ep.static).compile()
                found[key] = pallas_kernels(compiled.as_text())
    return found, time.perf_counter() - t0


def require_kernels(found: dict, admit: set, decode: set) -> None:
    for prog, kernels in found.items():
        log(f"  pallas kernels in {prog}: {sorted(kernels)}")
        want = decode if prog == "decode" else admit
        missing = want - set(kernels)
        if missing:
            raise SystemExit(f"{prog} program lacks kernels {sorted(missing)}"
                             f" (found {sorted(kernels)})")


def serve(engine, reqs) -> int:
    """Submit every request, step the engine until it drains; every
    request must finish at its full length. Returns tokens served."""
    handles = [engine.submit(r) for r in reqs]
    t0, steps = time.perf_counter(), 0
    while engine.has_work:
        if engine.step() == 0:
            raise SystemExit("serving engine stalled")
        steps += 1
    bad = [h for h, r in zip(handles, reqs) if h.status != "done"
           or len(h.output) != r.max_new_tokens]
    if bad:
        raise SystemExit(f"requests not served in full: {bad}")
    n = sum(len(h.output) for h in handles)
    log(f"  served {len(handles)} requests, {n} tokens in {steps} steps "
        f"({time.perf_counter() - t0:.1f} s wall, compiles included); "
        f"compile_counts {engine.compile_counts()}")
    return n


def compare(name: str, got, want) -> None:
    import numpy as np
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        raise SystemExit(f"{name}: non-finite logits")
    err = float(np.max(np.abs(got - want)))
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    log(f"  {name}: max abs err {err:.4g} (max |logit| "
        f"{float(np.max(np.abs(want))):.4g}), relative error {rel:.4g} "
        f"(tolerance {REL_TOL})")
    if not rel <= REL_TOL:
        raise SystemExit(f"{name}: relative error {rel:.4g} > {REL_TOL}")


def requests(cfg, lens, max_new, budgets, seed=SEED):
    import numpy as np
    from repro.training import GenRequest
    rng = np.random.default_rng(seed)
    return [GenRequest(rng.integers(0, cfg.vocab_size, n, dtype=np.int32),
                       max_new, budget=budgets[i % len(budgets)], seed=i)
            for i, n in enumerate(lens)]


def init_params(cfg, ecfg):
    """Random weights from SEED, made on the device in one program."""
    import jax
    from repro.models import model_init, router_init
    key = jax.random.PRNGKey(SEED)
    params = jax.jit(lambda k: model_init(k, cfg, ecfg))(key)
    rp = jax.jit(lambda k: router_init(k, cfg, ecfg))(
        jax.random.fold_in(key, 1))
    n = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    log(f"  weights: {n / 1e9:.2f} GB ({cfg.n_layers} of 28 layers)")
    return params, rp


def full_budget_policy(cfg, spec):
    """The serving engine's budget-1.0 policy row, as traced f32 leaves."""
    import jax
    import jax.numpy as jnp
    from repro.core.policy import solve_budget
    pol = solve_budget(cfg, spec, 1.0, static=True)
    return jax.tree.map(lambda v: jnp.asarray(v, jnp.float32), pol)


def teacher_forced(engine, spec):
    """``run(params, rp, prompt, nxt, policy)`` -> (logits at the prompt's
    last token, logits of one decode step on token ``nxt``): one request
    through a fresh cache of the engine's layout and KV dtype, by the
    model calls the engine's admit and decode programs make, without
    sampling. ``prompt`` is a 1-D int32 array, ``nxt`` an int."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import (cache_init, decode_step, paged_cache_init,
                              prefill_chunk_step, prefill_into_slot)
    from repro.runtime.pagedkv import n_pages_for
    cfg, L, kv = engine.cfg, engine.max_seq, engine.kv_dtype

    if engine.kv_layout == "ring":
        @jax.jit
        def ring(p, r, tokens, nxt, pol):
            caches = cache_init(cfg, 1, L, kv)
            l0, caches, _ = prefill_into_slot(
                p, r, {"tokens": tokens}, caches, 0, cfg, spec, mode="infer",
                max_cache_len=L, policy=pol)
            t = jnp.full((1,), tokens.shape[1], jnp.int32)
            return l0, decode_step(p, r, nxt, caches, t, cfg, spec,
                                   mode="infer", policy=pol)[0]

        return lambda p, r, prompt, nxt, pol: ring(
            p, r, jnp.asarray(prompt[None]), jnp.asarray([[nxt]], jnp.int32),
            pol)

    ps = engine.page_size
    chunk = jax.jit(lambda p, r, ck, caches, wp, row, pos0, plen, pol:
                    prefill_chunk_step(p, r, ck, caches, wp, row, pos0, plen,
                                       cfg, spec, mode="infer", policy=pol))
    step = jax.jit(lambda p, r, nxt, caches, t, pol, table, trash:
                   decode_step(p, r, nxt, caches, t, cfg, spec, mode="infer",
                               policy=pol, table=table, trash=trash)[0])

    def paged(p, r, prompt, nxt, pol):
        plen = prompt.size
        n = n_pages_for(plen + 1, ps)          # the prompt and one new token
        caches = paged_cache_init(cfg, n + 1, ps, kv)     # page n: trash
        row = np.full(n_pages_for(L, ps), -1, np.int32)
        row[:n] = np.arange(n)
        for c in range(n_pages_for(plen, ps)):
            ck = np.zeros((1, ps), np.int32)
            seg = prompt[c * ps:(c + 1) * ps]
            ck[0, :seg.size] = seg
            l0, caches = chunk(p, r, jnp.asarray(ck), caches, jnp.int32(c),
                               jnp.asarray(row), jnp.int32(c * ps),
                               jnp.int32(plen), pol)
        l1 = step(p, r, jnp.asarray([[nxt]], jnp.int32), caches,
                  jnp.full((1,), plen, jnp.int32), pol,
                  jnp.asarray(row[None]), jnp.full((1,), n, jnp.int32))
        return l0, l1

    return paged


def kernel_vs_ref_check(name, engine, prompt):
    """Kernel path vs the jnp path (``kernel_backend="ref"``), same weights,
    same chip, budget 1.0 (no router decision can flip on a rounding
    difference): full-sequence prefill logits of one prompt, then one
    teacher-forced decode step through the engine's cache layout and KV
    dtype, which reaches the layout's decode attention kernel."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.ops import resolve_backend
    from repro.models import forward
    cfg, spec = engine.cfg, engine.spec
    pol = full_budget_policy(cfg, spec)
    nxt = int(prompt[0])
    out = {}
    for sp in (spec, dataclasses.replace(spec, kernel_backend="ref")):
        fwd = jax.jit(lambda p, r, b, pl: forward(p, r, b, cfg, sp,
                                                 mode="infer", policy=pl)[0])
        out[sp.kernel_backend] = (
            fwd(engine.params, engine.rp,
                {"tokens": jnp.asarray(prompt[None])}, pol),
            teacher_forced(engine, sp)(engine.params, engine.rp, prompt,
                                       nxt, pol)[1])
    kern, ref = out[spec.kernel_backend], out["ref"]
    what = f"{resolve_backend(spec.kernel_backend)} kernels vs ref"
    compare(f"{name} prefill logits, {what}", kern[0], ref[0])
    kv = cfg.dtype if engine.kv_dtype == "fp32" else engine.kv_dtype
    compare(f"{name} decode logits ({engine.kv_layout} {kv} KV), {what}",
            kern[1], ref[1])


def phase_a(cfg):
    """Ring KV layout, the config's full ElastiFormer routers."""
    from repro.configs import get_elastic
    from repro.training import ServingEngine
    log("phase A: ring KV cache, token/head/moefied-expert routers, "
        "budgets {1.0, 0.5}")
    ecfg = get_elastic(ARCH, cfg)
    params, rp = init_params(cfg, ecfg)
    plens = (96, 224)             # ring compiles one admit per length
    engine = ServingEngine(params, rp, cfg, ecfg, mode="infer",
                           batch_size=4, max_seq=512)
    found, dt = compile_programs(engine, plens)
    log(f"  compiled {sorted(found)} in {dt:.1f} s")
    require_kernels(found, {"flash_attention", "moe_gmm"},
                    {"decode_attention"})
    reqs = requests(cfg, [plens[i % 2] for i in range(6)], 32, (1.0, 0.5))
    n = serve(engine, reqs)
    counts = engine.compile_counts()
    if counts != {"prefill": len(plens), "decode": 1}:
        raise SystemExit(f"budgets recompiled: {counts}")
    kernel_vs_ref_check("phase A", engine, reqs[0].prompt)
    return n


def phase_b(cfg):
    """Block-paged KV layout, int8 KV cache, dense MLP."""
    import numpy as np
    from repro.configs import get_elastic
    from repro.training import ServingEngine
    log("phase B: paged KV cache, int8 KV, dense MLP, mixed prompt lengths")
    ecfg = dataclasses.replace(get_elastic(ARCH, cfg), mlp_n_experts=0,
                               mlp_expert_topk=0)
    params, rp = init_params(cfg, ecfg)
    engine = ServingEngine(params, rp, cfg, ecfg, mode="infer",
                           batch_size=8, max_seq=512, kv_layout="paged",
                           page_size=16, kv_dtype="int8")
    found, dt = compile_programs(engine, (16,))
    log(f"  compiled {sorted(found)} in {dt:.1f} s")
    require_kernels(found, {"fused_mlp"}, {"paged_decode_attention"})
    lens = np.random.default_rng(SEED + 1).integers(8, 400, size=8)
    reqs = requests(cfg, lens.tolist(), 24, (1.0, 0.5), SEED + 1)
    n = serve(engine, reqs)
    if engine.compile_counts() != {"prefill": 1, "decode": 1}:
        raise SystemExit(f"recompiled: {engine.compile_counts()}")
    kernel_vs_ref_check("phase B", engine, reqs[0].prompt)
    return n


def tp4(cfg):
    """TP-sharded serving on a (data=1, model=4) mesh, compared with the
    one-device path in the same process."""
    import jax
    from jax.sharding import NamedSharding
    from repro.configs import get_elastic
    from repro.runtime import make_mesh
    from repro.training import ServingEngine
    log("--chips 4: ring serving, TP over a (data=1, model=4) mesh")
    ecfg = get_elastic(ARCH, cfg)
    params, rp = init_params(cfg, ecfg)              # on device 0
    mesh = make_mesh((1, 4), ("data", "model"))
    engine = ServingEngine(params, rp, cfg, ecfg, mode="infer",
                           batch_size=4, max_seq=512, mesh=mesh)
    plen = 224
    dec = engine.entry_points(plen=plen)["decode"]
    leaves = jax.tree.leaves((dec.args[0], dec.args[3]))  # params, caches
    if not all(isinstance(x.sharding, NamedSharding) for x in leaves):
        raise SystemExit("a parameter or cache leaf is not mesh-placed")
    wq = dec.args[0]["scan"][0]["attn"]["wq"]
    k_cache = dec.args[3]["scan"][0]["attn"]["k"]
    log(f"  all {len(leaves)} param/cache leaves NamedSharding-placed; "
        f"wq {wq.sharding.spec}, k cache {k_cache.sharding.spec}")
    if "model" not in tuple(wq.sharding.spec) or \
            "model" not in tuple(k_cache.sharding.spec):
        raise SystemExit("heads are not sharded over `model`")
    found, dt = compile_programs(engine, (plen,))
    log(f"  compiled {sorted(found)} in {dt:.1f} s")
    require_kernels(found, {"flash_attention", "moe_gmm"},
                    {"decode_attention"})
    # the per-shard kernel sees H/4 heads; the unsharded fallback would
    # see all H on a gathered cache
    shape = found["decode"]["decode_attention"]
    heads = int(shape.split("[")[1].split(",")[1])
    log(f"  decode_attention output per device: {shape} "
        f"({heads} of {cfg.n_heads} heads)")
    if heads != cfg.n_heads // 4:
        raise SystemExit("decode runs the unsharded kernel fallback")
    reqs = requests(cfg, [plen] * 6, 32, (1.0, 0.5))
    n = serve(engine, reqs)

    # logits: prefill, then one decode step through the cache, one device
    # vs the mesh (same weights, same inputs, teacher-forced next token);
    # one jit per placement: the kernel wrappers read the mesh at trace time
    pol = full_budget_policy(cfg, engine.spec)
    prompt, nxt = reqs[0].prompt, int(reqs[0].prompt[0])
    one = teacher_forced(engine, engine.spec)(params, rp, prompt, nxt, pol)
    with mesh:
        tp = teacher_forced(engine, engine.spec)(engine.params, engine.rp,
                                                 prompt, nxt, pol)
    compare("TP (model=4) vs one-device prefill logits", tp[0], one[0])
    compare("TP (model=4) vs one-device decode logits", tp[1], one[1])
    return n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only TP-sharded serving on a 4-chip mesh "
                         "and its comparison with one device")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found "
                 f"{devices[0].platform!r} devices")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, found {len(devices)}")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.configs import get_config
    from repro.kernels.ops import resolve_backend
    from repro.launch.compile_cache import enable_compile_cache

    log(f"jax {jax.__version__}, jaxlib "
        f"{importlib.metadata.version('jaxlib')}, libtpu "
        f"{importlib.metadata.version('libtpu')}; {len(devices)} x "
        f"{devices[0].device_kind}")
    log(f"compile cache: {enable_compile_cache()}")
    log(f"kernel backend: {resolve_backend('auto')}")
    cfg = dataclasses.replace(get_config(ARCH), n_layers=LAYERS)
    t0 = time.perf_counter()
    if args.chips == 4:
        served = tp4(cfg)
    else:
        served = phase_a(cfg)
        jax.clear_caches()         # phase A's programs and weights go
        served += phase_b(cfg)
    peak = max(d.memory_stats().get("peak_bytes_in_use", 0)
               for d in jax.devices()[:args.chips])
    log(f"tokens served {served}; peak_bytes_in_use {peak / 1e9:.2f} GB; "
        f"total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
