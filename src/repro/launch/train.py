"""Distributed ElastiFormer self-distillation training driver.

Wires together: config registry -> mesh -> sharded frozen base model ->
router init -> distillation train step -> fault-tolerant supervised loop
(checkpoint/restart, straggler watchdog) -> deterministic sharded data.

On the CPU it is exercised end-to-end with smoke configs
(tests/test_train_loop.py, examples/train_elastic_lm.py); ``--mesh D,M``
runs it on a (data, model) mesh of the devices present.
"""
from __future__ import annotations

import argparse
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import Checkpointer
from repro.configs import get_config, get_elastic
from repro.core.policy import (as_spec_policy, capacity_anneal, ragged_bucket,
                               solve_budget)
from repro.data import LMDataPipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import _mesh_shape
from repro.models import model_init, router_init, router_param_count
from repro.optim import cosine_schedule
from repro.runtime import (FailureInjector, StragglerWatchdog, make_mesh,
                           run_resilient)
from repro.runtime import sharding as SH
from repro.training import TrainState, init_train_state, make_train_step

log = logging.getLogger("repro.train")


def build_trainer(arch: str, *, variant: str = "full", mesh=None,
                  lr: float = 1e-4, total_steps: int = 1000,
                  seq_len: int = 512, global_batch: int = 32,
                  remat: bool = True, compression: bool = False,
                  seed: int = 0, ecfg=None):
    cfg = get_config(arch, variant)
    ecfg = ecfg or get_elastic(arch, cfg)
    key = jax.random.PRNGKey(seed)
    params = model_init(key, cfg, ecfg)
    rp = router_init(jax.random.fold_in(key, 1), cfg, ecfg)
    log.info("base params: %.3fM frozen; router params: %d (%.5f%%)",
             sum(x.size for x in jax.tree.leaves(params)) / 1e6,
             router_param_count(rp),
             100 * router_param_count(rp)
             / max(1, sum(x.size for x in jax.tree.leaves(params))))
    if mesh is not None:
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, s), params,
            SH.param_shardings(params, mesh))
    state = init_train_state(rp, use_compression=compression)
    step_fn = jax.jit(make_train_step(
        cfg, ecfg, lr=cosine_schedule(lr, total_steps), mesh=mesh,
        remat=remat, chunked=cfg.vocab_size > 0,
        compress_axis="pod" if (compression and mesh is not None
                                and "pod" in mesh.axis_names) else None),
        donate_argnums=(0,), static_argnames=("bucket",))
    pipe = LMDataPipeline(vocab=cfg.vocab_size, seq_len=seq_len,
                          global_batch=global_batch, seed=seed)
    return cfg, ecfg, params, state, step_fn, pipe


def train(arch: str, *, variant: str = "smoke", total_steps: int = 100,
          seq_len: int = 128, global_batch: int = 8, lr: float = 1e-3,
          ckpt_dir: str = "/tmp/repro_ckpt", save_every: int = 25,
          mesh_shape: tuple = None,
          inject_failures: tuple = (), seed: int = 0,
          budget: float = None, anneal_from: float = None,
          anneal_steps: int = None):
    """``budget``: target compute budget; capacities come from the roofline
    budget solver instead of the config defaults. ``anneal_from``: start the
    distillation near that budget and anneal linearly to ``budget`` over
    ``anneal_steps`` (default: all steps). The policy is a *traced* argument
    of the jitted train step, so the whole schedule runs on ONE compile."""
    mesh = (make_mesh(mesh_shape, ("data", "model"))
            if mesh_shape is not None else None)
    cfg, ecfg, params, state, step_fn, pipe = build_trainer(
        arch, variant=variant, mesh=mesh, lr=lr, total_steps=total_steps,
        seq_len=seq_len, global_batch=global_batch, seed=seed)
    ckpt = Checkpointer(ckpt_dir, keep=3)
    box = {"state": state, "metrics": {}}

    policy_at = None
    if budget is None and (anneal_from is not None
                           or anneal_steps is not None):
        raise ValueError("--anneal-from/--anneal-steps require --budget "
                         "(the anneal target)")
    if budget is not None:
        spec, _ = as_spec_policy(ecfg)
        sched = capacity_anneal(
            anneal_from if anneal_from is not None else budget, budget,
            anneal_steps if anneal_steps is not None else total_steps)
        cache = {}

        def policy_at(step: int):
            b = round(sched(step), 4)
            if b not in cache:   # solver output as traced jnp leaves
                # ragged: the STATIC capacity bucket rides beside the traced
                # policy — the whole anneal schedule costs one compile per
                # bucket (<= routing.RAGGED_N_BUCKETS), each doing work
                # proportional to its bucket instead of full dense shapes;
                # a full-budget start resolves the IDENTITY sentinel bucket,
                # so the anneal's teacher-speed steps skip routing work
                # while the routers keep their BCE/load gradients
                pol = solve_budget(cfg, spec, b)
                bkt = (ragged_bucket(pol, seq_len, spec=spec)
                       if spec.routing_impl == "ragged" else None)
                cache[b] = (pol, bkt)
            return cache[b]

    def do_step(step: int) -> dict:
        batch = {"tokens": jnp.asarray(pipe.batch_at(step))}
        if policy_at is None:
            box["state"], m = step_fn(box["state"], params, batch)
        else:
            pol, bkt = policy_at(step)
            box["state"], m = step_fn(box["state"], params, batch, pol,
                                      bucket=bkt)
        box["metrics"] = {k: float(v) for k, v in m.items()}
        if step % 10 == 0:
            log.info("step %d %s", step, box["metrics"])
        return box["metrics"]

    def save(step: int):
        ckpt.save(step, {"router": box["state"].router_params,
                         "opt_m": box["state"].opt.m,
                         "opt_v": box["state"].opt.v},
                  extra={"step": step, "data": pipe.state(),
                         "opt_step": int(box["state"].opt.step)})

    def restore() -> int:
        latest = ckpt.latest_step()
        if latest is None:
            box["state"] = init_train_state(state.router_params)
            return 0
        tree = {"router": box["state"].router_params,
                "opt_m": box["state"].opt.m, "opt_v": box["state"].opt.v}
        loaded, extra = ckpt.restore(latest, tree)
        opt = box["state"].opt._replace(
            step=jnp.asarray(extra["opt_step"], jnp.int32),
            m=loaded["opt_m"], v=loaded["opt_v"])
        box["state"] = TrainState(loaded["router"], opt, box["state"].ef)
        pipe.restore(extra["data"])
        return extra["step"]

    watchdog = StragglerWatchdog()
    metrics, restarts = run_resilient(
        start_step=restore(), total_steps=total_steps, do_step=do_step,
        save=save, restore=restore, save_every=save_every,
        injector=FailureInjector(inject_failures), watchdog=watchdog)
    ckpt.wait()
    return box["state"], metrics, restarts, watchdog


def main():
    logging.basicConfig(level=logging.INFO)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="toy-lm")
    ap.add_argument("--variant", default="smoke")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default="/tmp/repro_ckpt")
    ap.add_argument("--mesh", type=_mesh_shape, default=None,
                    help="train on a 'data,model' mesh of the devices "
                         "present (e.g. 1,4)")
    ap.add_argument("--budget", type=float, default=None,
                    help="target compute budget in (0,1]; capacities from "
                         "the roofline budget solver")
    ap.add_argument("--anneal-from", type=float, default=None,
                    help="start budget of the linear capacity anneal "
                         "(traced policy: the schedule re-uses one compile)")
    ap.add_argument("--anneal-steps", type=int, default=None)
    args = ap.parse_args()
    enable_compile_cache()
    _, metrics, restarts, _ = train(
        args.arch, variant=args.variant, total_steps=args.steps,
        seq_len=args.seq_len, global_batch=args.batch, lr=args.lr,
        ckpt_dir=args.ckpt, mesh_shape=args.mesh,
        budget=args.budget, anneal_from=args.anneal_from,
        anneal_steps=args.anneal_steps)
    print("final:", metrics, "restarts:", restarts)


if __name__ == "__main__":
    main()
