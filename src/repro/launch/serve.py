"""Serving driver: load (or init) a model + trained routers, run the elastic
threshold-routed decode over a stream of requests.

Per-request compute budgets ride on the traced ElasticPolicy: one compiled
decode step serves every budget, including mixed budgets inside one batch.

Closed loop (submit everything, drain):
    python -m repro.launch.serve --arch toy-lm --requests 16 --max-new 32
    python -m repro.launch.serve --arch toy-lm --budget 0.25,0.5,1.0

Open loop (continuous batching under Poisson arrivals; reports throughput,
per-request latency, and slot occupancy):
    python -m repro.launch.serve --arch toy-lm --arrival-rate 8 \
        --requests 32 --budget 0.4,0.8,1.0

SPMD serving (`--mesh data,model`): the engine runs across the mesh —
params by the TP name rules, KV caches kv-head-sharded, slots packed
per data replica — and the open-loop report breaks occupancy and latency
out per replica. `--remesh-at N` re-meshes the LIVE engine after the N-th
submission (to `--remesh-to`, or the next `valid_mesh_shapes` entry):
    python -m repro.launch.serve --arch toy-lm --mesh 2,4 \
        --arrival-rate 8 --requests 32 --remesh-at 16 --remesh-to 1,4
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import get_config, get_elastic
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model_init, router_init
from repro.runtime.elastic import make_mesh, valid_mesh_shapes
from repro.training import GenRequest, ServingEngine


def _budget_list(s: str):
    try:
        vals = [float(b) for b in s.split(",")]
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"--budget expects a float or comma list of floats, got {s!r}")
    for v in vals:
        if not 0.0 < v <= 1.0:
            raise argparse.ArgumentTypeError(
                f"budgets must be fractions in (0, 1], got {v}")
    return vals


def _mesh_shape(s: str):
    try:
        d, m = (int(x) for x in s.split(","))
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"expected a 'data,model' int pair, got {s!r}")
    if d < 1 or m < 1:
        raise argparse.ArgumentTypeError(f"mesh axes must be >= 1, got {s!r}")
    return (d, m)


def open_loop(engine, requests, rate: float, seed: int = 0, arrive=None,
              remesh_at=None, remesh_to=None):
    """Submit ``requests`` at Poisson arrival times (``rate`` req/s, or an
    explicit ``arrive`` schedule in seconds) while continuously stepping the
    engine; returns (handles, elapsed_seconds). Each handle's ``t_submit``
    is pinned to its *scheduled* arrival, so ``latency`` measures
    arrival -> last token (queueing included) — the same baseline a
    lockstep discipline is judged by.

    ``remesh_at=N``: after the N-th submission, re-mesh the LIVE engine to
    the ``remesh_to`` (data, model) shape — in-flight requests keep
    decoding the same tokens on the new mesh."""
    if arrive is None:
        rng = np.random.default_rng(seed)
        arrive = np.cumsum(rng.exponential(1.0 / rate, len(requests)))
    handles = [None] * len(requests)
    i, t0 = 0, time.perf_counter()
    remeshed = remesh_at is None
    while i < len(requests) or engine.has_work:
        now = time.perf_counter() - t0
        while i < len(requests) and arrive[i] <= now:
            handles[i] = engine.submit(requests[i])
            handles[i].t_submit = t0 + arrive[i]
            i += 1
        if not remeshed and i >= remesh_at:
            remeshed = True
            tm = time.perf_counter()
            engine.reshard(make_mesh(remesh_to, ("data", "model")))
            print(f"[serve] re-meshed live to (data, model)={remesh_to} "
                  f"after {i} submissions ({time.perf_counter() - tm:.2f}s, "
                  f"{engine.scheduler.active} requests in flight)")
        if engine.step() == 0 and i < len(requests):
            # idle: sleep until the next arrival
            wait = arrive[i] - (time.perf_counter() - t0)
            if wait > 0:
                time.sleep(min(wait, 0.05))
    return handles, time.perf_counter() - t0


def latency_stats(handles) -> dict:
    """Latency columns (milliseconds) over SERVED handles — rejected /
    deadline-expired requests are excluded (their "latency" is time to
    rejection, not service). Columns: end-to-end mean/p50/p95, TTFT
    (arrival -> first token: queue wait + prefill) p50/p95, and
    inter-token latency (decode-step gap) mean/p95 — all sourced from the
    per-token timestamps on ``RequestHandle``."""
    done = [h for h in handles
            if h is not None and h.latency is not None
            and h.status != "rejected"]
    lat = np.asarray([h.latency for h in done], float)
    ttft = np.asarray([h.ttft for h in done if h.ttft is not None], float)
    itl = np.asarray([g for h in done for g in h.inter_token()], float)
    pct = lambda a, q: float(np.percentile(a, q) * 1e3) if a.size else 0.0
    return {
        "mean_ms": float(lat.mean() * 1e3) if lat.size else 0.0,
        "p50_ms": pct(lat, 50),
        "p95_ms": pct(lat, 95),
        "ttft_p50_ms": pct(ttft, 50),
        "ttft_p95_ms": pct(ttft, 95),
        "itl_mean_ms": float(itl.mean() * 1e3) if itl.size else 0.0,
        "itl_p95_ms": pct(itl, 95),
    }


def replica_report(engine, handles) -> str:
    """Per-replica occupancy + mean latency lines for the open-loop report
    (a handle's replica = the data shard its final slot lived on). After a
    live re-mesh the window is "since the re-mesh": the occupancy counters
    restart there (the old replica axis no longer exists), so requests that
    finished before it are excluded rather than re-attributed to replicas
    they never ran on."""
    sched = engine.scheduler
    t0 = engine.remeshed_at
    hs_all = [h for h in handles if h is not None and h.slot is not None
              and (t0 is None or h.t_done is None or h.t_done >= t0)]
    lines = [] if t0 is None else \
        [f"  (per-replica window: since the live re-mesh; "
         f"{len(handles) - len(hs_all)} earlier requests excluded)"]
    for r in range(sched.n_replicas):
        hs = [h for h in hs_all if sched.replica_of(h.slot) == r]
        st = latency_stats(hs)
        lines.append(
            f"  replica {r}: {len(hs)} requests, occupancy "
            f"{sched.replica_occupancy[r]:.0%}, e2e mean {st['mean_ms']:.0f}"
            f" / p50 {st['p50_ms']:.0f} / p95 {st['p95_ms']:.0f} ms, "
            f"ttft p95 {st['ttft_p95_ms']:.0f} ms, "
            f"itl p95 {st['itl_p95_ms']:.1f} ms")
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="toy-lm")
    ap.add_argument("--variant", default="smoke")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--mode", default="infer", choices=["infer", "base"])
    ap.add_argument("--kv-layout", default="ring", choices=["ring", "paged"],
                    help="KV cache layout: 'ring' reserves max_seq per slot; "
                         "'paged' serves from a block-paged pool with prefix "
                         "sharing and chunked prefill (one compile for any "
                         "prompt length)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (paged layout only)")
    ap.add_argument("--n-pages", type=int, default=None,
                    help="total physical KV pages (default: ring-equivalent "
                         "HBM, i.e. batch * pages-per-full-sequence)")
    ap.add_argument("--kv-dtype", default="fp32",
                    choices=["fp32", "bf16", "int8"],
                    help="KV cache storage dtype; int8 stores per-(token,"
                         "head) scales as sibling leaves and dequantizes "
                         "inside the decode kernels (docs/quantization.md)")
    ap.add_argument("--weight-dtype", default="fp32",
                    choices=["fp32", "bf16", "int8"],
                    help="base weight storage dtype; int8 quantizes per "
                         "output channel at engine init")
    ap.add_argument("--budget", default=None, type=_budget_list,
                    help="per-request compute budget(s) in (0,1]: a float, "
                         "or a comma list assigned round-robin (mixed "
                         "budgets batch together on one compiled step)")
    ap.add_argument("--arrival-rate", type=float, default=None,
                    help="open-loop mode: Poisson request arrivals at this "
                         "rate (req/s); reports per-request latency and "
                         "slot occupancy on top of throughput")
    ap.add_argument("--trace", default="poisson",
                    choices=["poisson", "bursty", "diurnal"],
                    help="open-loop arrival process (benchmarks/workloads"
                         ".py): 'bursty' = 4x burst in the middle 40%% of "
                         "requests, 'diurnal' = sinusoidal rate around "
                         "--arrival-rate")
    ap.add_argument("--depth-routed", action="store_true",
                    help="enable the elastic depth router (per-token whole-"
                         "layer skip; docs/elastic_policy.md): budgets below "
                         "1.0 skip full blocks per token, decode skips write "
                         "no KV at that layer (per-layer validity masks)")
    ap.add_argument("--controller", action="store_true",
                    help="enable the SLO feedback controller (graceful "
                         "degradation: admission budgets -> in-flight "
                         "budgets -> load shedding -> remesh escalation; "
                         "docs/serving.md)")
    ap.add_argument("--slo-p95-ms", type=float, default=None,
                    help="p95 TTFT SLO target in ms for the default class "
                         "(implies --controller; default 500)")
    ap.add_argument("--slo-floor", type=float, default=0.25,
                    help="lowest budget the controller may degrade to")
    ap.add_argument("--flop-budget", type=float, default=None,
                    help="per-replica, per-step FLOP admission budget in "
                         "full-budget-row units (default: slots per "
                         "replica, i.e. slot-limited; without --mesh the "
                         "single replica holds all --batch slots)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="sample from the top-k logits (0 = all)")
    ap.add_argument("--eos", type=int, default=None,
                    help="stop token id (default: config eos_id)")
    ap.add_argument("--mesh", type=_mesh_shape, default=None,
                    help="run SPMD on a 'data,model' mesh (e.g. 2,4): TP "
                         "over `model`, the slot array split into `data` "
                         "replicas the scheduler packs independently")
    ap.add_argument("--remesh-at", type=int, default=None,
                    help="after this many submissions, re-mesh the LIVE "
                         "engine (open-loop only; in-flight requests "
                         "resume with identical tokens)")
    ap.add_argument("--remesh-to", type=_mesh_shape, default=None,
                    help="target 'data,model' shape for --remesh-at "
                         "(default: the next valid_mesh_shapes entry)")
    args = ap.parse_args()
    enable_compile_cache()

    mesh = None
    if args.mesh is not None:
        if args.batch % args.mesh[0]:
            ap.error(f"--batch {args.batch} must be a multiple of the mesh "
                     f"data axis {args.mesh[0]}")
        mesh = make_mesh(args.mesh, ("data", "model"))
    if args.remesh_at is not None:
        if args.mesh is None or args.arrival_rate is None:
            ap.error("--remesh-at requires --mesh and --arrival-rate")
        if args.remesh_to is None:
            n_dev = args.mesh[0] * args.mesh[1]
            cands = [s for s in valid_mesh_shapes(n_dev, args.mesh[1])
                     if s != tuple(args.mesh) and args.batch % s[0] == 0]
            if not cands:
                ap.error(f"no alternative mesh shape for {args.mesh} whose "
                         f"data axis divides --batch {args.batch}")
            args.remesh_to = cands[0]
        elif args.batch % args.remesh_to[0]:
            # fail at argparse time, not mid-serve with requests in flight
            ap.error(f"--batch {args.batch} must be a multiple of the "
                     f"--remesh-to data axis {args.remesh_to[0]}")

    cfg = get_config(args.arch, args.variant)
    ecfg = get_elastic(args.arch, cfg)
    if args.kv_layout == "paged" and ecfg is not None \
            and getattr(ecfg, "mlp_n_experts", 0):
        # paged prefill is chunked; moefied expert-capacity buffers depend
        # on the chunking, so the paged engine requires a dense MLP
        print(f"[serve] --kv-layout paged: dropping mlp_n_experts="
              f"{ecfg.mlp_n_experts} (dense MLP required; see docs/paged_kv.md)")
        ecfg = dataclasses.replace(ecfg, mlp_n_experts=0, mlp_expert_topk=0)
    if args.depth_routed and ecfg is not None:
        # depth_capacity=1.0 enables the router (spec.depth_routed) while the
        # default policy stays teacher-exact; budgets/controller lower it live
        ecfg = dataclasses.replace(ecfg, depth_capacity=1.0)
    controller = None
    if args.controller or args.slo_p95_ms is not None:
        from repro.runtime.controller import SLOController, SLOTarget
        slo_ms = args.slo_p95_ms if args.slo_p95_ms is not None else 500.0
        controller = SLOController(
            targets={"default": SLOTarget(p95_ttft_ms=slo_ms)},
            floor=args.slo_floor)
    key = jax.random.PRNGKey(0)
    params = model_init(key, cfg, ecfg)
    rp = router_init(jax.random.fold_in(key, 1), cfg, ecfg)
    engine = ServingEngine(params, rp, cfg, ecfg, mode=args.mode,
                           controller=controller,
                           batch_size=args.batch,
                           max_seq=args.prompt_len + args.max_new,
                           eos_id=args.eos,
                           step_flop_budget=args.flop_budget,
                           mesh=mesh, kv_layout=args.kv_layout,
                           page_size=args.page_size, n_pages=args.n_pages,
                           kv_dtype=args.kv_dtype,
                           weight_dtype=args.weight_dtype)
    budgets = args.budget
    rng = np.random.default_rng(0)
    reqs = [GenRequest(rng.integers(0, cfg.vocab_size, args.prompt_len,
                                    dtype=np.int32), args.max_new,
                       budget=(budgets[i % len(budgets)] if budgets else None),
                       temperature=args.temperature, top_k=args.top_k,
                       seed=i)
            for i in range(args.requests)]

    if args.arrival_rate is not None:
        arrive = None
        if args.trace != "poisson":
            try:
                from benchmarks.workloads import arrival_times
            except ImportError:     # not launched from the repo root
                import pathlib
                import sys
                sys.path.insert(
                    0, str(pathlib.Path(__file__).resolve().parents[3]))
                from benchmarks.workloads import arrival_times
            arrive = arrival_times(args.trace, args.arrival_rate,
                                   len(reqs), seed=0)
        # warm the compile caches outside the timed window
        engine.generate([reqs[0]])
        engine.scheduler.reset_stats()
        handles, dt = open_loop(engine, reqs, args.arrival_rate,
                                arrive=arrive,
                                remesh_at=args.remesh_at,
                                remesh_to=args.remesh_to)
        n_tok = sum(len(h.output) for h in handles)
        st = latency_stats(handles)
        print(f"open loop: {len(reqs)} requests @ {args.arrival_rate} req/s "
              f"({args.trace}), {n_tok} tokens in {dt:.2f}s "
              f"({n_tok / dt:.1f} tok/s)")
        print(f"latency: e2e mean {st['mean_ms']:.0f} / p50 "
              f"{st['p50_ms']:.0f} / p95 {st['p95_ms']:.0f} ms; "
              f"ttft p50 {st['ttft_p50_ms']:.0f} / p95 "
              f"{st['ttft_p95_ms']:.0f} ms; itl mean "
              f"{st['itl_mean_ms']:.1f} / p95 {st['itl_p95_ms']:.1f} ms; "
              f"slot occupancy {engine.occupancy:.0%} "
              f"(budgets={budgets or 'config-default'})")
        if controller is not None:
            cs = controller.summary()
            served = sum(h.status == "done" for h in handles)
            print(f"controller: admission {cs['admission_budget']:.2f}, "
                  f"depth {cs['depth_budget']:.2f}, "
                  f"inflight {cs['inflight_budget']:.2f} after "
                  f"{cs['evals']} evals; events {cs['events'] or '{}'}; "
                  f"served {served}, shed {engine.n_rejected}, expired "
                  f"{engine.n_expired} (slo p95 ttft "
                  f"{controller.target_for('default').p95_ttft_ms:.0f} ms)")
        if engine.scheduler.n_replicas > 1 or mesh is not None:
            print(replica_report(engine, handles))
    else:
        t0 = time.perf_counter()
        outs = engine.generate(reqs)
        dt = time.perf_counter() - t0
        n_tok = sum(len(o) for o in outs)
        print(f"served {len(reqs)} requests, {n_tok} tokens in {dt:.2f}s "
              f"({n_tok / dt:.1f} tok/s, mode={args.mode}, "
              f"budgets={budgets or 'config-default'})")
        print("sample output:", outs[0][:16])
    print(f"compiles: {engine.compile_counts()} (budgets, slots, and "
          f"sampling knobs never recompile)")
    if args.kv_layout == "paged":
        st = engine.paged_stats()
        print(f"paged pool: peak {st['peak_allocated']}/{st['usable']} pages "
              f"(page_size={st['page_size']}, "
              f"{st['registered_prefixes']} prefixes registered)")


if __name__ == "__main__":
    main()
