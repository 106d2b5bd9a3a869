#!/usr/bin/env python3
"""Serving benchmark: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/workloads/<traffic>.json``). The configuration's ``model_type``
names the directory ``bench/archs/<model_type>/`` that holds what is its
architecture's own: its mapping to the program's configuration, its
weights and its plain reference (``archs/__init__.py``). The run makes the
configuration's weights on the chip from the seed, builds the program's
``ServingEngine`` from the configuration, warms the programs the cell's
traffic uses, then offers the mix open-loop for ``--seconds`` through
``submit()``/``step()``. Each request is timed from when it was due.

A cell on more than one chip runs on a ``(data=1, model=chips)`` mesh:
the weights are made where the program's sharding rules place them, the
engine is given the mesh, and the warm-up and the window run under it.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` profiles
the stretches the cell's ``trace`` names (``stretches`` of ``stretch_s``
seconds, spread over the window) and prints the per-layer metrics of
their sum, each read by its own reader in ``bench/metrics/<metric>.py``.

After the window a sample of finished requests, drawn from the seed and
holding the longest, is compared with the plain reference
(``archs/<model_type>/reference.py``): at each served token, the gap
between its logit and the reference's best at that position. Each
statistic of those gaps that the cell's ``check.limits`` names
(``max_gap``, ``median_gap``, ``far_share`` of the requests at the cell's
budget; with a suffix ``_b<budget>``, of those of a class at another
budget) must stay within its limit, and no program may have compiled
inside the window.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``check``: each number compared beside its limit).
Without a TPU, or on a device missing from ``bench/peaks.json``, the run
exits non-zero before it prints anything.

Options the benchmark's own runs do not use: ``--sweep r1,r2,...`` serves
a window of steady Poisson arrivals at each rate in one process and prints
one line per rate (the knee sweep); ``--control fp8`` puts the reference
computed with float8 operands in the program's place (the comparison's
control, which has to come out not correct), ``--control bf16`` the
reference with bfloat16 operands (the program's own precision).
"""
from __future__ import annotations

import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from contextlib import nullcontext  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import archs  # noqa: E402

# a served token whose logit lies this far below the reference's best was
# picked after a different upstream decision (a router flipped) or a fault:
# at budget 1.0 on a v5e, bf16 rounding alone kept every served token
# within 0.094 of the reference's best over 18 seeds
FAR_GAP = 0.1
# every trace, lowering or compilation JAX reports, in order
COMPILES: list = []
_WATCHING = False
_WATCH = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/backend_compile_duration")


def _watch(name, _secs, **_kw):
    if name in _WATCH:
        COMPILES.append(name)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default=None)
    ap.add_argument("--control", choices=("fp8", "bf16"), default=None)
    return ap.parse_args(argv)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, bench_dir: Path):
    """The cell's traffic mix and configuration, each found by the name
    BENCHMARK.json gives it."""
    spec = load_json(bench_dir.parent / "BENCHMARK.json")
    entry = {w["name"]: w for w in spec["workloads"]}[name]
    cell = load_json(bench_dir / "workloads" / f"{entry['traffic']}.json")
    cell.update(config=entry["config"], chips=entry["chips"])
    conf = load_json(bench_dir / "configs" / f"{entry['config']}.json")
    return cell, conf


def per_layer_metrics(name: str, bench_dir: Path) -> list:
    """The per-layer metrics BENCHMARK.json gives this cell."""
    spec = load_json(bench_dir.parent / "BENCHMARK.json")
    return [m for m in spec["per_layer"]
            if "workloads" not in m or name in m["workloads"]]


def check_device(chips: int, bench_dir: Path, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        sys.exit(f"bench: needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, found {len(devs)}")
    peaks = load_json(bench_dir / "peaks.json")
    kind = devs[0].device_kind
    if require_tpu and kind not in peaks:
        sys.exit(f"bench: no peaks for device kind {kind!r} in peaks.json")
    return devs[:chips], peaks.get(kind, next(iter(peaks.values())))


# ------------------------------ building ------------------------------------

def program_layout(cfg, ecfg):
    """Shapes and dtypes of (params, router params) as the program's own
    initialisers give them."""
    import jax
    from repro.models import model_init, router_init
    key = jax.random.PRNGKey(0)
    return (jax.eval_shape(lambda k: model_init(k, cfg, ecfg), key),
            jax.eval_shape(lambda k: router_init(k, cfg, ecfg), key))


def mesh_placement(cfg, ecfg, chips: int):
    """A ``(data=1, model=chips)`` mesh and the shardings (params, router
    params) the program gives its weights on it: the base weights by its
    own rules (``repro.runtime.sharding.param_shardings``), the routers
    replicated, as the engine places them."""
    import jax
    from repro.runtime import make_mesh
    from repro.runtime.sharding import param_shardings, replicated
    mesh = make_mesh((1, chips), ("data", "model"))
    params, rp = program_layout(cfg, ecfg)
    return mesh, (param_shardings(params, mesh),
                  jax.tree.map(lambda _: replicated(mesh), rp))


def check_layout(cfg, ecfg, params, rp) -> None:
    """The weights the benchmark made must have exactly the shapes and
    dtypes the program's own initialisers would give."""
    import jax
    want = program_layout(cfg, ecfg)
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                       (params, rp))
    sig = lambda t: jax.tree.map(lambda x: (x.shape, str(x.dtype)), t)
    if sig(want) != sig(got):
        raise SystemExit(f"bench: weight layout differs from the program's:"
                         f"\n want {sig(want)}\n got  {sig(got)}")


def build_engine(cfg, ecfg, conf: dict, params, rp, mesh=None):
    from repro.training import ServingEngine
    e = conf["engine"]
    return ServingEngine(params, rp, cfg, ecfg, mode=e["mode"],
                         batch_size=e["slots"], max_seq=e["max_seq"],
                         theta=conf["elastic"]["theta"],
                         kv_layout=e["kv_layout"], page_size=e["page_size"],
                         kv_dtype=e["kv_dtype"], mesh=mesh)


def under_mesh(s: dict):
    """The context the engine's calls run in: its mesh, if it has one."""
    return s["mesh"] if s["mesh"] is not None else nullcontext()


def gen_request(r, i):
    from repro.training import GenRequest
    return GenRequest(r.prompt, r.max_new, budget=r.budget, eos_id=None,
                      temperature=0.0, seed=i)


def drain(engine, limit_s: float = 600.0) -> None:
    t0 = time.perf_counter()
    while engine.has_work:
        if engine.step() == 0 or time.perf_counter() - t0 > limit_s:
            raise SystemExit("bench: the engine stalled while draining")


# ------------------------------- the window ---------------------------------

def stretch_starts(seconds: float, n: int) -> list:
    """Offsets from the window's open after which the traced stretches may
    start: evenly over the middle 80% of the window."""
    return [seconds * (0.1 + 0.8 * k / n) for k in range(n)]


def run_window(engine, reqs, cell, seconds: float, trace_root=None) -> dict:
    """Offer ``reqs`` open-loop for ``seconds``. Returns the window's record:
    its bounds, every handle with its due time, and per step its host
    bounds, occupancy, the prompts it admitted, the decode contexts it ran
    and the traced stretch it ran in (None outside any).

    With ``trace_root`` the profiler records each stretch of the cell's
    ``trace`` into ``trace_root/<k>``: stretch k starts at the first step
    after its offset that admits a request (or, failing one, late enough
    to end before the next offset) and lasts ``stretch_s``. The time the
    profiler then takes to write the stretch out stops the window's clock,
    so a traced run serves as much as an untraced one."""
    import jax
    from jax.profiler import TraceAnnotation
    clock = time.perf_counter
    n, i = len(reqs), 0
    handles, steps, live = [], [], []
    plan, stretch, trace_end, n_done, slack = [], None, 0.0, 0, 0.0
    if trace_root is not None:
        n_st, span = cell["trace"]["stretches"], cell["trace"]["stretch_s"]
        plan = stretch_starts(seconds, n_st)
        slack = max(0.0, 0.8 * seconds / n_st - span)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1     # the harness's own spans, no more
    counts0 = engine.compile_counts()
    n_traced0 = len(COMPILES)
    t0 = clock()
    base, end = t0, t0 + seconds      # base: t0 plus the profiler's pauses
    while True:
        now = clock()
        if now >= end:
            break
        if stretch is not None and now >= trace_end:
            jax.profiler.stop_trace()
            stretch = None
            pause = clock() - now
            base, end = base + pause, end + pause
            continue
        if stretch is None and plan and now - base >= plan[0]:
            sched = engine.scheduler
            due = sched.pending or (i < n and base + reqs[i].due <= now)
            if (due and sched.active < engine.B) \
                    or now - base >= plan[0] + slack:
                plan.pop(0)
                jax.profiler.start_trace(str(trace_root / str(n_done)),
                                         profiler_options=opts)
                stretch, trace_end = n_done, now + span
                n_done += 1
        if i < n and base + reqs[i].due <= now:
            with TraceAnnotation("bench.submit"):
                while i < n and base + reqs[i].due <= now:
                    h = engine.submit(gen_request(reqs[i], i))
                    h.t_submit = base + reqs[i].due
                    handles.append((h, reqs[i]))
                    live.append(h)
                    i += 1
        if engine.has_work:
            before = [len(h.output) for h in live]
            a = clock()
            with TraceAnnotation("bench.engine_step"):
                engine.step()
            b = clock()
            admitted, ctxs = [], []
            for h, k in zip(live, before):
                got = len(h.output)
                plen = len(h.request.prompt)
                if k == 0 and got >= 1:
                    admitted.append(plen)
                ctxs += [plen + j for j in range(max(k, 1), got)]
            live = [h for h in live if not h.done]
            steps.append({"t0": a, "t1": b, "stretch": stretch,
                          "occupancy": engine.scheduler.active / engine.B,
                          "admitted": admitted, "ctxs": ctxs})
        else:
            nxt = base + reqs[i].due if i < n else end
            with TraceAnnotation("bench.gen_wait"):
                time.sleep(max(0.0, min(nxt, end) - clock()))
    t_end = clock()
    if stretch is not None:
        jax.profiler.stop_trace()
    return {"t0": t0, "t_end": t_end, "handles": handles,
            "steps": steps, "pending": engine.scheduler.pending,
            "compiles": (counts0, engine.compile_counts()),
            "traced_in_window": COMPILES[n_traced0:]}


def percentile(xs, q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation, as numpy."""
    return float(np.percentile(np.asarray(xs, float), q))


def window_stats(rec: dict) -> dict:
    """TTFT over every request due in the window (one with no first token
    by the window's end counts its wait so far), ITL over every gap between
    two tokens of a request that ends inside the window, and every token
    emitted in the window over the window's length."""
    t0, t1 = rec["t0"], rec["t_end"]
    ttft, itl, n_tok = [], [], 0
    for h, _r in rec["handles"]:
        if h.t_submit >= t1:
            continue
        first = h.t_tokens[0] if h.t_tokens else None
        ttft.append((min(first, t1) if first is not None else t1)
                    - h.t_submit)
        ts = h.t_tokens
        n_tok += sum(1 for t in ts if t0 < t <= t1)
        itl += [b - a for a, b in zip(ts, ts[1:]) if t0 < b <= t1]
    return {"ttft": ttft, "itl": itl, "tokens": n_tok,
            "window_s": t1 - t0}


def e2e_metrics(rec: dict, setup_s: float, names) -> dict:
    s = window_stats(rec)
    vals = {
        "ttft_p90_ms": lambda: percentile(s["ttft"], 90) * 1e3,
        "itl_p50_ms": lambda: percentile(s["itl"], 50) * 1e3,
        "itl_p95_ms": lambda: percentile(s["itl"], 95) * 1e3,
        "output_tok_s": lambda: s["tokens"] / s["window_s"],
        "setup_s": lambda: setup_s,
    }
    units = {"ttft_p90_ms": "ms", "itl_p50_ms": "ms", "itl_p95_ms": "ms",
             "output_tok_s": "tokens/s", "setup_s": "s"}
    return {n: {"value": vals[n](), "unit": units[n]} for n in names}


# ------------------------------ correctness ---------------------------------

def limit_key(key: str, budget: float) -> tuple:
    """(statistic, budget) that a ``check.limits`` key names: ``<stat>``
    for the requests at the cell's ``budget``, ``<stat>_b<budget>`` for
    those of a class at another budget (``max_gap_b1``)."""
    m = re.fullmatch(r"(.+)_b(\d+(?:\.\d+)?)", key)
    return (m.group(1), float(m.group(2))) if m else (key, float(budget))


def sample_finished(rec: dict, seed: int, check: dict,
                    budget: float) -> list:
    """Finished requests at ``budget`` to compare: the one with the most
    served tokens, then others in an order drawn from the seed, until
    ``tokens`` served tokens or ``max_requests`` requests."""
    done = [(h, r) for h, r in rec["handles"]
            if h.done and h.finish_reason == "length"
            and len(h.output) == r.max_new and r.budget == budget]
    if not done:
        return []
    done.sort(key=lambda hr: -len(hr[0].output))
    rest = done[1:]
    order = np.random.default_rng(seed).permutation(len(rest))
    out, n = [done[0]], len(done[0][0].output)
    for j in order:
        if n >= check["tokens"] or len(out) >= check["max_requests"]:
            break
        out.append(rest[j])
        n += len(rest[j][0].output)
    return out


GAP_STATS = ("max_gap", "median_gap", "far_share")


def gap_stats(gaps) -> dict:
    """The numbers a cell may compare, from the gaps of every compared
    served token: the widest, the median, and the share above FAR_GAP."""
    g = np.concatenate(gaps)
    return {"max_gap": float(g.max()), "median_gap": float(np.median(g)),
            "far_share": float(np.mean(g > FAR_GAP))}


def compare(served_gaps, params, rp, conf, cell, picked, budget,
            control=None) -> dict:
    """The gaps of the served tokens of requests at ``budget`` below the
    reference's best logit (``served_gaps`` of the architecture's
    reference), and with a control those of the control's own tokens."""
    length = cell["check"]["ref_length"]
    prog, ctl, n = [], [], 0
    t = time.perf_counter()
    for h, r in picked:
        g, cg = served_gaps(params, rp, conf, budget, r.prompt,
                            h.output, length, control=control)
        prog.append(g)
        ctl.append(cg)
        n += g.size
    out = {"program": gap_stats(prog), "tokens": n,
           "requests": len(picked), "seconds": time.perf_counter() - t}
    if control is not None:
        out["control"] = gap_stats(ctl)
    return out


# ------------------------------ per-layer -----------------------------------

def load_reader(name: str, bench_dir: Path):
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def traced_record(rec: dict) -> dict:
    """The steps that ran while the profiler was on."""
    steps = [s for s in rec["steps"] if s["stretch"] is not None]
    return {"steps": steps, "all_steps": rec["steps"]}


def reduce_traces(trace_root: Path) -> dict:
    """The sum of every traced stretch's reduction."""
    import trace as T
    kernels = ("decode_attention", "paged_decode_attention",
               "flash_attention", "fused_mlp", "moe_gmm")
    reds = []
    for d in sorted(p for p in trace_root.iterdir() if p.is_dir()):
        files = sorted(d.glob("**/*.xplane.pb"))
        if not files:
            raise SystemExit(f"bench: the profiler wrote no trace in {d}")
        t = time.perf_counter()
        reds.append(T.reduce(T.load(files[-1]),
                             {"decode": "step", "admit": "admit"}, kernels))
        log(f"trace {d.name}: {files[-1].stat().st_size / 1e6:.1f} MB, "
            f"{reds[-1]['window_s']:.2f} s traced, reduced in "
            f"{time.perf_counter() - t:.1f} s")
    if not reds:
        raise SystemExit("bench: no traced stretch in the window")
    return T.combine(reds)


# -------------------------------- main --------------------------------------

def device_info(devs) -> dict:
    peak = 0
    for d in devs:
        try:
            peak = max(peak, int(d.memory_stats()["peak_bytes_in_use"]))
        except Exception:  # noqa: BLE001 - backends without memory stats
            pass
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def setup(args, bench_dir, require_tpu, overrides, fault):
    """Everything before the window. Returns a dict of what it built."""
    cell, conf = load_cell(args.workload, bench_dir)
    arch = archs.load(conf, bench_dir)
    devs, peaks = check_device(cell["chips"], bench_dir, require_tpu)
    import jax
    global _WATCHING
    if not _WATCHING:
        jax.monitoring.register_event_duration_secs_listener(_watch)
        _WATCHING = True
    sys.path.insert(0, str(bench_dir.parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    log(f"compile cache: {enable_compile_cache()}")
    cfg, ecfg = arch.program.model_config(conf, overrides or {})
    mesh, shardings = None, None
    if cell["chips"] > 1:
        mesh, shardings = mesh_placement(cfg, ecfg, cell["chips"])
    params, rp = arch.weights.make_weights(conf, args.seed, shardings)
    check_layout(cfg, ecfg, params, rp)
    log(f"weights: {archs.weight_bytes(params) / 1e9:.2f} GB, "
        f"{conf['num_hidden_layers']} layers, seed {args.seed}"
        + (f", mesh {dict(mesh.shape)}" if mesh is not None else ""))
    engine = build_engine(cfg, ecfg, conf, params, rp, mesh)
    if fault is not None:
        fault(engine)
    s = {"cell": cell, "conf": conf, "arch": arch, "devs": devs,
         "peaks": peaks, "params": params, "rp": rp, "engine": engine,
         "mesh": mesh}
    import traffic
    V = conf["vocab_size"]
    with under_mesh(s):
        for r in traffic.warmup(cell, args.seed, V):
            engine.submit(gen_request(r, 0))
            drain(engine)
    log(f"warm: compile counts {engine.compile_counts()}")
    return s


def sweep(args, s) -> None:
    """Serve a window at each rate; one JSON line per rate on stdout."""
    import traffic
    cell, engine = s["cell"], s["engine"]
    for rate in [float(x) for x in args.sweep.split(",")]:
        c = dict(cell, arrivals={"kind": "poisson", "rate": rate})
        reqs = traffic.generate(c, args.seconds, args.seed,
                                s["conf"]["vocab_size"])
        with under_mesh(s):
            rec = run_window(engine, reqs, c, args.seconds)
            pending = rec["pending"]
            t = time.perf_counter()
            drain(engine)
        st = window_stats(rec)
        due = len(st["ttft"])
        print(json.dumps({
            "sweep_rate": rate, "mean_rate": traffic.mean_rate(c["arrivals"]),
            "due": due, "queued_at_end": pending,
            "output_tok_s": st["tokens"] / st["window_s"],
            "ttft_p50_ms": percentile(st["ttft"], 50) * 1e3,
            "ttft_p90_ms": percentile(st["ttft"], 90) * 1e3,
            "itl_p50_ms": percentile(st["itl"], 50) * 1e3,
            "itl_p95_ms": percentile(st["itl"], 95) * 1e3,
            "drain_s": time.perf_counter() - t}), flush=True)


def run_cell(args, bench_dir, require_tpu, overrides, fault) -> dict:
    """One run of the cell: set-up, the window, the comparison. Returns the
    result line's object."""
    s = setup(args, bench_dir, require_tpu, overrides, fault)
    if args.sweep:
        sweep(args, s)
        return {}
    import traffic
    cell, conf, engine = s["cell"], s["conf"], s["engine"]
    reqs = traffic.generate(cell, args.seconds, args.seed,
                            conf["vocab_size"])
    trace_root = None
    if args.trace:
        trace_root = bench_dir.parent / ".bench_out" / "trace"
        shutil.rmtree(trace_root, ignore_errors=True)
    setup_s = time.perf_counter() - T_PROC0
    with under_mesh(s):
        rec = run_window(engine, reqs, cell, args.seconds, trace_root)
    dev = device_info(s["devs"])

    checks = {}
    c0, c1 = rec["compiles"]
    n_comp = sum(c1.values()) - sum(c0.values()) \
        + len(rec["traced_in_window"])
    checks["window_compiles"] = {"value": n_comp, "limit": 0}
    if cell["arrivals"]["kind"] == "offline":
        checks["backlog_left"] = {"value": rec["pending"], "min": 1}

    metrics, breakdown = {}, None
    if args.trace:
        ctx = {"cell": cell, "conf": conf, "peaks": s["peaks"],
               "dims": s["arch"].weights.dims(conf),
               "chips": cell["chips"]}
        red = reduce_traces(trace_root)
        shutil.rmtree(trace_root, ignore_errors=True)
        trec = traced_record(rec)
        for m in per_layer_metrics(args.workload, bench_dir):
            v = load_reader(m["name"], bench_dir)(red, trec, ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        dev["busy_s"], dev["window_s"] = red["busy_s"], red["window_s"]
        breakdown = {"device_ops": [[n, v] for n, v in red["device_ops"]],
                     "idle_gaps": red["idle_gaps"]}
    else:
        spec = load_json(bench_dir.parent / "BENCHMARK.json")
        names = [m["name"] for m in spec["end_to_end"]
                 if "workloads" not in m
                 or args.workload in m["workloads"]]
        metrics = e2e_metrics(rec, setup_s, names)

    limits = {k: (limit_key(k, cell["budget"]), lim)
              for k, lim in cell["check"]["limits"].items()}
    picked = {b: sample_finished(rec, args.seed, cell["check"], b)
              for b in sorted({b for (_st, b), _l in limits.values()})}
    st = window_stats(rec)
    failed = sum(1 for h, _ in rec["handles"]
                 if h.status in ("rejected", "cancelled"))
    del s["engine"], engine
    gc.collect()
    judged = {}
    for b, pk in picked.items():
        if not pk:
            continue
        cmp = compare(s["arch"].reference.served_gaps, s["params"], s["rp"],
                      conf, cell, pk, b, args.control)
        log(f"compared {cmp['requests']} requests at budget {b}, "
            f"{cmp['tokens']} served tokens in {cmp['seconds']:.1f} s: "
            f"{cmp['program']}")
        # with a control, the control stands in the program's place
        judged[b] = cmp["program"]
        if args.control:
            log(f"control ({args.control}) in the program's place: "
                f"{cmp['control']}")
            judged[b] = cmp["control"]
    for k, ((stat, b), lim) in limits.items():
        checks[k] = {"value": judged[b][stat] if b in judged else None,
                     "limit": lim}
    correct = (all(checks[k]["value"] is not None
                   and checks[k]["value"] <= lim
                   for k, (_sb, lim) in limits.items())
               and n_comp == 0
               and ("backlog_left" not in checks
                    or checks["backlog_left"]["value"] >= 1))
    out = {"correct": bool(correct), "attempted": len(st["ttft"]),
           "failed": failed, "metrics": metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = checks
    for k, v in checks.items():
        log(f"check {k}: {json.dumps(v)}")
    return out


def main(argv=None, *, require_tpu: bool = True, bench_dir: Path = BENCH,
         overrides: dict | None = None, fault=None) -> int:
    args = parse(argv)
    out = run_cell(args, bench_dir, require_tpu, overrides, fault)
    if out:
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
