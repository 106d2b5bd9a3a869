#!/usr/bin/env python3
"""A cell on the chip with the engine's spans and the model's scopes read
from its traces (``engine_trace.py``), beside the harness's own reduction:

    python3 bench/tests/chip_spans.py --workload <cell> --seed <n> \
        --seconds <s>

serves the cell's window with its traced stretches as ``run.py --trace 1``
does, skips the comparison with the reference, and prints one JSON line:
the cell's per-layer metrics, the engine metrics of
``engine_trace.METRICS``, the longest idle gaps labelled by engine phase,
device ms per execution under each scope of each program, host ms and
count of each ``serve.*`` span, and the profiler's cost: the mean wall
time of the steps that decode and admit nothing, inside the traced
stretches and outside them. With ``--keep <dir>`` the trace of the
stretch that holds the longest idle gap is copied there.

    python3 bench/tests/chip_spans.py --record <dir> --workload <cell> \
        --seed <n> --seconds <s>

instead admits the first request of the cell's traffic, lets it decode,
then traces two single steps into ``<dir>/<k>.xplane.pb``: one that also
admits the second request cut to a 24-token prompt (two paged chunks),
one that only decodes.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import statistics
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE)]

import engine_trace as E  # noqa: E402
import run  # noqa: E402
import trace as T  # noqa: E402

PROGRAMS = {"decode": "step", "admit": "admit"}
KERNELS = ("decode_attention", "paged_decode_attention", "flash_attention",
           "fused_mlp", "moe_gmm")
RECORD_PROMPT = 24


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1      # as the harness traces
    return opts


def _one_file(d: Path) -> Path:
    files = sorted(d.glob("**/*.xplane.pb"))
    if not files:
        raise SystemExit(f"chip_spans: the profiler wrote no trace in {d}")
    return files[-1]


def record(s, seed: int, seconds: float, out: Path) -> list:
    """The two recorded steps' trace files (see the module's doc)."""
    import jax
    import traffic
    from jax.profiler import TraceAnnotation
    engine, cell = s["engine"], s["cell"]
    reqs = traffic.generate(cell, seconds, seed, s["conf"]["vocab_size"])
    engine.submit(run.gen_request(reqs[0], 0))
    for _ in range(4):
        engine.step()
    short = dataclasses.replace(run.gen_request(reqs[1], 1),
                                prompt=reqs[1].prompt[:RECORD_PROMPT])
    out.mkdir(parents=True, exist_ok=True)
    files = []
    for k, admit in enumerate((short, None)):
        d = out / f"profile_{k}"
        jax.profiler.start_trace(str(d), profiler_options=_profile_options())
        if admit is not None:
            with TraceAnnotation("bench.submit"):
                engine.submit(admit)
        with TraceAnnotation("bench.engine_step"):
            engine.step()
        jax.profiler.stop_trace()
        f = out / f"{k}.xplane.pb"
        shutil.copy(_one_file(d), f)
        shutil.rmtree(d)
        files.append(f)
    return files


def measure(s, workload: str, seed: int, seconds: float, bench_dir: Path,
            keep: Path | None = None) -> dict:
    """One traced window of the cell, reduced; the result line's object."""
    import traffic
    cell, conf, engine = s["cell"], s["conf"], s["engine"]
    reqs = traffic.generate(cell, seconds, seed, conf["vocab_size"])
    root = bench_dir.parent / ".bench_out" / "spans"
    shutil.rmtree(root, ignore_errors=True)
    with run.under_mesh(s):
        rec = run.run_window(engine, reqs, cell, seconds, root)
    reds, files = [], []
    for d in sorted(p for p in root.iterdir() if p.is_dir()):
        files.append(_one_file(d))
        reds.append(E.reduce(T.load(files[-1]), files[-1], PROGRAMS,
                             KERNELS))
    if keep is not None:
        longest = max(range(len(reds)),
                      key=lambda i: max([g[1] for g in reds[i]["idle_gaps"]],
                                        default=0.0))
        keep.mkdir(parents=True, exist_ok=True)
        shutil.copy(files[longest], keep / f"{workload}.xplane.pb")
    shutil.rmtree(root, ignore_errors=True)
    red = E.combine(reds)
    ctx = {"cell": cell, "conf": conf, "peaks": s["peaks"],
           "dims": s["arch"].weights.dims(conf), "chips": cell["chips"]}
    trec = run.traced_record(rec)
    layer = {}
    for m in run.per_layer_metrics(workload, bench_dir):
        v = run.load_reader(m["name"], bench_dir)(red, trec, ctx)
        if v is not None:
            layer[m["name"]] = v
    n = red["program_n"]
    scopes = {f"{prog}/{scope or '-'}": secs / n[prog] * 1e3
              for (prog, scope), secs in red["scope_s"].items()
              if n.get(prog)}

    def bare_ms(traced):
        ts = [st["t1"] - st["t0"] for st in rec["steps"]
              if st["ctxs"] and not st["admitted"]
              and (st["stretch"] is not None) == traced]
        return ({"mean_ms": statistics.fmean(ts) * 1e3, "steps": len(ts)}
                if ts else None)
    return {
        "per_layer": layer,
        "engine": {k: f(red) for k, f in E.METRICS.items()},
        "idle_gaps": red["idle_gaps"],
        "scope_ms_per_run": scopes,
        "program_n": n,
        "spans": {k: {"ms": v * 1e3, "n": red["span_n"][k]}
                  for k, v in sorted(red["span_s"].items())},
        "window_s": red["window_s"], "busy_s": red["busy_s"],
        "engine_idle_s": red["engine_idle_s"],
        "bare_step": {"traced": bare_ms(True), "untraced": bare_ms(False)},
    }


def main(argv=None, *, require_tpu: bool = True, bench_dir: Path = run.BENCH,
         overrides: dict | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--record", type=Path, default=None)
    ap.add_argument("--keep", type=Path, default=None)
    own, rest = ap.parse_known_args(argv)
    args = run.parse(rest)
    s = run.setup(args, bench_dir, require_tpu, overrides, None)
    if own.record is not None:
        with run.under_mesh(s):
            files = record(s, args.seed, args.seconds, own.record)
        for f in files:
            print(json.dumps({"trace": str(f), "bytes": f.stat().st_size}),
                  flush=True)
        return 0
    print(json.dumps(measure(s, args.workload, args.seed, args.seconds,
                             bench_dir, own.keep)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
