"""Reduction of one profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

On a TPU the device plane (``/device:TPU:<n>``) has an ``XLA Modules``
line, one event per execution of a compiled program (named after the
jitted function: the serving engine's ``admit`` and ``step``), and an
``XLA Ops`` line, one event per operation; a Pallas kernel's operation
carries the kernel's ``name``. Host spans come from the harness's own
``jax.profiler.TraceAnnotation``s (``bench.*``) on the host plane, on the
same clock.

A trace holds one traced stretch: from its first ``bench.*`` span's start
to its last one's end. Busy time is the union of the device's operation
intervals inside it; an idle gap is a stretch of it with no operation
running, labelled by the host span covering its middle. ``combine`` sums
the stretches of one run.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

MODULES, OPS, TRACEME = "XLA Modules", "XLA Ops", "XLA TraceMe"
DROPPED = "Trace Buffers Dropped"
# operations whose interval holds other operations of the same line
CONTAINERS = ("while", "conditional", "call")
HOST_PREFIX = "bench."
_SUFFIX = re.compile(r"(\.\d+)+$")


class TraceError(RuntimeError):
    pass


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def _runs(line):
    """Module events with the execution they belong to: a program may run
    as several device programs (continuations), one event each, that share
    the execution's ``run_id``."""
    out = []
    for e in line.events:
        run = dict(e.stats).get("run_id")
        out.append((e.name, e.start_ns, e.start_ns + e.duration_ns, run))
    return out


def planes(pd) -> dict:
    """{"device": [(plane name, {line name: [(name, start, end)]})],
    "host": [(name, start, end)] of the bench.* spans, "dropped": whether
    the device's trace buffer overflowed}."""
    dev, host, dropped = [], [], False
    for pl in pd.planes:
        if pl.name.startswith("/device:TPU:") and "Core" not in pl.name:
            lines = {ln.name: (_runs(ln) if ln.name == MODULES
                               else _events(ln))
                     for ln in pl.lines if ln.name in (MODULES, OPS)}
            dev.append((pl.name, lines))
            dropped |= any(e.name == DROPPED for ln in pl.lines
                           for e in ln.events if ln.name == TRACEME)
        elif pl.name.startswith("/host:"):
            for ln in pl.lines:
                host += [e for e in _events(ln)
                         if e[0].startswith(HOST_PREFIX)]
    dev.sort(key=lambda p: p[0])
    host.sort(key=lambda e: e[1])
    return {"device": dev, "host": host, "dropped": dropped}


def union(intervals, lo, hi) -> list:
    """Merged [start, end) intervals clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


_HLO = re.compile(r"%?([\w.\-]+)")


def op_name(event_name: str) -> str:
    """The HLO instruction's name of an ``XLA Ops`` event. On a TPU the
    event carries the instruction's text, ``%fusion.12 = bf16[...] ...``."""
    m = _HLO.match(event_name.strip())
    return m.group(1) if m else event_name


def base_name(name: str) -> str:
    """An operation's name without XLA's ``.N`` uniquifying suffixes."""
    return _SUFFIX.sub("", op_name(name))


def program_of(module_name: str, programs: dict):
    """The key of ``programs`` ({key: jitted function name}) whose function
    names this module (``jit_<fn>`` with an optional ``(id)``), or None."""
    m = re.match(r"jit_(\w+?)(\(\d+\))?$", module_name.strip())
    if not m:
        return None
    for key, fn in programs.items():
        if m.group(1) == fn:
            return key
    return None


def reduce(pd, programs: dict, kernels=(), n_top: int = 10) -> dict:
    """Everything the per-layer readers take from one trace.

    ``programs``: {key: jitted function name}, e.g. {"decode": "step",
    "admit": "admit"}; ``kernels``: Pallas kernel names to time. Device
    numbers are averaged over the device planes (one per chip used).
    ``program_op_s`` gives, for each program, the seconds of each operation
    (by ``base_name``) that starts inside one of its executions."""
    p = planes(pd)
    if not p["device"]:
        raise TraceError("no TPU device plane in the trace")
    if p["dropped"]:
        raise TraceError("the profiler dropped device events (its buffer "
                         "filled): profile a shorter stretch")
    if not p["host"]:
        raise TraceError("no bench.* host span in the trace")
    lo = p["host"][0][1]
    hi = max(e[2] for e in p["host"])
    window = (hi - lo) / 1e9
    busy, prog_t, prog_n, kern_t, op_t, gaps = [], [], [], [], [], []
    prog_op = []
    for _name, lines in p["device"]:
        ops = lines.get(OPS, [])
        mods = lines.get(MODULES, [])
        merged = union([(s, e) for _n, s, e in ops], lo, hi)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        pt, runs, execs = defaultdict(float), defaultdict(set), []
        for i, (n, s, e, run) in enumerate(mods):
            key = program_of(n, programs)
            if key is not None and lo <= s and e <= hi:
                pt[key] += (e - s) / 1e9
                runs[key].add(run if run is not None else ("event", i))
                execs.append((s, e, key))
        prog_t.append(pt)
        prog_n.append({k: len(v) for k, v in runs.items()})
        execs.sort()
        starts = [x[0] for x in execs]
        kt, ot = defaultdict(float), defaultdict(float)
        pot = defaultdict(lambda: defaultdict(float))
        for n, s, e in ops:
            if not (lo <= s and e <= hi):
                continue
            b = base_name(n)
            ot[b] += (e - s) / 1e9
            if b in kernels:
                kt[b] += (e - s) / 1e9
            j = bisect.bisect_right(starts, s) - 1
            if j >= 0 and s < execs[j][1] and b not in CONTAINERS:
                pot[execs[j][2]][b] += (e - s) / 1e9
        kern_t.append(kt)
        op_t.append(ot)
        prog_op.append(pot)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    nd = len(p["device"])

    def mean(ds):
        keys = set().union(*ds) if ds else set()
        return {k: sum(d.get(k, 0.0) for d in ds) / nd for k in keys}

    top_gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n_top]
    ops_mean = {k: v for k, v in mean(op_t).items() if k not in CONTAINERS}
    return {
        "window_s": window,
        "busy_s": sum(busy) / nd,
        "program_s": mean(prog_t),
        "program_n": mean(prog_n),
        "program_op_s": {k: mean([p.get(k, {}) for p in prog_op])
                         for k in set().union(*prog_op)},
        "kernel_s": mean(kern_t),
        "op_s": ops_mean,
        "device_ops": sorted(ops_mean.items(), key=lambda kv: -kv[1])[:n_top],
        "idle_gaps": [[_label(p["host"], (s + e) / 2), (e - s) / 1e9]
                      for s, e in top_gaps],
    }


def combine(reds, n_top: int = 10) -> dict:
    """One reduction of several traced stretches: times, counts and
    windows summed; the operations that took most time and the longest
    idle gaps over all of them."""
    def total(key):
        out = defaultdict(float)
        for r in reds:
            for k, v in r[key].items():
                out[k] += v
        return dict(out)
    ops = total("op_s")
    prog_op = defaultdict(lambda: defaultdict(float))
    for r in reds:
        for prog, d in r["program_op_s"].items():
            for k, v in d.items():
                prog_op[prog][k] += v
    gaps = sorted((g for r in reds for g in r["idle_gaps"]),
                  key=lambda g: -g[1])
    return {
        "window_s": sum(r["window_s"] for r in reds),
        "busy_s": sum(r["busy_s"] for r in reds),
        "program_s": total("program_s"),
        "program_n": total("program_n"),
        "program_op_s": {k: dict(v) for k, v in prog_op.items()},
        "kernel_s": total("kernel_s"),
        "op_s": ops,
        "device_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:n_top],
        "idle_gaps": [list(g) for g in gaps[:n_top]],
        "stretches": len(reds),
    }


def _label(host, t) -> str:
    """The innermost bench.* span covering time t, or "outside spans"."""
    best = None
    for name, s, e in host:
        if s <= t < e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "outside spans"

