"""The serving engine's own spans and the model's named scopes in one
profiler trace, reduced beside ``trace.reduce``.

``ServingEngine.step`` records each of its phases as a host span
(``serve.step`` around ``serve.schedule``, ``serve.admit`` with
``serve.admit.prefix``/``.call``/``.sync``, ``serve.pages``,
``serve.upload``, ``serve.decode``, ``serve.sync``, ``serve.emit``), and
each compiled operation carries its name-scope path in the ``tf_op`` stat
of its event metadata (``jit(step)/while/body/closed_call/attention/dot``),
which ``ProfileData`` does not expose: ``xplane.py`` reads it from the
file, by the operation event's metadata id.

``reduce`` returns every key of ``trace.reduce`` with the value it has
there, except ``idle_gaps``, whose gaps are labelled here by the innermost
span of either family (``bench.*`` or ``serve.*``), and adds:

- ``span_s``, ``span_n``: host seconds and count of each ``serve.*`` span
  inside the traced window;
- ``engine_idle_s``: device idle seconds inside ``serve.step`` spans;
- ``scope_s``: {(program key, scope): device seconds} of the operations
  inside executions of that program in the window, each put down to the
  innermost scope of ``SCOPES`` in its ``tf_op`` (None: no scope).

``combine`` sums them over stretches as ``trace.combine`` does. The
functions named after metrics read one reduction; each returns None where
the trace holds nothing it reads (a program without the spans or scopes).
"""
from __future__ import annotations

import bisect
from collections import defaultdict

import trace as T
import xplane

ENGINE_PREFIX = "serve."
SCOPES = ("attention", "mlp", "router", "lm_head", "sample")
# the host's own work around each decode step, outside any wait on it
DECODE_HOST = ("serve.pages", "serve.upload", "serve.decode", "serve.emit")


def engine_spans(pd) -> list:
    """[(name, start, end)] of the ``serve.*`` spans, by start."""
    out = []
    for pl in pd.planes:
        if pl.name.startswith("/host:"):
            for ln in pl.lines:
                out += [e for e in T._events(ln)
                        if e[0].startswith(ENGINE_PREFIX)]
    return sorted(out, key=lambda e: e[1])


def scope_of(tf_op: str):
    """The innermost scope of SCOPES in a ``tf_op`` stat (``path:type``),
    or None."""
    for part in reversed(tf_op.rsplit(":", 1)[0].split("/")):
        if part in SCOPES:
            return part
    return None


def _device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") and "Core" not in name


def op_scopes(path) -> dict:
    """{device plane name: [(scope, start, end)]} of the plane's ``XLA
    Ops`` events, without the operations that hold others (``while``,
    ``conditional``, ``call``)."""
    out = {}
    for pl in xplane.read(path, want_plane=_device_plane,
                          want_events=lambda _p, ln: ln == T.OPS):
        scope = {mid: scope_of(str(stats.get("tf_op", "")))
                 for mid, (name, stats) in pl.event_metadata.items()
                 if T.base_name(name) not in T.CONTAINERS}
        out[pl.name] = [(scope[m], s, e) for ln in pl.lines
                        if ln.name == T.OPS
                        for m, s, e in ln.events if m in scope]
    return out


def overlap(a, b) -> float:
    """Total length of the intersection of two sorted lists of disjoint
    [start, end) intervals."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def scope_seconds(ops, mods, programs, lo, hi) -> dict:
    """{(program key, scope): seconds} of ``ops`` [(scope, start, end)]
    that start inside an execution (``mods``: the ``XLA Modules`` events
    of ``trace.planes``) of a program of ``programs`` lying in [lo, hi]."""
    runs = sorted((s, e, key) for n, s, e, _run in mods
                  if (key := T.program_of(n, programs)) is not None
                  and lo <= s and e <= hi)
    starts = [r[0] for r in runs]
    out = defaultdict(float)
    for scope, s, e in ops:
        j = bisect.bisect_right(starts, s) - 1
        if j >= 0 and s < runs[j][1]:
            out[(runs[j][2], scope)] += (e - s) / 1e9
    return out


def reduce(pd, path, programs: dict, kernels=(), n_top: int = 10) -> dict:
    """``trace.reduce`` of the trace ``pd`` read from ``path``, with the
    engine's spans and the model's scopes (see the module's doc)."""
    red = T.reduce(pd, programs, kernels, n_top)
    p = T.planes(pd)
    lo = p["host"][0][1]
    hi = max(e[2] for e in p["host"])
    spans = engine_spans(pd)
    inside = [sp for sp in spans if lo <= sp[1] and sp[2] <= hi]
    span_s, span_n = defaultdict(float), defaultdict(int)
    for n, s, e in inside:
        span_s[n] += (e - s) / 1e9
        span_n[n] += 1
    steps = T.union([(s, e) for n, s, e in inside if n == "serve.step"],
                    lo, hi)
    scopes = op_scopes(path)
    idle, scope_t, gaps = 0.0, defaultdict(float), []
    for name, lines in p["device"]:
        merged = T.union([(s, e) for _n, s, e in lines.get(T.OPS, [])],
                         lo, hi)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        g = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
             if edges[i + 1] > edges[i]]
        gaps += g
        idle += overlap(g, steps) / 1e9
        for k, v in scope_seconds(scopes.get(name, []),
                                  lines.get(T.MODULES, []), programs,
                                  lo, hi).items():
            scope_t[k] += v
    nd = len(p["device"])
    host = p["host"] + spans
    top = sorted(gaps, key=lambda g: g[0] - g[1])[:n_top]
    red["idle_gaps"] = [[T._label(host, (s + e) / 2), (e - s) / 1e9]
                        for s, e in top]
    red["span_s"], red["span_n"] = dict(span_s), dict(span_n)
    red["engine_idle_s"] = idle / nd
    red["scope_s"] = {k: v / nd for k, v in scope_t.items()}
    return red


def combine(reds, n_top: int = 10) -> dict:
    """``trace.combine`` of several stretches' reductions, with the keys
    ``reduce`` adds summed."""
    out = T.combine(reds, n_top)
    for key in ("span_s", "span_n", "scope_s"):
        tot = defaultdict(float)
        for r in reds:
            for k, v in r[key].items():
                tot[k] += v
        out[key] = dict(tot)
    out["engine_idle_s"] = sum(r["engine_idle_s"] for r in reds)
    return out


# ------------------------------ metrics -------------------------------------

def decode_host_ms(red):
    """Engine host: host time in ``serve.pages``, ``.upload``, ``.decode``
    and ``.emit`` over the window, per ``serve.decode`` span, ms: the
    host's own cost of a token step, outside any wait on the device."""
    n = red["span_n"].get("serve.decode", 0)
    if not n:
        return None
    return sum(red["span_s"].get(k, 0.0) for k in DECODE_HOST) / n * 1e3


def engine_idle_share(red):
    """Engine host: device idle time inside ``serve.step`` spans over the
    traced window, in percent; the rest of ``device_idle_share`` is the
    harness's."""
    if not red["span_n"].get("serve.step"):
        return None
    return 100.0 * red["engine_idle_s"] / red["window_s"]


def _per_decode_ms(red, scope):
    n = red["program_n"].get("decode", 0)
    scoped = [k for k in red["scope_s"] if k[0] == "decode" and k[1]]
    if not n or not scoped:
        return None
    return red["scope_s"].get(("decode", scope), 0.0) / n * 1e3


def decode_attention_ms(red):
    """Model step: device time of the operations under ``attention`` in
    the decode program's executions, per execution, ms."""
    return _per_decode_ms(red, "attention")


def decode_mlp_ms(red):
    """Model step: device time of the operations under ``mlp`` in the
    decode program's executions, per execution, ms."""
    return _per_decode_ms(red, "mlp")


METRICS = {f.__name__: f for f in (decode_host_ms, engine_idle_share,
                                   decode_attention_ms, decode_mlp_ms)}
