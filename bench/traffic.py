"""One general generator for every traffic mix under ``workloads/``.

A mix is a data file of parameters; nothing here knows a mix by name.
Lengths are lognormal and clipped, as ``heavy_tailed_lengths`` draws them,
and arrivals are Poisson, modulated Poisson (periodic bursts) or an
offline backlog. Every seed gets the same multiset of lengths and of
inter-arrival gaps, drawn at stratified quantiles, in an order of its own,
so seeds change which request comes when, not how much work a run holds.
A backlog may be cut into blocks (``arrivals.block``) that each hold the
same stratified lengths, so that the requests served first, whichever
seed orders them, are the same work too; with ``arrivals.order_seed``
that seed, not the run's, orders the sizes and arrivals, so that every
run serves the same work in the same order and the run's seed draws only
the prompts' tokens (and the weights). A mix may hold classes of
requests (``classes``: each a share of every block, with a budget and
lengths of its own).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass
class Request:
    due: float              # seconds after the window opens
    prompt: np.ndarray      # (P,) int32
    max_new: int
    budget: float = 1.0


def lognormal_lengths(n: int, median: float, sigma: float, lo: int, hi: int,
                      buckets=None) -> np.ndarray:
    """n lengths at the stratified quantiles (i + 0.5) / n of a lognormal,
    clipped to [lo, hi], each rounded up to the next bucket if given."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.clip(np.round(median * np.exp(sigma * z)), lo, hi).astype(int)
    if buckets:
        b = np.asarray(sorted(buckets))
        x = b[np.minimum(np.searchsorted(b, x), b.size - 1)]
    return x


def unit_gaps(n: int) -> np.ndarray:
    """n unit-mean exponential gaps at stratified quantiles."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u)


def _invert_intensity(lam: np.ndarray, rate: float, period: float,
                      burst_at: float, burst_len: float,
                      factor: float) -> np.ndarray:
    """Times t with integral_0^t rate(s) ds = lam, where rate(s) is
    ``rate * factor`` for s mod period in [burst_at, burst_at + burst_len)
    and ``rate`` otherwise."""
    per = rate * (period + (factor - 1.0) * burst_len)   # mass per period
    out = np.empty_like(lam)
    for i, m in enumerate(lam):
        k, r = divmod(float(m), per)
        t = k * period
        seg = [(burst_at, rate), (burst_len, rate * factor),
               (period - burst_at - burst_len, rate)]
        for dur, rt in seg:
            if r <= dur * rt:
                t += r / rt
                break
            r -= dur * rt
            t += dur
        out[i] = t
    return out


def arrivals(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    kind = spec["kind"]
    if kind == "offline":
        return np.zeros(n)
    gaps = rng.permutation(unit_gaps(n))
    lam = np.cumsum(gaps)
    if kind == "poisson":
        return lam / spec["rate"]
    if kind == "bursty":
        return _invert_intensity(lam, spec["rate"], spec["period_s"],
                                 spec["burst_at_s"], spec["burst_s"],
                                 spec["burst_factor"])
    raise ValueError(f"unknown arrival kind {kind!r}")


def mean_rate(spec: dict) -> float:
    if spec["kind"] == "bursty":
        return spec["rate"] * (1 + (spec["burst_factor"] - 1)
                               * spec["burst_s"] / spec["period_s"])
    return spec.get("rate", 0.0)


def n_requests(cell: dict, seconds: float) -> int:
    """Requests generated for a window: the backlog, or enough arrivals to
    outlast the window by a quarter."""
    a = cell["arrivals"]
    if a["kind"] == "offline":
        return int(a["backlog"])
    return int(math.ceil(mean_rate(a) * seconds * 1.25)) + 8


def _lengths(k: int, spec: dict) -> np.ndarray:
    return lognormal_lengths(k, spec["median"], spec["sigma"], spec["lo"],
                             spec["hi"], spec.get("buckets"))


def classes(cell: dict) -> list:
    """The cell's request classes, each with its share of a block, budget,
    prompt and output lengths (a class's own ``prompt``/``output`` keys
    replace the cell's)."""
    out = []
    for c in cell.get("classes") or [{"share": 1}]:
        out.append({"share": int(c["share"]),
                    "budget": float(c.get("budget", cell["budget"])),
                    "prompt": {**cell["prompt"], **c.get("prompt", {})},
                    "output": {**cell["output"], **c.get("output", {})}})
    return out


def sizes(cell: dict, n: int, rng) -> list:
    """n (prompt length, output length, budget) in queue order: blocks of
    ``arrivals.block`` requests (all n by default), each holding every
    class's share of the block at stratified lengths, in an order of its
    own."""
    cls = classes(cell)
    block = int(cell["arrivals"].get("block", n))
    total = sum(c["share"] for c in cls)
    if n % block or block % total:
        raise ValueError(f"{n} requests do not fill blocks of {block} "
                         f"shared {total} ways")
    out = []
    for _ in range(n // block):
        blk = []
        for c in cls:
            k = c["share"] * block // total
            pl = rng.permutation(_lengths(k, c["prompt"]))
            ol = rng.permutation(_lengths(k, c["output"]))
            blk += [(int(a), int(b), c["budget"]) for a, b in zip(pl, ol)]
        if len(cls) > 1:
            blk = [blk[j] for j in rng.permutation(len(blk))]
        out += blk
    return out


def generate(cell: dict, seconds: float, seed: int, vocab: int) -> list:
    """The window's requests, sorted by due time (a backlog in its queue
    order)."""
    n = n_requests(cell, seconds)
    rng = np.random.default_rng(seed)
    a = cell["arrivals"]
    order = np.random.default_rng(a["order_seed"]) if "order_seed" in a \
        else rng
    sz = sizes(cell, n, order)
    due = arrivals(a, n, order)
    reqs = [Request(float(due[i]),
                    rng.integers(0, vocab, sz[i][0], dtype=np.int32),
                    sz[i][1], sz[i][2]) for i in range(n)]
    return sorted(reqs, key=lambda r: r.due)


def warmup(cell: dict, seed: int, vocab: int) -> list:
    """One short request per prompt shape and budget the cell's traffic can
    use: each prompt bucket of each class, or (without buckets) its longest
    prompt."""
    rng = np.random.default_rng(seed ^ 0x5EED)
    out = []
    for c in classes(cell):
        p = c["prompt"]
        for n in sorted(p["buckets"]) if p.get("buckets") else [p["hi"]]:
            out.append(Request(0.0, rng.integers(0, vocab, n, dtype=np.int32),
                               2, c["budget"]))
    return out
