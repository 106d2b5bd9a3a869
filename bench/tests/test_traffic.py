"""The generator: same seed, same requests; every seed the same work."""
import json

import numpy as np
import pytest

import traffic
from conftest import BENCH


def cell(name):
    return json.loads((BENCH / "workloads" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["elastic-b05-chat",
                                  "paged-int8-rag-backlog",
                                  "elastic-b10-offline"])
def test_seed_determines_requests(name):
    c = cell(name)
    a = traffic.generate(c, 40, 3_000_000_019, 152064)
    b = traffic.generate(c, 40, 3_000_000_019, 152064)
    other = traffic.generate(c, 40, 7, 152064)
    assert [(r.due, r.max_new, r.prompt.tolist()) for r in a] == \
        [(r.due, r.max_new, r.prompt.tolist()) for r in b]
    # another seed: the same multiset of sizes and gaps, other tokens
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in other)
    assert sorted(r.prompt.size for r in a) == \
        sorted(r.prompt.size for r in other)
    assert a[-1].due == pytest.approx(other[-1].due)
    assert a[0].prompt.tolist() != other[0].prompt.tolist()
    sizes = lambda rs: [(r.prompt.size, r.max_new, r.budget) for r in rs]
    if "order_seed" in c["arrivals"]:
        # ... and in the same order
        assert sizes(a) == sizes(other)
    else:
        assert sizes(a) != sizes(other)


def test_run_seed_orders_without_order_seed():
    c = cell("paged-int8-rag-backlog")
    del c["arrivals"]["order_seed"]
    a = traffic.generate(c, 40, 3_000_000_019, 152064)
    b = traffic.generate(c, 40, 7, 152064)
    assert [r.prompt.size for r in a] != [r.prompt.size for r in b]


def test_lengths_clip_and_buckets():
    x = traffic.lognormal_lengths(400, 256, 0.6, 16, 1024,
                                  [128, 256, 512, 1024])
    assert set(x) <= {128, 256, 512, 1024}
    assert np.median(x) == 256
    y = traffic.lognormal_lengths(400, 48, 0.6, 16, 128)
    assert y.min() >= 16 and y.max() <= 128


def test_bursty_rate():
    spec = {"kind": "bursty", "rate": 2.0, "period_s": 10.0,
            "burst_at_s": 4.0, "burst_s": 2.0, "burst_factor": 4.0}
    t = traffic.arrivals(spec, 4000, np.random.default_rng(0))
    assert traffic.mean_rate(spec) == pytest.approx(3.2)
    ph = np.mod(t, 10.0)
    in_burst = np.mean((ph >= 4.0) & (ph < 6.0))
    # a 2 s burst at 4x holds 8 of every 16 units of arrivals
    assert in_burst == pytest.approx(0.5, abs=0.03)
    assert t[-1] / 4000 == pytest.approx(1 / 3.2, rel=0.05)


def test_offline_backlog_due_at_open():
    c = cell("elastic-b10-offline")
    reqs = traffic.generate(c, 40, 1, 152064)
    assert len(reqs) == c["arrivals"]["backlog"]
    assert all(r.due == 0.0 for r in reqs)


def test_backlog_blocks_hold_the_same_work():
    c = cell("paged-int8-rag-backlog")
    block = c["arrivals"]["block"]
    del c["arrivals"]["order_seed"]
    for seed in (3_000_000_019, 7):
        reqs = traffic.generate(c, 40, seed, 152064)
        assert len(reqs) == c["arrivals"]["backlog"]
        first = [(sorted(r.prompt.size for r in reqs[i:i + block]),
                  sorted(r.max_new for r in reqs[i:i + block]))
                 for i in range(0, len(reqs), block)]
        assert all(f == first[0] for f in first)


def test_classes_share_every_block():
    c = cell("elastic-b05-chat")
    block = c["arrivals"]["block"]
    reqs = traffic.generate(c, 40, 3_000_000_019, 152064)
    want = {float(k["budget"]): k["share"] for k in c["classes"]}
    for i in range(0, len(reqs), block):
        got = [r.budget for r in reqs[i:i + block]]
        assert {b: got.count(b) for b in set(got)} == want
    hi = next(k for k in c["classes"] if k["budget"] == 1.0)["output"]["hi"]
    assert all(r.max_new <= hi for r in reqs if r.budget == 1.0)
    warm = traffic.warmup(c, 1, 152064)
    assert sorted({(r.prompt.size, r.budget) for r in warm}) == sorted(
        (n, b) for n in c["prompt"]["buckets"] for b in want)
