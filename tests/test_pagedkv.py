"""Paged KV cache subsystem: page pool accounting, prefix sharing, chunked
prefill, CoW forks, and preemption-by-page-pressure.

Core acceptance properties:

* The paged engine is TOKEN-FOR-TOKEN identical to the ring engine on a
  mixed-budget staggered workload (greedy and seeded sampling) — the page
  indirection is a memory-layout change, never a numerics change.
* ``compile_counts() == {prefill: 1, decode: 1}`` for ANY mix of prompt
  lengths: chunked prefill collapses the ring engine's per-length prefill
  buckets into one graph.
* Pages are refcounted: prefix-sharing increfs survive until the LAST
  holder frees (cancel / EOS / length), then the pool drains to empty.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import ElasticConfig, get_config
from repro.models import model_init, router_init
from repro.runtime.pagedkv import PagePool, n_pages_for, prefix_keys
from repro.training import GenRequest, ServingEngine
from tests.conftest import f32

# dense MLP: paged mode excludes moefied experts (expert-capacity buffers
# depend on the prefill chunking — see ServingEngine._validate_paged)
DENSE_KW = dict(mlp_token_capacity=0.5, mha_token_capacity=0.5,
                mha_head_topk=2, lora_rank=1)


@pytest.fixture(scope="module")
def setup():
    key = jax.random.PRNGKey(0)
    cfg = f32(get_config("toy-lm", "smoke"))
    ecfg = ElasticConfig(**DENSE_KW)
    params = model_init(key, cfg, ecfg)
    rp = router_init(jax.random.fold_in(key, 1), cfg, ecfg)
    return cfg, ecfg, params, rp


@pytest.fixture(scope="module")
def ring(setup):
    cfg, ecfg, params, rp = setup
    return ServingEngine(params, rp, cfg, ecfg, mode="infer",
                         batch_size=2, max_seq=64)


@pytest.fixture(scope="module")
def paged(setup):
    cfg, ecfg, params, rp = setup
    return ServingEngine(params, rp, cfg, ecfg, mode="infer",
                         batch_size=2, max_seq=64,
                         kv_layout="paged", page_size=8)


def _drain(eng, handles):
    while not all(h.done for h in handles):
        if eng.step() == 0:
            raise RuntimeError("engine stalled")


# ------------------------------ pool (unit) ----------------------------------

def test_pool_alloc_free_refcount():
    pool = PagePool(8, page_size=4, n_replicas=2)
    assert pool.pages_per_replica == 4 and pool.usable_per_replica == 3
    # last id of each replica range is the trash page, never allocatable
    assert pool.trash_page(0) == 3 and pool.trash_page(1) == 7
    a = pool.alloc(0, 3)
    assert sorted(a) == [0, 1, 2] and pool.alloc(0, 1) is None
    assert pool.can_alloc(1, 3) and not pool.can_alloc(1, 4)
    b = pool.alloc(1, 2)
    assert all(pool.replica_of(p) == 1 for p in b)
    pool.incref(a[0])
    pool.free(a)                      # a[0] survives at refcount 1
    assert pool.allocated == 3 and pool.n_free(0) == 2
    pool.free([a[0]])
    assert pool.n_free(0) == 3
    with pytest.raises(RuntimeError, match="double free"):
        pool.free([a[0], a[0]])
    st = pool.stats()
    assert st["allocated"] == 2 and st["peak_allocated"] == 5


def test_pool_prefix_registry_purged_on_free():
    pool = PagePool(4, page_size=4)
    [p] = pool.alloc(0, 1)
    pool.register_prefix("k1", p)
    assert pool.lookup_prefix("k1", 0) == p
    assert pool.lookup_prefix("k1", 1) is None   # replica-local lookups
    pool.incref(p)
    pool.free([p])
    assert pool.lookup_prefix("k1", 0) == p      # still held by one ref
    pool.free([p])
    assert pool.lookup_prefix("k1", 0) is None   # last free purges the key
    assert pool.stats()["registered_prefixes"] == 0


def test_prefix_keys_chain_and_namespace():
    toks = list(range(20))
    ks = prefix_keys(toks, 8)
    assert len(ks) == 2                  # only FULL pages get keys
    # chained: a diverging EARLIER block changes every later key
    ks2 = prefix_keys([99] + toks[1:], 8)
    assert ks2[0] != ks[0] and ks2[1] != ks[1]
    # same prefix, later divergence: shared head key, distinct tail key
    ks3 = prefix_keys(toks[:8] + [99] + toks[9:], 8)
    assert ks3[0] == ks[0] and ks3[1] != ks[1]
    # the routing namespace (mode/budget/theta) splits the key space
    assert prefix_keys(toks, 8, namespace=("infer", 0.5, 0.5)) != \
        prefix_keys(toks, 8, namespace=("infer", 1.0, 0.5))
    assert n_pages_for(0, 8) == 0 and n_pages_for(1, 8) == 1 \
        and n_pages_for(8, 8) == 1 and n_pages_for(9, 8) == 2


# ----------------------- engine: parity + compile flatness -------------------

def test_paged_matches_ring_staggered_mixed_budgets(setup, ring, paged):
    """4 distinct prompt lengths, mixed budgets + one sampled row, admitted
    staggered into 2 slots: every output bit-matches the ring engine's solo
    run AND the chunked prefill keeps ONE compile across all lengths."""
    cfg, ecfg, params, rp = setup
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, L, dtype=np.int32)
               for L in (5, 13, 16, 29)]
    reqs = [GenRequest(prompts[0], 6, budget=0.4),
            GenRequest(prompts[1], 6, budget=1.0),
            GenRequest(prompts[2], 6),
            GenRequest(prompts[3], 6, temperature=0.8, top_k=4, seed=11)]
    oracle = [ring.generate([r])[0] for r in reqs]
    h0 = paged.submit(reqs[0])
    paged.step(); paged.step()            # r0 mid-flight when r1 lands
    h1 = paged.submit(reqs[1])
    paged.step()
    h2, h3 = paged.submit(reqs[2]), paged.submit(reqs[3])
    handles = [h0, h1, h2, h3]
    _drain(paged, handles)
    for h, o in zip(handles, oracle):
        np.testing.assert_array_equal(np.asarray(h.output), o)
    assert paged.compile_counts() == {"prefill": 1, "decode": 1}
    st = paged.paged_stats()
    assert st["allocated"] == 0 and st["free"] == st["usable"]


def test_prefix_sharing_refcounts_and_parity(setup, ring, paged):
    """Two live requests with a common 16-token prefix share its 2 full
    pages physically; outputs still match solo runs; the pool drains to
    zero after both finish (refcounted frees)."""
    cfg, ecfg, params, rp = setup
    rng = np.random.default_rng(1)
    pre = rng.integers(0, cfg.vocab_size, 16, dtype=np.int32)
    a = np.concatenate([pre, rng.integers(0, cfg.vocab_size, 4,
                                          dtype=np.int32)])
    b = np.concatenate([pre, rng.integers(0, cfg.vocab_size, 4,
                                          dtype=np.int32)])
    h1 = paged.submit(GenRequest(a, 4, budget=0.5))
    paged.step()
    h2 = paged.submit(GenRequest(b, 4, budget=0.5))
    paged.step()
    st = paged.paged_stats()
    assert st["shared"] == 2              # 16-token prefix @ page_size 8
    _drain(paged, [h1, h2])
    np.testing.assert_array_equal(
        np.asarray(h1.output), ring.generate([GenRequest(a, 4, budget=0.5)])[0])
    np.testing.assert_array_equal(
        np.asarray(h2.output), ring.generate([GenRequest(b, 4, budget=0.5)])[0])
    assert paged.paged_stats()["allocated"] == 0
    # different budgets must NOT share (namespaced keys: the token gate's
    # keep decisions — hence the page bytes — depend on the solved policy)
    h3 = paged.submit(GenRequest(a, 2, budget=0.5))
    paged.step()
    h4 = paged.submit(GenRequest(a, 2, budget=1.0))
    paged.step()
    assert paged.paged_stats()["shared"] == 0
    _drain(paged, [h3, h4])


def test_cancel_returns_shared_pages(setup, paged):
    cfg, ecfg, params, rp = setup
    rng = np.random.default_rng(2)
    p = rng.integers(0, cfg.vocab_size, 20, dtype=np.int32)
    h1 = paged.submit(GenRequest(p, 8, budget=0.5))
    paged.step()
    h2 = paged.submit(GenRequest(p, 8, budget=0.5))
    paged.step()
    assert paged.paged_stats()["shared"] == 2
    assert paged.cancel(h1)
    # h2 still holds the shared pages: nothing recycled out from under it
    assert paged.paged_stats()["shared"] == 0
    assert paged.paged_stats()["allocated"] > 0
    assert paged.cancel(h2)
    assert paged.paged_stats()["allocated"] == 0


def test_fork_cow_bit_matches_independent_run(setup, ring, paged):
    """fork() mid-decode: the child shares full history pages, deep-copies
    only the partial tail (CoW), and — greedy — must emit EXACTLY what an
    independent request with prompt + parent-output-so-far emits."""
    cfg, ecfg, params, rp = setup
    rng = np.random.default_rng(3)
    p = rng.integers(0, cfg.vocab_size, 11, dtype=np.int32)
    hp = paged.submit(GenRequest(p, 10, budget=0.7))
    for _ in range(5):
        paged.step()
    prefix_out = list(hp.output)
    assert 0 < len(prefix_out) < 10
    hc = paged.fork(hp)
    _drain(paged, [hp, hc])
    indep = ring.generate([GenRequest(
        np.concatenate([p, np.asarray(prefix_out, np.int32)]),
        10 - len(prefix_out), budget=0.7)])[0]
    np.testing.assert_array_equal(np.asarray(hc.output), indep)
    # greedy parent continues identically (fork never perturbs the parent)
    np.testing.assert_array_equal(
        np.asarray(hp.output[len(prefix_out):]), indep)
    assert paged.paged_stats()["allocated"] == 0
    with pytest.raises(ValueError, match="running"):
        paged.fork(hp)                    # finished requests cannot fork


def test_preemption_by_page_pressure_resumes_exactly(setup, ring):
    """A pool too small for two full-length requests forces an eviction;
    the preempted request re-queues as a continuation and still emits its
    solo-run tokens (position-keyed sampling + prompt+output re-prefill)."""
    cfg, ecfg, params, rp = setup
    rng = np.random.default_rng(4)
    reqs = [GenRequest(rng.integers(0, cfg.vocab_size, 24, dtype=np.int32),
                       10, budget=0.8) for _ in range(2)]
    oracle = [ring.generate([r])[0] for r in reqs]
    # 8 usable pages + 1 trash; each request needs ceil(34/8) = 5 pages at
    # full length, so both fit initially (3+3) but collide as they grow
    tiny = ServingEngine(params, rp, cfg, ecfg, mode="infer", batch_size=2,
                         max_seq=64, kv_layout="paged", page_size=8,
                         n_pages=9)
    handles = [tiny.submit(r) for r in reqs]
    steps = 0
    while not all(h.done for h in handles):
        assert tiny.step() > 0, "stalled"
        steps += 1
        assert steps < 200
    for h, o in zip(handles, oracle):
        np.testing.assert_array_equal(np.asarray(h.output), o)
    assert tiny.paged_stats()["allocated"] == 0


def test_paged_validation(setup):
    cfg, ecfg, params, rp = setup
    moe = dataclasses.replace(ecfg, mlp_n_experts=4, mlp_expert_topk=2)
    with pytest.raises(ValueError, match="dense MLP"):
        ServingEngine(params, rp, cfg, moe, mode="infer",
                      batch_size=2, max_seq=32, kv_layout="paged")
    with pytest.raises(ValueError, match="kv_layout"):
        ServingEngine(params, rp, cfg, ecfg, batch_size=2, max_seq=32,
                      kv_layout="blocked")
    with pytest.raises(ValueError, match="infer/base"):
        ServingEngine(params, rp, cfg, ecfg, mode="train",
                      batch_size=2, max_seq=32, kv_layout="paged")
    eng = ServingEngine(params, rp, cfg, ecfg, mode="infer", batch_size=2,
                        max_seq=32, kv_layout="paged", page_size=8,
                        n_pages=4)               # 3 usable + 1 trash
    p = np.arange(30, dtype=np.int32)
    with pytest.raises(ValueError, match="pages"):
        eng.submit(GenRequest(p, 2))             # needs 4 pages > 3 usable


# ------------------------- int8 cache bit-stability --------------------------

def test_int8_fork_and_preemption_bit_stable(setup):
    """int8 KV cache (docs/quantization.md): fork CoW and preemption replay
    are BIT-stable. Rows are quantized once at the write site, so a CoW
    deep-copied tail page and a re-prefilled continuation hold exactly the
    bytes an independent int8 solo run produces — greedy outputs match
    token-for-token across ring/paged layouts and across evictions."""
    cfg, ecfg, params, rp = setup
    kw = dict(mode="infer", batch_size=2, max_seq=64,
              kv_dtype="int8", weight_dtype="int8")
    ring8 = ServingEngine(params, rp, cfg, ecfg, **kw)
    paged8 = ServingEngine(params, rp, cfg, ecfg, kv_layout="paged",
                           page_size=8, **kw)
    rng = np.random.default_rng(5)
    p = rng.integers(0, cfg.vocab_size, 11, dtype=np.int32)
    # ---- fork mid-decode: child == independent int8 run ----
    hp = paged8.submit(GenRequest(p, 10, budget=0.7))
    for _ in range(5):
        paged8.step()
    prefix_out = list(hp.output)
    assert 0 < len(prefix_out) < 10
    hc = paged8.fork(hp)
    _drain(paged8, [hp, hc])
    indep = ring8.generate([GenRequest(
        np.concatenate([p, np.asarray(prefix_out, np.int32)]),
        10 - len(prefix_out), budget=0.7)])[0]
    np.testing.assert_array_equal(np.asarray(hc.output), indep)
    np.testing.assert_array_equal(
        np.asarray(hp.output[len(prefix_out):]), indep)
    assert paged8.paged_stats()["allocated"] == 0
    # ---- preemption under page pressure: replay == solo paged int8 run ----
    # (the oracle is paged: a ring prefill attends f32 K/V, so ring-vs-
    # paged int8 is bounded-error, not bitwise — docs/quantization.md)
    reqs = [GenRequest(rng.integers(0, cfg.vocab_size, 24, dtype=np.int32),
                       10, budget=0.8) for _ in range(2)]
    oracle = [paged8.generate([r])[0] for r in reqs]
    tiny = ServingEngine(params, rp, cfg, ecfg, kv_layout="paged",
                         page_size=8, n_pages=9, **kw)
    handles = [tiny.submit(r) for r in reqs]
    steps = 0
    while not all(h.done for h in handles):
        assert tiny.step() > 0, "stalled"
        steps += 1
        assert steps < 200
    for h, o in zip(handles, oracle):
        np.testing.assert_array_equal(np.asarray(h.output), o)
    assert tiny.paged_stats()["allocated"] == 0
