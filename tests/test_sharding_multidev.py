"""Multi-device sharding tests: run a real pjit distillation step, an
elastic re-mesh, and the SPMD serving engine on 8 fake CPU devices
(subprocess, so the main test process keeps 1 device). Proves the sharding
rules + shard_map distill loss + elastic resharding + sharded continuous
batching actually execute SPMD, not just lower."""
import os
import subprocess
import sys

import pytest


def _run_spmd_script(script: str):
    env = dict(os.environ)
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(root, "src"), root])
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.configs import get_config, get_elastic
from repro.models import model_init, router_init, forward
from repro.runtime import sharding as SH
from repro.runtime.elastic import make_mesh, rescale_training_state
from repro.training import init_train_state, make_train_step
from repro.optim import cosine_schedule

cfg = dataclasses.replace(get_config("qwen2-7b", "smoke"), dtype="float32")
ecfg = get_elastic("qwen2-7b", cfg)
key = jax.random.PRNGKey(0)
params = model_init(key, cfg, ecfg)
rp = router_init(jax.random.fold_in(key, 1), cfg, ecfg)
batch = {"tokens": jax.random.randint(key, (4, 32), 0, cfg.vocab_size)}

# ---- single device reference ----
step_ref = make_train_step(cfg, ecfg, lr=cosine_schedule(1e-3, 10), mesh=None)
s_ref, m_ref = jax.jit(step_ref)(init_train_state(rp), params, batch)

# ---- 2x4 mesh SPMD ----
mesh = make_mesh((2, 4), ("data", "model"))
p_sh = jax.tree.map(lambda x, s: jax.device_put(x, s), params,
                    SH.param_shardings(params, mesh))
b_sh = {"tokens": jax.device_put(batch["tokens"],
                                 NamedSharding(mesh, P("data", None)))}
step = make_train_step(cfg, ecfg, lr=cosine_schedule(1e-3, 10), mesh=mesh)
with mesh:
    s_spmd, m_spmd = jax.jit(step)(init_train_state(rp), p_sh, b_sh)
# distill loss is exact under SPMD (distributed top-50 KL is exact math);
# the load-balance loss uses PER-SHARD batch statistics under the
# per-block shard_map (GShard-style per-group load loss: a mean of
# products != product of means), so total loss matches only loosely.
a, b = float(m_ref["distill"]), float(m_spmd["distill"])
assert abs(a - b) / max(abs(a), 1e-6) < 5e-3, ("distill", a, b)
a, b = float(m_ref["loss"]), float(m_spmd["loss"])
assert abs(a - b) / max(abs(a), 1e-6) < 5e-2, ("loss", a, b)

# updates point the same way (load-loss grads differ per-shard slightly)
va = jnp.concatenate([x.ravel() for x in jax.tree.leaves(s_ref.router_params)])
vb = jnp.concatenate([x.ravel() for x in jax.tree.leaves(s_spmd.router_params)])
cos = float(jnp.sum(va * vb) / (jnp.linalg.norm(va) * jnp.linalg.norm(vb)))
assert cos > 0.999, f"router update cos {cos}"

# ---- elastic re-mesh: 8 -> 4 devices ----
mesh2 = make_mesh((1, 4), ("data", "model"))
p2, rp2, opt2 = rescale_training_state(
    params, s_spmd.router_params, s_spmd.opt, mesh2)
b2 = {"tokens": jax.device_put(batch["tokens"],
                               NamedSharding(mesh2, P("data", None)))}
step2 = make_train_step(cfg, ecfg, lr=cosine_schedule(1e-3, 10), mesh=mesh2)
from repro.training import TrainState
with mesh2:
    s3, m3 = jax.jit(step2)(TrainState(rp2, opt2, None), p2, b2)
assert np.isfinite(float(m3["loss"]))
print("SPMD-OK", float(m_ref["loss"]), float(m_spmd["loss"]), float(m3["loss"]))
"""


@pytest.mark.slow
def test_spmd_matches_single_device_and_elastic_remesh(tmp_path):
    assert "SPMD-OK" in _run_spmd_script(_SCRIPT)


_SERVE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp
import numpy as np
from repro.configs import ElasticConfig, get_config
from repro.models import model_init, router_init
from repro.runtime.elastic import make_mesh, valid_mesh_shapes
from repro.training import GenRequest, ServingEngine

cfg = dataclasses.replace(get_config("toy-lm", "smoke"), dtype="float32")
ecfg = ElasticConfig(mlp_token_capacity=0.5, mha_token_capacity=0.5,
                     mha_head_topk=2, mlp_n_experts=4, mlp_expert_topk=2,
                     lora_rank=1)
key = jax.random.PRNGKey(0)
params = model_init(key, cfg, ecfg)
rp = router_init(jax.random.fold_in(key, 1), cfg, ecfg)
rng = np.random.default_rng(0)
prompts = [rng.integers(0, cfg.vocab_size, 8, dtype=np.int32)
           for _ in range(4)]
reqs = [GenRequest(prompts[0], 6, budget=0.4),       # mixed budgets...
        GenRequest(prompts[1], 6, budget=1.0),
        GenRequest(prompts[2], 6),                   # ...engine default...
        GenRequest(prompts[3], 6, temperature=0.8, top_k=4, seed=11)]

# oracle: the single-device engine serving each request alone
solo = ServingEngine(params, rp, cfg, ecfg, mode="infer", batch_size=2,
                     max_seq=24)
oracle = [solo.generate([r])[0] for r in reqs]

# ---- sharded engine, staggered admissions, 2x4 (data, model) mesh ----
mesh = make_mesh((2, 4), ("data", "model"))
eng = ServingEngine(params, rp, cfg, ecfg, mode="infer", batch_size=4,
                    max_seq=24, mesh=mesh)
assert eng.scheduler.n_replicas == 2
h0 = eng.submit(reqs[0])
eng.step(); eng.step()            # r0 is 2 tokens in when r1 lands
h1 = eng.submit(reqs[1])
eng.step()
h2, h3 = eng.submit(reqs[2]), eng.submit(reqs[3])
handles = [h0, h1, h2, h3]
while not all(h.done for h in handles):
    eng.step()
assert eng.compile_counts() == {"prefill": 1, "decode": 1}, \
    eng.compile_counts()
# admission spread across BOTH replicas (least-loaded placement)
assert {eng.scheduler.replica_of(h.slot) for h in handles} == {0, 1}
for h, o in zip(handles, oracle):     # token-for-token vs single device
    np.testing.assert_array_equal(np.asarray(h.output), o)
print("SERVE-PARITY-OK")

# ---- donation survives SPMD: the sharded caches alias through the jits ----
from repro.launch.hloprof import input_output_alias
dec = eng.entry_points()["decode"]
n_donated = sum(len(jax.tree.leaves(dec.args[i])) for i in dec.donated)
with mesh:
    alias = input_output_alias(
        dec.fn.lower(*dec.args, **dec.static).compile().as_text())
assert len(alias) >= n_donated, (alias, n_donated)
print("SPMD-DONATE-OK")

# ---- live re-mesh mid-flight: 2x4 -> 1x4, identical greedy tokens ----
assert (1, 4) in valid_mesh_shapes(4, 4)
eng2 = ServingEngine(params, rp, cfg, ecfg, mode="infer", batch_size=4,
                     max_seq=24, mesh=mesh)
hs = [eng2.submit(r) for r in reqs]
eng2.step(); eng2.step()          # all four in flight, mid-generation
eng2.reshard(make_mesh((1, 4), ("data", "model")))
assert eng2.scheduler.n_replicas == 1
while not all(h.done for h in hs):
    eng2.step()
assert eng2.compile_counts() == {"prefill": 0, "decode": 1}  # post-remesh
for h, o in zip(hs, oracle):
    np.testing.assert_array_equal(np.asarray(h.output), o)
print("REMESH-OK")

# ---- one RoutingPlan sort per block still holds under the mesh ----
from repro.core import routing as R
from repro.core.policy import ElasticPolicy, ElasticSpec
spec = ElasticSpec(mha_token_routed=True, mlp_token_routed=True)
sp_params = model_init(key, cfg, spec)
sp_rp = router_init(jax.random.fold_in(key, 1), cfg, spec)
from repro.models import forward
pol = ElasticPolicy.uniform(0.5, static=True)
batch = {"tokens": jnp.zeros((4, 32), jnp.int32)}
with mesh:
    before = R.PLAN_SORT_COUNT
    jax.jit(lambda rp, b: forward(sp_params, rp, b, cfg, spec, mode="train",
                                  policy=pol)[0]).lower(sp_rp, batch)
    assert R.PLAN_SORT_COUNT - before == 1, (R.PLAN_SORT_COUNT, before)
print("ONE-SORT-OK")

# ---- kernel dispatch lowers PER-SHARD under shard_map ----
# monkeypatch the kernel entry points (ops dispatches via module
# attributes) to record the shapes each shard's kernel call sees
from repro.kernels import ops as OPS
_dec = OPS._decode_mod
_fm = OPS._fused_mlp_mod
from repro.kernels import ref as KREF

B, L, H, K, Dh = 4, 16, 8, 4, 8
q = jax.random.normal(key, (B, 1, H, Dh), jnp.float32)
kc = jax.random.normal(jax.random.fold_in(key, 2), (B, L, K, Dh),
                       jnp.float32)
vc = jax.random.normal(jax.random.fold_in(key, 3), (B, L, K, Dh),
                       jnp.float32)
pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
t = jnp.asarray([3, 7, 5, 9], jnp.int32)
valid = pos <= t[:, None]

seen = []
orig = _dec.decode_attention
def probe(q, k, v, kv_pos, t, **kw):
    seen.append(q.shape)
    return orig(q, k, v, kv_pos, t, **kw)
_dec.decode_attention = probe
with mesh:
    got = jax.jit(lambda *a: OPS.decode_attention_sharded(
        *a, window=0, backend="interpret"))(q, kc, vc, pos, t, valid)
_dec.decode_attention = orig
# the kernel grid saw the LOCAL block: batch/data x heads/model
assert (B // 2, 1, H // 4, Dh) in seen, seen
np.testing.assert_allclose(
    np.asarray(got),
    np.asarray(KREF.decode_attention_ref(q, kc, vc, pos, t,
                                         kv_valid=valid)),
    rtol=1e-5, atol=1e-5)

S, D, F = 16, 8, 32
x = jax.random.normal(key, (B, S, D), jnp.float32)
wi = jax.random.normal(jax.random.fold_in(key, 4), (D, F), jnp.float32) * .1
wo = jax.random.normal(jax.random.fold_in(key, 5), (F, D), jnp.float32) * .1
wg = jax.random.normal(jax.random.fold_in(key, 6), (D, F), jnp.float32) * .1

# prefill flash attention, the dense fused MLP and the grouped expert
# matmul: Mosaic refuses to auto-partition a kernel, so under a mesh each
# runs per shard too (heads / the FFN dim over `model`, batch over data)
def per_shard(mod, name, wrapper, args, kw, want):
    seen, orig = [], getattr(mod, name)
    def probe(*a, **k):
        seen.append(tuple(getattr(v, "shape", None) for v in a[:3]))
        return orig(*a, **k)
    setattr(mod, name, probe)
    with mesh:
        got = jax.jit(lambda *a: wrapper(*a, **kw))(*args)
    setattr(mod, name, orig)
    assert want in seen, seen
    return got

qf = jax.random.normal(key, (B, S, H, Dh), jnp.float32)
kf = jax.random.normal(jax.random.fold_in(key, 7), (B, S, K, Dh))
vf = jax.random.normal(jax.random.fold_in(key, 8), (B, S, K, Dh))
got = per_shard(OPS._flash_mod, "flash_attention", OPS.flash_attention_sharded,
                (qf, kf, vf), dict(causal=True, backend="interpret"),
                ((B // 2, S, H // 4, Dh), (B // 2, S, K // 4, Dh),
                 (B // 2, S, K // 4, Dh)))
np.testing.assert_allclose(
    np.asarray(got), np.asarray(KREF.flash_attention_ref(qf, kf, vf)),
    rtol=1e-5, atol=1e-5)
got = per_shard(_fm, "fused_mlp", OPS.fused_mlp_sharded,
                (x, wi, wo, wg), dict(backend="interpret"),
                ((B // 2, S, D), (D, F // 4), (F // 4, D)))
np.testing.assert_allclose(
    np.asarray(got), np.asarray(KREF.fused_mlp_ref(x, wi, wo, wg)),
    rtol=1e-4, atol=1e-5)
E, C = 2, 8
xe = jax.random.normal(key, (B, E, C, D), jnp.float32)
wie = jax.random.normal(jax.random.fold_in(key, 9), (E, D, F)) * .1
woe = jax.random.normal(jax.random.fold_in(key, 10), (E, F, D)) * .1
got = per_shard(OPS._moe_gmm_mod, "moe_gmm", OPS.moe_gmm_sharded,
                (xe, wie, woe), dict(act="gelu", backend="interpret"),
                ((B // 2, E, C, D), (E, D, F // 4), (E, F // 4, D)))
np.testing.assert_allclose(
    np.asarray(got), np.asarray(KREF.moe_gmm_ref(xe, wie, woe, act="gelu")),
    rtol=1e-4, atol=1e-5)
print("KERNEL-SHARD-OK")
"""


_PAGED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp
import numpy as np
from repro.configs import ElasticConfig, get_config
from repro.models import model_init, router_init
from repro.runtime.elastic import make_mesh
from repro.training import GenRequest, ServingEngine

cfg = dataclasses.replace(get_config("toy-lm", "smoke"), dtype="float32")
# dense MLP: paged mode excludes moefied experts (chunk-parity contract)
ecfg = ElasticConfig(mlp_token_capacity=0.5, mha_token_capacity=0.5,
                     mha_head_topk=2, lora_rank=1)
key = jax.random.PRNGKey(0)
params = model_init(key, cfg, ecfg)
rp = router_init(jax.random.fold_in(key, 1), cfg, ecfg)
rng = np.random.default_rng(0)
# FOUR distinct prompt lengths: the chunked prefill must hold ONE compile.
# All-greedy rows: cross-mesh token parity is a GREEDY contract (the TP
# all-reduce changes float association by ~1e-6, which gumbel-perturbed
# sampling can amplify into a different token — same as the ring engine).
reqs = [GenRequest(rng.integers(0, cfg.vocab_size, L, dtype=np.int32), 6,
                   budget=b)
        for L, b in ((5, 0.4), (13, 1.0), (16, None), (29, 0.6))]

# oracle: single-device RING engine serving each request alone
solo = ServingEngine(params, rp, cfg, ecfg, mode="infer", batch_size=2,
                     max_seq=48)
oracle = [solo.generate([r])[0] for r in reqs]

# ---- paged engine, staggered admissions, 2x4 (data, model) mesh ----
mesh = make_mesh((2, 4), ("data", "model"))
eng = ServingEngine(params, rp, cfg, ecfg, mode="infer", batch_size=4,
                    max_seq=48, mesh=mesh, kv_layout="paged", page_size=8)
assert eng.scheduler.n_replicas == 2
h0 = eng.submit(reqs[0])
eng.step(); eng.step()            # r0 is 2 tokens in when r1 lands
h1 = eng.submit(reqs[1])
eng.step()
h2, h3 = eng.submit(reqs[2]), eng.submit(reqs[3])
handles = [h0, h1, h2, h3]
while not all(h.done for h in handles):
    assert eng.step() > 0
assert eng.compile_counts() == {"prefill": 1, "decode": 1}, \
    eng.compile_counts()
# admissions spread over BOTH replicas; page ids stay replica-local
assert {eng.scheduler.replica_of(h.slot) for h in handles} == {0, 1}
for h, o in zip(handles, oracle):     # token-for-token vs 1-device ring
    np.testing.assert_array_equal(np.asarray(h.output), o)
st = eng.paged_stats()
assert st["allocated"] == 0 and st["free"] == st["usable"], st
print("PAGED-SPMD-PARITY-OK")

# ---- prefix sharing + CoW fork still exact on the mesh ----
pre = rng.integers(0, cfg.vocab_size, 16, dtype=np.int32)
a = np.concatenate([pre, rng.integers(0, cfg.vocab_size, 4, dtype=np.int32)])
hp = eng.submit(GenRequest(a, 8, budget=0.5))
for _ in range(3):
    eng.step()
head = list(hp.output)
hc = eng.fork(hp)
while not (hp.done and hc.done):
    assert eng.step() > 0
ind = solo.generate([GenRequest(
    np.concatenate([a, np.asarray(head, np.int32)]), 8 - len(head),
    budget=0.5)])[0]
np.testing.assert_array_equal(np.asarray(hc.output), ind)
np.testing.assert_array_equal(np.asarray(hp.output[len(head):]), ind)
assert eng.paged_stats()["allocated"] == 0
print("PAGED-SPMD-FORK-OK")
"""


@pytest.mark.slow
def test_paged_kv_spmd_parity(tmp_path):
    """Paged-KV acceptance on the production mesh: on a 2x4 (data, model)
    mesh the paged engine (block-paged pool, chunked prefill, per-replica
    page ranges) is token-for-token identical to the single-device ring
    engine across four distinct prompt lengths with ONE prefill compile,
    and a mid-decode CoW fork bit-matches an independent run."""
    out = _run_spmd_script(_PAGED_SCRIPT)
    for tag in ("PAGED-SPMD-PARITY-OK", "PAGED-SPMD-FORK-OK"):
        assert tag in out, out


@pytest.mark.slow
def test_sharded_serving_parity_and_live_remesh(tmp_path):
    """ISSUE 5 acceptance: on a 2x4 (data, model) mesh of 8 fake CPU
    devices, the sharded ServingEngine is token-for-token identical to the
    single-device engine on a mixed-budget staggered workload with flat
    compile counts; a mid-run reshard resumes with identical greedy tokens;
    RoutingPlan stays one-sort-per-block under the mesh; and the Pallas
    kernel entry points lower per-shard under shard_map."""
    out = _run_spmd_script(_SERVE_SCRIPT)
    for tag in ("SERVE-PARITY-OK", "SPMD-DONATE-OK", "REMESH-OK",
                "ONE-SORT-OK", "KERNEL-SHARD-OK"):
        assert tag in out, out


_QUANT_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax
import numpy as np
from repro.configs import ElasticConfig, get_config
from repro.models import model_init, router_init
from repro.runtime.elastic import make_mesh
from repro.training import GenRequest, ServingEngine

cfg = dataclasses.replace(get_config("toy-lm", "smoke"), dtype="float32")
ecfg = ElasticConfig(mlp_token_capacity=0.5, mha_token_capacity=0.5,
                     mha_head_topk=2, lora_rank=1)
key = jax.random.PRNGKey(0)
params = model_init(key, cfg, ecfg)
rp = router_init(jax.random.fold_in(key, 1), cfg, ecfg)
rng = np.random.default_rng(0)
# all-greedy rows: cross-mesh token parity is a greedy contract
reqs = [GenRequest(rng.integers(0, cfg.vocab_size, L, dtype=np.int32), 6,
                   budget=b)
        for L, b in ((5, 0.4), (13, 1.0), (16, None), (29, 0.6))]
kw = dict(mode="infer", max_seq=48, kv_dtype="int8", weight_dtype="int8")

# oracle: single-device int8 RING engine serving each request alone
solo = ServingEngine(params, rp, cfg, ecfg, batch_size=2, **kw)
oracle = [solo.generate([r])[0] for r in reqs]

# ---- int8 paged engine on the 2x4 production mesh, staggered ----
mesh = make_mesh((2, 4), ("data", "model"))
eng = ServingEngine(params, rp, cfg, ecfg, batch_size=4, mesh=mesh,
                    kv_layout="paged", page_size=8, **kw)
assert eng.scheduler.n_replicas == 2
h0 = eng.submit(reqs[0])
eng.step(); eng.step()            # r0 is 2 tokens in when r1 lands
h1 = eng.submit(reqs[1])
eng.step()
h2, h3 = eng.submit(reqs[2]), eng.submit(reqs[3])
handles = [h0, h1, h2, h3]
while not all(h.done for h in handles):
    assert eng.step() > 0
assert eng.compile_counts() == {"prefill": 1, "decode": 1}, \
    eng.compile_counts()
assert {eng.scheduler.replica_of(h.slot) for h in handles} == {0, 1}
# the 1-device int8 ring engine's tokens, until a near-tie in its logits
# (int8 paths quantize different f32 values; the mesh reorders sums)
from tests.conftest import assert_tokens_match_until_near_tie
for r, h, o in zip(reqs, handles, oracle):
    assert_tokens_match_until_near_tie(solo, r, h.output, o)
st = eng.paged_stats()
assert st["allocated"] == 0 and st["free"] == st["usable"], st
# the int8 pools AND their f32 scale siblings live on the mesh (the
# sharding pins cover both leaves — docs/quantization.md)
from jax.sharding import NamedSharding
leaves = jax.tree.leaves(eng._caches)
assert any(str(l.dtype) == "int8" for l in leaves), \
    sorted({str(l.dtype) for l in leaves})
for l in leaves:
    assert isinstance(l.sharding, NamedSharding), l.sharding
print("QUANT-SPMD-PARITY-OK")
"""


_DEPTH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax
import numpy as np
from repro.configs import ElasticConfig, get_config
from repro.models import model_init, router_init
from repro.runtime.elastic import make_mesh
from repro.training import GenRequest, ServingEngine

cfg = dataclasses.replace(get_config("toy-lm", "smoke"), dtype="float32")
# depth router live: per-(slot, layer) whole-block skips, so decode writes
# NO KV at skipped layers — the per-layer KV-validity masks must keep
# staggered neighbors exact across the replicas
ecfg = ElasticConfig(mlp_token_capacity=0.5, mha_token_capacity=0.5,
                     depth_capacity=0.75, lora_rank=1)
key = jax.random.PRNGKey(0)
params = model_init(key, cfg, ecfg)
rp = router_init(jax.random.fold_in(key, 1), cfg, ecfg)
rng = np.random.default_rng(0)
# all-greedy rows: cross-mesh token parity is a greedy contract
reqs = [GenRequest(rng.integers(0, cfg.vocab_size, L, dtype=np.int32), 6,
                   budget=b)
        for L, b in ((5, 0.4), (13, 1.0), (16, None), (29, 0.6))]

# oracle: single-device RING engine serving each request alone
solo = ServingEngine(params, rp, cfg, ecfg, mode="infer", batch_size=2,
                     max_seq=48)
oracle = [solo.generate([r])[0] for r in reqs]

for layout, kw in (("ring", {}), ("paged", {"page_size": 8})):
    mesh = make_mesh((2, 4), ("data", "model"))
    eng = ServingEngine(params, rp, cfg, ecfg, mode="infer", batch_size=4,
                        max_seq=48, mesh=mesh, kv_layout=layout, **kw)
    assert eng.scheduler.n_replicas == 2
    h0 = eng.submit(reqs[0])
    eng.step(); eng.step()            # r0 is 2 tokens in when r1 lands
    h1 = eng.submit(reqs[1])
    eng.step()
    h2, h3 = eng.submit(reqs[2]), eng.submit(reqs[3])
    handles = [h0, h1, h2, h3]
    while not all(h.done for h in handles):
        assert eng.step() > 0
    # decode stays ONE compile with depth live; prefill is one for paged
    # (chunked prefill) and one PER DISTINCT PROMPT LENGTH for ring — the
    # documented ring cost this 4-length mix deliberately exercises
    want_prefill = 1 if layout == "paged" else len({len(r.prompt)
                                                    for r in reqs})
    assert eng.compile_counts() == {"prefill": want_prefill, "decode": 1}, \
        eng.compile_counts()
    assert {eng.scheduler.replica_of(h.slot) for h in handles} == {0, 1}
    for h, o in zip(handles, oracle):   # token-for-token vs 1-device ring
        np.testing.assert_array_equal(np.asarray(h.output), o)
    # the per-layer KV-validity mask leaves live ON the mesh (the
    # constrain_kv_mask / constrain_page_pool pins cover them)
    from jax.sharding import NamedSharding
    for l in jax.tree.leaves(eng._caches):
        assert isinstance(l.sharding, NamedSharding), l.sharding
    print(f"DEPTH-SPMD-{layout.upper()}-OK")
"""


@pytest.mark.slow
def test_depth_serving_spmd_parity(tmp_path):
    """Elastic depth acceptance on the production mesh: with the depth
    router live (per-(slot, layer) whole-block skips writing NO KV at
    skipped layers), both cache layouts on a 2x4 (data, model) mesh are
    token-for-token identical to the single-device ring engine on a
    staggered mixed-budget workload, compile counts stay flat, and every
    cache leaf — including the per-layer KV-validity masks — is placed on
    the mesh."""
    out = _run_spmd_script(_DEPTH_SCRIPT)
    for tag in ("DEPTH-SPMD-RING-OK", "DEPTH-SPMD-PAGED-OK"):
        assert tag in out, out


@pytest.mark.slow
def test_quantized_serving_spmd_parity(tmp_path):
    """int8 KV + int8 weights on the 2x4 (data, model) mesh: the sharded
    paged engine serves the single-device int8 ring engine's tokens on a
    staggered mixed-budget workload — identical until a near-tie in the
    single-device logits — compile counts stay flat, the pool drains, and
    every cache leaf (int8 pool + f32 scale sibling) is placed on the
    mesh."""
    out = _run_spmd_script(_QUANT_SCRIPT)
    assert "QUANT-SPMD-PARITY-OK" in out, out
