"""Self-distillation training step (paper §4.2):

    L = L_distill + lambda_load * L_load + lambda_topk * L_topk

Teacher = frozen base model (mode='base'); student = same frozen weights +
trainable routers (+LoRA) (mode='train'). Gradients flow ONLY into the
router tree, so optimizer state is tiny.

Distributed top-50 KL (the TPU adaptation of the paper's loss): the naive
path would `top_k` over a vocab-sharded (B,S,V) logits tensor, forcing a
13 GB/device all-gather at phi3/train_4k scale. Instead:
  * the final hidden states (B,S,D) of teacher & student are produced once;
  * a lax.scan over sequence chunks computes logits chunk-by-chunk so the
    full (B,S,V) tensor never exists;
  * inside a shard_map over the `model` (vocab) axis, each shard top-50s its
    local vocab slice, all-gathers only (B,chunk,16*50) candidates + local
    logsumexp, and reduces to the exact global top-50 (the global top-k is
    a subset of the union of shard-local top-ks).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.distill import (cosine_distance, distill_loss,
                                topk_kl_from_gathered)
from repro.core.policy import as_spec_policy
from repro.models import forward
from repro.optim import (AdamWState, EFState, adamw_init, adamw_update,
                         compress_grads, ef_init)
from repro.runtime.sharding import batch_axes


class TrainState(NamedTuple):
    router_params: dict
    opt: AdamWState
    ef: Optional[EFState]


def init_train_state(router_params, use_compression: bool = False):
    return TrainState(router_params, adamw_init(router_params),
                      ef_init(router_params) if use_compression else None)


# ----------------------- distributed chunked top-k KL -----------------------

def _head_matrix(params, cfg):
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


def _mask_padded(logits_local, vocab: int, v_local: int, axis: str):
    shard = jax.lax.axis_index(axis)
    gidx = shard * v_local + jnp.arange(v_local)
    return jnp.where(gidx < vocab, logits_local, -1e30)


def chunked_topk_kl(h_student, h_teacher, head, *, k: int, vocab: int,
                    mesh: Optional[Mesh], seq_chunk: int = 512,
                    direction: str = "fwd", temp: float = 1.0,
                    full: bool = False):
    """h_*: (B,S,D); head: (D,V) (vocab-sharded over `model` when mesh).

    full=False: exact global top-k KL with residual bucket (paper default).
    full=True : exact full-vocab KL (the paper's fwd_kl/rev_kl variants) —
    decomposes over vocab shards given the global logsumexp, so it needs
    only a scalar-per-token collective."""
    B, S, D = h_student.shape
    c = min(seq_chunk, S)
    while S % c:
        c -= 1
    nC = S // c

    def _kl_terms(ls, lt):
        """Per-token partial KL sums from shard-local log-probs."""
        if direction == "fwd":
            return jnp.sum(jnp.exp(ls) * (ls - lt), axis=-1)
        return jnp.sum(jnp.exp(lt) * (lt - ls), axis=-1)

    if mesh is None or "model" not in mesh.axis_names:
        def body(_, hc):
            hs, ht = hc
            lt = (ht @ head).astype(jnp.float32) / temp
            ls = (hs @ head).astype(jnp.float32) / temp
            v = jnp.arange(head.shape[-1]) < vocab
            lt = jnp.where(v, lt, -1e30)
            ls = jnp.where(v, ls, -1e30)
            lt = jax.nn.log_softmax(lt, axis=-1)
            ls = jax.nn.log_softmax(ls, axis=-1)
            if full:
                return None, jnp.mean(_kl_terms(ls, lt))
            t_top, idx = jax.lax.top_k(lt, k)
            s_top = jnp.take_along_axis(ls, idx, axis=-1)
            return None, topk_kl_from_gathered(s_top, t_top, direction)
        hs = h_student.reshape(B, nC, c, D).transpose(1, 0, 2, 3)
        ht = h_teacher.reshape(B, nC, c, D).transpose(1, 0, 2, 3)
        _, kls = jax.lax.scan(body, None, (hs, ht))
        return jnp.mean(kls) * temp * temp

    ba = batch_axes(mesh)

    def sharded(hs_all, ht_all, head_loc):
        v_local = head_loc.shape[-1]

        def body(_, hc):
            hs, ht = hc                                   # (b, c, D) local
            lt = (ht @ head_loc).astype(jnp.float32) / temp   # (b, c, Vl)
            ls = (hs @ head_loc).astype(jnp.float32) / temp
            lt = _mask_padded(lt, vocab, v_local, "model")
            ls = _mask_padded(ls, vocab, v_local, "model")
            lse_t = jax.nn.logsumexp(lt, axis=-1)         # (b, c)
            lse_s = jax.nn.logsumexp(ls, axis=-1)
            # global logsumexp across vocab shards
            lse_t = jax.nn.logsumexp(
                jax.lax.all_gather(lse_t, "model", axis=0), axis=0)
            lse_s = jax.nn.logsumexp(
                jax.lax.all_gather(lse_s, "model", axis=0), axis=0)
            if full:
                # shard-local partial KL sums + psum over vocab shards
                kl = _kl_terms(ls - lse_s[..., None], lt - lse_t[..., None])
                return None, jnp.mean(jax.lax.psum(kl, "model"))
            kk = min(k, v_local)
            t_loc, idx = jax.lax.top_k(lt, kk)
            s_loc = jnp.take_along_axis(ls, idx, axis=-1)
            cand_t = jax.lax.all_gather(t_loc, "model", axis=2, tiled=True)
            cand_s = jax.lax.all_gather(s_loc, "model", axis=2, tiled=True)
            t_vals, pos = jax.lax.top_k(cand_t, k)        # exact global top-k
            s_vals = jnp.take_along_axis(cand_s, pos, axis=-1)
            kl = topk_kl_from_gathered(s_vals - lse_s[..., None],
                                       t_vals - lse_t[..., None], direction)
            return None, kl

        b = hs_all.shape[0]
        hs = hs_all.reshape(b, nC, c, D).transpose(1, 0, 2, 3)
        ht = ht_all.reshape(b, nC, c, D).transpose(1, 0, 2, 3)
        _, kls = jax.lax.scan(body, None, (hs, ht))
        # mean over chunks locally; mean over batch shards
        out = jnp.mean(kls) * temp * temp
        return jax.lax.pmean(out, ba) if ba else out

    f = jax.shard_map(
        sharded, mesh=mesh,
        in_specs=(P(ba, None, None), P(ba, None, None), P(None, "model")),
        out_specs=P(), check_vma=False)
    return f(h_student, h_teacher, head)


def lm_loss(logits, tokens):
    """Next-token cross entropy (evaluation metric, matches paper's LM Loss)."""
    lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    tgt = tokens[:, 1:]
    nll = -jnp.take_along_axis(lp, tgt[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


# ------------------------------- train step ---------------------------------

def make_loss_fn(cfg, ecfg, *, mesh: Optional[Mesh] = None, remat: bool = False,
                 chunked: bool = True, seq_chunk: int = 512):
    """``ecfg``: legacy ElasticConfig or new ElasticSpec. The returned
    loss_fn takes an optional ``policy`` (ElasticPolicy pytree) — pass it as
    a traced argument to anneal capacities during distillation with zero
    re-jits; omitted, the spec's default (static) policy applies — and an
    optional ``bucket`` (python int, STATIC: jit with
    static_argnames=("bucket",)): the ragged capacity-bucket size covering
    the policy's token budgets (core/policy.ragged_bucket), so the student
    forward lowers FLOPs proportional to the bucket. One compile per bucket,
    <= routing.RAGGED_N_BUCKETS (+ the identity graph that full-budget
    anneal starts resolve to — it skips routing work entirely while keeping
    the routers' BCE/load aux, so the anneal's early steps run at teacher
    speed with live router gradients) across a whole schedule."""
    use_hidden = chunked and cfg.family != "encoder" and cfg.vocab_size > 0
    spec, default_pol = as_spec_policy(ecfg)

    def loss_fn(router_params, params, batch, policy=None, bucket=None):
        pol = policy if policy is not None else default_pol
        if cfg.family == "encoder":
            t_out, _ = forward(params, None, batch, cfg, spec, mode="base")
            s_out, aux = forward(params, router_params, batch, cfg, spec,
                                 mode="train", remat=remat, policy=pol,
                                 bucket=bucket)
            dist = cosine_distance(s_out, jax.lax.stop_gradient(t_out))
        elif use_hidden:
            h_t, _ = forward(params, None, batch, cfg, spec, mode="base",
                             return_hidden=True)
            h_s, aux = forward(params, router_params, batch, cfg, spec,
                               mode="train", return_hidden=True, remat=remat,
                               policy=pol, bucket=bucket)
            direction = "rev" if "rev" in spec.distill_loss else "fwd"
            dist = chunked_topk_kl(
                h_s, jax.lax.stop_gradient(h_t), _head_matrix(params, cfg),
                k=spec.distill_topk, vocab=cfg.vocab_size, mesh=mesh,
                seq_chunk=seq_chunk, direction=direction,
                temp=spec.distill_temp,
                full=spec.distill_loss in ("fwd_kl", "rev_kl"))
        else:
            t_out, _ = forward(params, None, batch, cfg, spec, mode="base")
            s_out, aux = forward(params, router_params, batch, cfg, spec,
                                 mode="train", remat=remat, policy=pol,
                                 bucket=bucket)
            dist = distill_loss(s_out, jax.lax.stop_gradient(t_out), spec)
        loss = (dist + spec.lambda_load * aux.load
                + spec.lambda_topk * aux.topk)
        return loss, {"loss": loss, "distill": dist, "aux_load": aux.load,
                      "aux_topk": aux.topk, "sel_rate": aux.sel_rate}
    return loss_fn


def make_train_step(cfg, ecfg, *, lr, weight_decay: float = 0.0,
                    max_grad_norm: float = 1.0, mesh: Optional[Mesh] = None,
                    remat: bool = False, chunked: bool = True,
                    compress_axis: Optional[str] = None,
                    microbatch: Optional[int] = None):
    """Returns train_step(state, params, batch, policy=None, bucket=None)
    -> (state, metrics). `params` (frozen base model) is passed per-call so
    it can live donated/sharded outside the state. `policy` (ElasticPolicy)
    is likewise per-call and traced: capacity-annealing schedules re-use one
    compile. `bucket` is the STATIC ragged capacity-bucket hint (jit the
    step with static_argnames=("bucket",)): mixed-budget / annealed training
    stays at one graph per bucket while lowered FLOPs track the budget.

    microbatch=M: gradient accumulation over M sequential slices of the
    global batch (lax.scan). Activation live-set scales 1/M; the router
    gradient tree is tiny (<=0.3% of params) so accumulation is ~free —
    the §Perf HBM-fit lever for the train cells."""
    loss_fn = make_loss_fn(cfg, ecfg, mesh=mesh, remat=remat, chunked=chunked)
    vg = jax.value_and_grad(loss_fn, has_aux=True)

    def grads_of(rp, params, batch, policy, bucket):
        if not microbatch or microbatch <= 1:
            (_, metrics), grads = vg(rp, params, batch, policy, bucket)
            return grads, metrics

        def slice_mb(t, i):
            m = t.shape[0] // microbatch
            return jax.lax.dynamic_slice_in_dim(t, i * m, m, axis=0)

        def body(carry, i):
            g_acc, m_acc = carry
            mb = {k: slice_mb(v, i) for k, v in batch.items()}
            # NOTE: per-request (B,) policy leaves are not sliced here —
            # use scalar/per-layer policies with gradient accumulation
            (_, metrics), g = vg(rp, params, mb, policy, bucket)
            g_acc = jax.tree.map(jnp.add, g_acc, g)
            m_acc = jax.tree.map(jnp.add, m_acc, metrics)
            return (g_acc, m_acc), None

        g0 = jax.tree.map(jnp.zeros_like, rp)
        m0 = {k: jnp.zeros((), jnp.float32)
              for k in ("loss", "distill", "aux_load", "aux_topk",
                        "sel_rate")}
        (g, m), _ = jax.lax.scan(body, (g0, m0), jnp.arange(microbatch))
        inv = 1.0 / microbatch
        return (jax.tree.map(lambda x: x * inv, g),
                {k: v * inv for k, v in m.items()})

    def train_step(state: TrainState, params, batch, policy=None,
                   bucket=None):
        grads, metrics = grads_of(state.router_params, params, batch, policy,
                                  bucket)
        ef = state.ef
        if ef is not None:
            grads, ef = compress_grads(grads, ef, axis_name=compress_axis)
        new_rp, opt, om = adamw_update(
            grads, state.opt, state.router_params, lr=lr,
            weight_decay=weight_decay, max_grad_norm=max_grad_norm)
        metrics.update(om)
        return TrainState(new_rp, opt, ef), metrics

    return train_step
