"""Pallas TPU decode attention over the PAGED KV pool — the one-token
hot path when the serving engine runs the block-paged cache
(``runtime/pagedkv.py``).

Unlike the ring kernel there is no per-slot (B, L) cache: K/V live in a
global page pool of shape (N, page_size, K, Dh) and slot ``b`` owns the
pages named by its page-table row ``table[b]`` (int32, -1 = unused).
Positions are implicit in the table layout — table entry ``p`` of a row
holds absolute positions ``[p * page_size, (p+1) * page_size)`` — so the
kernel needs no position array: a key at page-entry ``p``, lane ``j`` is
attendable iff

    table[b, p] >= 0                      (entry backed by a page)
    p * page_size + j <= t[b]             (causal at this slot's position)
    pvalid[table[b, p], j]                (ElastiFormer token routing:
                                           skipped tokens hold no KV)

The grid is ``(B,)``: one step per slot, for all its query and kv heads.
Inside a step the kernel loops over the slot's LIVE table entries only —
entries ``0 .. t[b] // page_size``, in blocks of ``ppb`` pages
(``ppb * page_size`` = 128 positions; ``ppb`` follows from the page size
and the table length, no knob). Each live page is copied once, all kv
heads at once, from the pool in HBM in its native layout (the pool seen
as (N, page_size * K, Dh), rows in (lane, kv head) order: a free
reshape) into a double-buffered VMEM block ``(2, ppb, page_size * K,
Dh)``: block i + 1 is in flight while block i computes. Entries past
``t`` (pages pre-allocated for later writes) and -1 entries are never
copied; their value rows are zeroed in VMEM and their lanes masked, so
nothing of a page the slot does not attend reaches the output.

A block is scored in one ``(H, Dh) x (Dh, ppb * page_size * K)`` matmul
of all query heads against all kv heads' keys, each query head masked to
its own kv head's columns (GQA): the MXU loads the same key tiles as K
separate ``(G, Dh)`` matmuls would, and the kernel slices no kv head out
of a packed page, so one path serves every K and dtype. Online softmax
with f32 ``m``, ``l`` and accumulator; a row with no attendable key ends
as exact zeros. The table and ``t`` ride scalar prefetch; the per-lane
routing mask and, for int8 pools, the per-(lane, kv-head) f32 scales are
gathered by the same table into per-slot rows before the call and fold
into the score and probability columns. The jnp oracle is
``kernels/ref.py::paged_decode_attention_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def analysis_example():
    """Representative paged-pool decode call for the static kernel
    verifier: a pool with free pages, table rows longer than the live
    range (pre-allocated entries past ``t``, -1 holes), per-slot offsets
    riding scalar prefetch, GQA 2:1."""
    import numpy as np
    B, N, ps, H, K, Dh = 2, 12, 16, 4, 2, 128
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.normal(size=(B, 1, H, Dh)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(N, ps, K, Dh)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(N, ps, K, Dh)), jnp.float32)
    table = np.full((B, 12), -1, np.int32)
    table[0, :3] = [4, 1, 9]              # 2 live pages, mid-page offset
    table[1, :4] = [0, 6, 2, 11]          # 3 live pages, page-boundary
    t = jnp.asarray([20, 47], jnp.int32)
    pvalid = jnp.asarray(rng.integers(0, 2, size=(N, ps)), bool)
    return (paged_decode_attention,
            (q, kp, vp, jnp.asarray(table), t, pvalid),
            dict(interpret=True))


def _kernel(tbl_ref, t_ref, q_ref, kp_hbm, vp_hbm, ok_ref, col_ref, hm_ref,
            ks_ref, vs_ref, o_ref, kbuf, vbuf, sem, m_sc, l_sc, acc_sc, *,
            page_size: int, ppb: int, n_tbl: int, sm_scale: float):
    b = pl.program_id(0)
    t = t_ref[b]
    n = ppb * page_size
    # table entries this slot attends, and the blocks that hold them
    n_live = jnp.minimum(jnp.maximum(t // page_size + 1, 0), n_tbl)
    n_blk = (n_live + ppb - 1) // ppb

    def page(blk, i):
        idx = blk * ppb + i
        entry = tbl_ref[b, jnp.minimum(idx, n_tbl - 1)]
        return entry, (idx < n_live) & (entry >= 0)

    def copies(entry, slot, i):
        return [pltpu.make_async_copy(src.at[entry], buf.at[slot, i],
                                      sem.at[slot])
                for src, buf in ((kp_hbm, kbuf), (vp_hbm, vbuf))]

    def fetch(blk, slot):
        for i in range(ppb):
            entry, live = page(blk, i)

            @pl.when(live)
            def _start():
                for c in copies(entry, slot, i):
                    c.start()

            # a page not copied keeps whatever the buffer held: zero its
            # value rows, so a masked lane (probability 0) never meets a
            # NaN in p @ v
            @pl.when(jnp.logical_not(live))
            def _zero():
                vbuf[slot, i] = jnp.zeros(vbuf.shape[2:], vbuf.dtype)

    def wait(blk, slot):
        for i in range(ppb):
            entry, live = page(blk, i)

            @pl.when(live)
            def _wait():
                for c in copies(entry, slot, i):
                    c.wait()

    m_sc[...] = jnp.full_like(m_sc, NEG_INF)
    l_sc[...] = jnp.zeros_like(l_sc)
    acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when(n_blk > 0)
    def _first():
        fetch(0, 0)

    def body(blk, carry):
        slot = blk % 2

        @pl.when(blk + 1 < n_blk)
        def _next():
            fetch(blk + 1, 1 - slot)

        wait(blk, slot)
        q = q_ref[0].astype(jnp.float32)                   # (H, Dh)
        k = kbuf[slot].astype(jnp.float32)
        k = k.reshape(-1, k.shape[-1])                     # (C, Dh)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                                   # (H, C)
        if ks_ref is not None:
            # int8 pool: each key row's per-(lane, kv-head) f32 scale folds
            # into its score column — HBM only ever saw the int8 page
            # (docs/quantization.md)
            s = s * ks_ref[0, blk]
        # a column is one (lane, kv head) of the block: attendable for the
        # query heads of that kv head (hm), when routed in and backed by a
        # page (ok) and causal at t
        mask = ((hm_ref[...] > 0) & (ok_ref[0, blk] > 0)
                & (blk * n + col_ref[...] <= t))
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_sc[...]                                 # (H, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # masked keys get probability exactly 0 — also in a block where
        # every key is masked (there s - m_new == 0), so a row with no
        # attendable key keeps l == 0 and finishes as exact zeros
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_sc[...] = l_sc[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_sc[...] = m_new
        if vs_ref is not None:
            # value-row scales fold into p's columns (a dead page's scale
            # may be anything, so the mask applies after)
            p = jnp.where(mask, p * vs_ref[0, blk], 0.0)
        v = vbuf[slot].astype(jnp.float32)
        v = v.reshape(-1, v.shape[-1])                     # (C, Dh)
        acc_sc[...] = acc_sc[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, n_blk, body, 0)
    l = jnp.maximum(l_sc[...], 1e-30)
    o_ref[0] = (acc_sc[...] / l).astype(o_ref.dtype)


def paged_decode_attention(q, kp, vp, table, t, pvalid, *, kscale=None,
                           vscale=None, sm_scale: float | None = None,
                           interpret: bool = False):
    """q: (B, 1, H, Dh); kp, vp: (N, page_size, K, Dh) global page pool;
    table: (B, P) i32 page-table rows (-1 = unused entry); t: (B,) i32
    per-slot decode positions; pvalid: (N, page_size) bool per-lane
    routing validity; kscale/vscale: (N, page_size, K) f32 per-(lane,
    kv-head) dequant scale pools when kp/vp are int8 (both or neither).
    Reads only the pages of entries ``0 .. t[b] // page_size`` of each
    row. Returns (B, 1, H, Dh)."""
    B, _, H, Dh = q.shape
    N, ps, K = kp.shape[0], kp.shape[1], kp.shape[2]
    P = table.shape[1]
    G = H // K
    R = ps * K                        # rows of a page: (lane, kv head)
    quantized = kscale is not None
    sm_scale = Dh ** -0.5 if sm_scale is None else sm_scale
    table = jnp.asarray(table, jnp.int32)
    t = jnp.broadcast_to(jnp.asarray(t, jnp.int32).reshape(-1), (B,))
    # pages a block: one lane row of positions, at most the whole table
    ppb = max(1, min(P, LANES // ps))
    n_blk = -(-P // ppb)
    C = ppb * R                       # columns of a block

    # per-slot rows (B, n_blk, 1, C) in the block's column order: the
    # routing mask (False on -1 entries and on the padding to whole
    # blocks) and, for int8 pools, the scales — gathered by the table, as
    # the kernel gathers the pages
    pid = jnp.maximum(table, 0)
    pad = ((0, 0), (0, n_blk * ppb - P), (0, 0))
    ok = jnp.pad(pvalid[pid] & (table >= 0)[:, :, None], pad)
    ok = jnp.broadcast_to(ok[..., None], (B, n_blk * ppb, ps, K))
    ok = ok.astype(jnp.int32).reshape(B, n_blk, 1, C)

    def rows(scale):                  # (N, ps, K) -> (B, n_blk, 1, C)
        s = scale.astype(jnp.float32).reshape(N, R)[pid]
        return jnp.pad(s, pad).reshape(B, n_blk, 1, C)

    # column c of a block: position offset c // K, kv head c % K; query
    # head h reads the columns of kv head h // G
    c = jnp.arange(C, dtype=jnp.int32)
    col = (c // K)[None]                                   # (1, C)
    hm = (c[None] % K == jnp.arange(H)[:, None] // G).astype(jnp.int32)

    slot_spec = lambda *blk: pl.BlockSpec(
        (1, *blk), lambda b, tbl, tt: (b,) + (0,) * len(blk))
    whole_spec = lambda *blk: pl.BlockSpec(
        blk, lambda b, tbl, tt: (0,) * len(blk))
    in_specs = [slot_spec(H, Dh),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                slot_spec(n_blk, 1, C), whole_spec(1, C), whole_spec(H, C)]
    args = [q.reshape(B, H, Dh), kp.reshape(N, R, Dh), vp.reshape(N, R, Dh),
            ok, col, hm]
    kernel = functools.partial(_kernel, page_size=ps, ppb=ppb, n_tbl=P,
                               sm_scale=sm_scale)
    if quantized:
        in_specs += [slot_spec(n_blk, 1, C), slot_spec(n_blk, 1, C)]
        args += [rows(kscale), rows(vscale)]
        kfn = kernel
    else:
        kfn = lambda *refs: kernel(*refs[:8], None, None, *refs[8:])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=in_specs,
        out_specs=slot_spec(H, Dh),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, R, Dh), kp.dtype),
            pltpu.VMEM((2, ppb, R, Dh), vp.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, Dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kfn,
        name="paged_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(table, t, *args)
    return out.reshape(B, 1, H, Dh)
