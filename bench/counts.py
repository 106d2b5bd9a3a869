"""Operations and bytes each kernel's work needs, from its shapes and the
lengths the harness itself issued. These are the least the work requires
(what its inputs and outputs hold), not what a kernel happens to move, so
a share of the roofline built on them cannot pass 100% unless the time
leaves part of the work out.

``d`` is ``weights.dims(cfg)``; ``ctx`` is the number of cache positions
a decode query attends (its own included).
"""
from __future__ import annotations

BF16, INT8, F32 = 2, 1, 4


def decode_attention(d: dict, ctxs, kv_bytes: int = BF16) -> tuple:
    """One decode step of the ring kernel over every live slot, all layers:
    (flops, bytes). The K and V rows each slot holds are read once; q in,
    context out. ``kv_bytes`` 1 adds the int8 rows' f32 scales."""
    H, K, Dh, L = d["H"], d["K"], d["Dh"], d["L"]
    flops = bytes_ = 0
    for c in ctxs:
        flops += 4 * H * Dh * c * L
        kv = 2 * c * K * Dh * kv_bytes
        if kv_bytes == INT8:
            kv += 2 * c * K * F32
        bytes_ += (kv + 2 * H * Dh * BF16) * L
    return flops, bytes_


def paged_decode_attention(d: dict, ctxs) -> tuple:
    """The paged kernel over int8 pages: the same work as the ring kernel
    with int8 K/V and per-(position, kv-head) f32 scales."""
    return decode_attention(d, ctxs, kv_bytes=INT8)


def flash_attention(d: dict, plen: int) -> tuple:
    """Causal prefill attention of one prompt, all layers: QK^T and PV over
    the lower triangle (the diagonal included); q, k, v in and out once."""
    H, K, Dh, L = d["H"], d["K"], d["Dh"], d["L"]
    pairs = plen * (plen + 1) // 2
    flops = 4 * H * Dh * pairs * L
    bytes_ = plen * (2 * H + 2 * K) * Dh * BF16 * L
    return flops, bytes_


def fused_mlp(d: dict, tokens: int) -> tuple:
    """The dense SwiGLU MLP over ``tokens`` real rows, all layers: the three
    weight matrices read once, the rows in and out."""
    D, F, L = d["D"], d["F"], d["L"]
    flops = 2 * 3 * tokens * D * F * L
    bytes_ = (3 * D * F * BF16 + 2 * tokens * D * BF16) * L
    return flops, bytes_


def roofline_seconds(flops: float, bytes_: float, peaks: dict) -> tuple:
    """(least seconds, "compute" or "memory"): the larger of the two bounds."""
    tc = flops / peaks["bf16_flops"]
    tm = bytes_ / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


def token_flops(d: dict, ctx: int, logits: bool) -> int:
    """Dense (budget 1.0) model operations for one token at ``ctx`` cache
    positions (itself included): every projection, attention over ctx,
    the MLP, and the LM head when the token's logits are computed."""
    D, H, K, Dh, F, V, L = (d[k] for k in "D H K Dh F V L".split())
    per_layer = 2 * D * (2 * H + 2 * K) * Dh + 4 * H * Dh * ctx \
        + 2 * 3 * D * F
    return per_layer * L + (2 * D * V if logits else 0)


def prompt_flops(d: dict, plen: int) -> int:
    """A prompt of ``plen`` tokens admitted: token i attends i + 1
    positions; logits only at the last."""
    D, H, K, Dh, F, L = (d[k] for k in "D H K Dh F L".split())
    per_tok = 2 * D * (2 * H + 2 * K) * Dh + 2 * 3 * D * F
    attn = 4 * H * Dh * (plen * (plen + 1) // 2)
    return (per_tok * plen + attn) * L + 2 * D * d["V"]
