"""Kernel ``moe_gmm``: least time the dropless prompt experts need, the
larger of operations over the bf16 peak and bytes over HBM bandwidth,
summed over the prompts admitted in the traced window, over the kernel's
device time there, in percent. Only the admission runs ``moe_gmm`` (decode
gathers its experts' weights); reported only at budget 1.0, where the
native router alone decides the work.

Per admitted prompt of ``p`` tokens and per MoE layer, the operations are
exact: ``p * k`` routed rows through three ``D x Fe`` matrices,
``2 * 3 * p * k * D * Fe``. The bytes are those rows in and out plus every
expert's three matrices read once: a prompt of 128 tokens or more leaves an
expert of a near-uniform top-4-of-32 router unpicked with probability
about ``(7/8) ** 128``, some 4e-8, so every expert's weights are needed."""
import counts
from trace import TraceError

KERNEL = "moe_gmm"


def moe_gmm(d: dict, plen: int) -> tuple:
    """(flops, bytes) of one prompt's dropless experts, all MoE layers."""
    D, Fe, E, k, L = d["D"], d["Fe"], d["E"], d["k"], d["L_moe"]
    flops = 2 * 3 * plen * k * D * Fe * L
    bytes_ = (2 * plen * k * D + 3 * E * D * Fe) * counts.BF16 * L
    return flops, bytes_


def read(red, rec, ctx):
    cell, d, peaks = ctx["cell"], ctx["dims"], ctx["peaks"]
    if cell.get("budget") != 1.0 or cell.get("classes") or not d["L_moe"]:
        return None
    least = sum(counts.roofline_seconds(*moe_gmm(d, p), peaks)[0]
                for s in rec["steps"] for p in s["admitted"])
    if least <= 0:
        return None
    t = red["kernel_s"].get(KERNEL, 0.0)
    if t <= 0:
        raise TraceError(f"prompts admitted in the traced window but no "
                         f"{KERNEL} operation in the trace")
    return 100.0 * least / t
