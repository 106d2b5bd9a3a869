"""Kernel ``flash_attention``: least time its work needs (causal QK^T and PV at each admitted prompt's length), the larger of
operations over the bf16 peak and bytes over HBM bandwidth, summed over
the traced window, over the kernel's device time there, in percent.
Reported only where no router decides the work (budget 1.0)."""
import counts
from trace import TraceError

KERNEL = "flash_attention"


def read(red, rec, ctx):
    d, peaks = ctx["dims"], ctx["peaks"]
    least = 0.0
    for s in rec["steps"]:
        for p in s["admitted"]:
            least += counts.roofline_seconds(
                *counts.flash_attention(d, p), peaks)[0]
    if least <= 0:
        return None
    t = red["kernel_s"].get(KERNEL, 0.0)
    if t <= 0:
        raise TraceError(f"work for {KERNEL} in the traced window but no "
                         f"{KERNEL} operation in the trace")
    return 100.0 * least / t
