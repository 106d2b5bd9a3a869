"""Kernel ``paged_decode_attention``: least time its work needs (the int8 K/V rows and scales each live slot holds, read once), the larger of
operations over the bf16 peak and bytes over HBM bandwidth, summed over
the traced window, over the kernel's device time there, in percent.
Reported only where no router decides the work (budget 1.0)."""
import counts
from trace import TraceError

KERNEL = "paged_decode_attention"


def read(red, rec, ctx):
    d, peaks = ctx["dims"], ctx["peaks"]
    least = 0.0
    for s in rec["steps"]:
        if s["ctxs"]:
            least += counts.roofline_seconds(
                *counts.paged_decode_attention(d, s["ctxs"]), peaks)[0]
    if least <= 0:
        return None
    t = red["kernel_s"].get(KERNEL, 0.0)
    if t <= 0:
        raise TraceError(f"work for {KERNEL} in the traced window but no "
                         f"{KERNEL} operation in the trace")
    return 100.0 * least / t
