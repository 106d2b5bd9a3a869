"""Kernel ``fused_mlp``: least time its work needs (the dense MLP over each
admission chunk's real tokens, its weights read once a chunk), the larger
of operations over the bf16 peak and bytes over HBM bandwidth, summed over
the traced window, over the kernel's device time there, in percent. The
paged admission runs it once a chunk of ``page_size`` prompt tokens.
Reported only where no router decides the work (budget 1.0)."""
import counts
from trace import TraceError

KERNEL = "fused_mlp"


def read(red, rec, ctx):
    d, peaks = ctx["dims"], ctx["peaks"]
    ps = ctx["conf"]["engine"]["page_size"]
    least = 0.0
    for s in rec["steps"]:
        for p in s["admitted"]:
            chunks = [ps] * (p // ps) + ([p % ps] if p % ps else [])
            least += sum(counts.roofline_seconds(
                *counts.fused_mlp(d, t), peaks)[0] for t in chunks)
    if least <= 0:
        return None
    t = red["kernel_s"].get(KERNEL, 0.0)
    if t <= 0:
        raise TraceError(f"work for {KERNEL} in the traced window but no "
                         f"{KERNEL} operation in the trace")
    return 100.0 * least / t
