"""Model step: the dense model's operations for the prompts admitted in
the traced window, over the admission program's device time there (the
mean over the chips used) and their bf16 peak, all chips together, in
percent. The whole admission's share of the peak, beside the prefill
kernels' rooflines."""
import counts
from trace import TraceError


def read(red, rec, ctx):
    d = ctx["dims"]
    flops = sum(counts.prompt_flops(d, p)
                for s in rec["steps"] for p in s["admitted"])
    if not flops:
        return None
    t = red["program_s"].get("admit", 0.0)
    if t <= 0:
        raise TraceError("prompts admitted in the traced window but no "
                         "admission program (jit_admit) ran")
    n = ctx["chips"]
    return 100.0 * flops / (t * ctx["peaks"]["bf16_flops"] * n)
