"""Model step: the dense model's operations for the tokens the decode
program emitted in the traced window (each at its context), over the
decode program's device time there (the mean over the chips used) and
their bf16 peak, all chips together, in percent.
The whole decode step's share of the peak, beside the decode kernels'
rooflines."""
import counts
from trace import TraceError


def read(red, rec, ctx):
    d = ctx["dims"]
    flops = sum(counts.token_flops(d, c, True)
                for s in rec["steps"] for c in s["ctxs"])
    if not flops:
        return None
    t = red["program_s"].get("decode", 0.0)
    if t <= 0:
        raise TraceError("decode tokens in the traced window but no "
                         "decode program (jit_step) ran")
    n = ctx["chips"]
    return 100.0 * flops / (t * ctx["peaks"]["bf16_flops"] * n)
