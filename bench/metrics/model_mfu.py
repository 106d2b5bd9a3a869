"""Device: the dense (budget 1.0) model's operations for every prompt and
output token processed in the traced window, counted from the
configuration's shapes at each token's context, over the traced window
and the chip's bf16 peak, in percent. Dense-equivalent by design: a
saving from routing raises it like any other speed-up."""
import counts


def read(red, rec, ctx):
    d = ctx["dims"]
    flops = 0
    for s in rec["steps"]:
        flops += sum(counts.prompt_flops(d, p) for p in s["admitted"])
        flops += sum(counts.token_flops(d, c, True) for c in s["ctxs"])
    if not flops:
        return None
    n = ctx["chips"]
    return 100.0 * flops / (red["window_s"] * ctx["peaks"]["bf16_flops"] * n)
