"""Model step: device time of the engine's admission program (``admit``,
ring prefill or one paged chunk per call) per prompt token admitted in
the traced window, microseconds."""
from trace import TraceError


def read(red, rec, ctx):
    tokens = sum(sum(s["admitted"]) for s in rec["steps"])
    if not tokens:
        return None
    t = red["program_s"].get("admit", 0.0)
    if t <= 0:
        raise TraceError("prompts were admitted in the traced window but "
                         "no admission program (jit_admit) ran")
    return t / tokens * 1e6
