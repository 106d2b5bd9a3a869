"""Model step: device time of one execution of the engine's decode
program (``step``), mean over its executions in the traced window, ms."""
from trace import TraceError


def read(red, rec, ctx):
    n = red["program_n"].get("decode", 0)
    if not n:
        raise TraceError("no execution of the decode program (jit_step) "
                         "in the traced window")
    return red["program_s"]["decode"] / n * 1e3
