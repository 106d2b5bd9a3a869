"""Collectives: device time of the collective operations inside one
execution of the engine's decode program (``step``), mean over its
executions in the traced window and over the chips used, ms. An operation
is a collective where its name holds ``all-reduce``, ``all-gather``,
``reduce-scatter``, ``all-to-all`` or ``collective`` (their
``-start``/``-done`` halves, ``collective-permute``, the TPU's
``async-collective-start``/``-done``, the fusions named after any of
them), or the name of a JAX collective that XLA gives the instruction it
lowers to inside ``shard_map`` (``psum``, ``pmax``, ``pmin``,
``ppermute``, ``all_gather``, ``all_to_all``). None where the decode
program runs no collective (one chip)."""
import re

from trace import TraceError

COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all"
                        r"|collective|psum|pmax|pmin|ppermute|all_gather"
                        r"|all_to_all")


def read(red, rec, ctx):
    n = red["program_n"].get("decode", 0)
    if not n:
        raise TraceError("no execution of the decode program (jit_step) "
                         "in the traced window")
    ops = red["program_op_s"].get("decode", {})
    t = sum(v for k, v in ops.items() if COLLECTIVE.search(k))
    if t <= 0:
        return None
    return t / n * 1e3
