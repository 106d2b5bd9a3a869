#!/usr/bin/env python3
"""One run of a cell at the CPU size of ``smoke.py`` on four virtual CPU
devices, in a process of its own (the device count is fixed when JAX
starts):

    python3 bench/tests/multichip.py <dir> --workload <cell> --seed <n> \
        --seconds <s> --trace 0 [--fault <name of faults.py>] \
        [--backend interpret|ref] [--control fp8]

writes the small checkout under ``<dir>``, runs the cell there with the
look for a chip skipped, and prints one JSON line: the run's result line
(``result``) and where the built engine holds its state (``placement``):
the mesh's shape and, for the parameter, router and cache leaves, how
many there are, how many are ``NamedSharding``s over the whole mesh, and
how many are split over its ``model`` axis.
"""
import os
import sys

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=4")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import argparse  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
from contextlib import redirect_stdout  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE), str(HERE.parents[1] / "src")]

import faults  # noqa: E402
import run  # noqa: E402
from smoke import smoke_checkout  # noqa: E402


def placement(engine) -> dict:
    from jax.sharding import NamedSharding
    import jax
    mesh = engine.mesh
    out = {"mesh": dict(mesh.shape) if mesh is not None else None}
    trees = {"params": engine.params, "rp": engine.rp,
             "caches": engine._caches}
    for name, tree in trees.items():
        leaves = jax.tree.leaves(tree)
        named = [x for x in leaves if isinstance(x.sharding, NamedSharding)
                 and mesh is not None and x.sharding.mesh == mesh]
        split = [x for x in named
                 if "model" in jax.tree.leaves(tuple(x.sharding.spec))]
        out[name] = {"leaves": len(leaves), "on_mesh": len(named),
                     "split": len(split)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("dir", type=Path)
    ap.add_argument("--fault", default=None, choices=sorted(faults.FAULTS))
    ap.add_argument("--backend", default="interpret")
    own, rest = ap.parse_known_args(argv)
    seen = {}

    def hook(engine):
        seen.update(placement(engine))
        if own.fault is not None:
            faults.FAULTS[own.fault](engine)

    out = io.StringIO()
    with redirect_stdout(out):
        run.main(rest, require_tpu=False, bench_dir=smoke_checkout(own.dir),
                 overrides={"kernel_backend": own.backend}, fault=hook)
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    print(json.dumps({"result": res, "placement": seen}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
