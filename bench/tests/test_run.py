"""Whole runs at a size the CPU holds: the harness's look for a chip is
skipped, everything after it runs, kernels interpreted.

Faults planted under the timed path must turn ``correct`` false, and so
must the float8 control put in the program's place."""
import io
import json
import os
import shutil
import subprocess
import sys
from contextlib import redirect_stdout

import pytest

import faults
import run
from conftest import BENCH
from smoke import smoke_checkout

KEYS = ["correct", "attempted", "failed", "metrics", "device", "check"]
CELLS = ["elastic-b05-chat", "paged-int8-rag-backlog", "elastic-b10-offline",
         "tp4-b10-chat"]
SEED = "3000000007"


def chips(cell) -> int:
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    return {w["name"]: w["chips"] for w in spec["workloads"]}[cell]


def gap_checks(res, cell):
    """The result's checks of served-token gaps, at any budget."""
    budget = json.loads((BENCH / "workloads" / f"{cell}.json").read_text())[
        "budget"]
    return [c for k, c in res["check"].items()
            if run.limit_key(k, budget)[0] in run.GAP_STATS]


def multichip_run(tmp_path, cell, *extra, fault=None, backend="interpret"):
    """The cell at the CPU size on four virtual devices, in a process of
    its own (``multichip.py``): its result line and where the engine held
    its state."""
    cmd = [sys.executable, str(BENCH / "tests" / "multichip.py"),
           str(tmp_path), "--workload", cell, "--seed", SEED, "--seconds",
           "2", "--trace", "0", "--backend", backend, *extra]
    if fault is not None:
        cmd += ["--fault", fault.__name__]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(BENCH.parent / "src"))
    p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    return out["result"], out["placement"]


def one_run(tmp_path, cell, *extra, fault=None, backend="interpret"):
    if chips(cell) > 1:
        return multichip_run(tmp_path, cell, *extra, fault=fault,
                             backend=backend)[0]
    b = smoke_checkout(tmp_path)
    out = io.StringIO()
    with redirect_stdout(out):
        run.main(["--workload", cell, "--seed", SEED, "--seconds",
                  "2", "--trace", "0", *extra], require_tpu=False,
                 bench_dir=b, overrides={"kernel_backend": backend},
                 fault=fault)
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_last_line(tmp_path, cell):
    res = one_run(tmp_path, cell)
    assert list(res) == KEYS
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    want = {m["name"] for m in spec["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["check"]["window_compiles"] == {"value": 0, "limit": 0}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["count"] == chips(cell)


def test_multichip_cell_runs_on_the_mesh(tmp_path):
    """A cell on four chips: every parameter, router and cache leaf is
    placed over the whole (data=1, model=4) mesh, the weights and the KV
    cache split over ``model``, and the run comes out correct."""
    res, placed = multichip_run(tmp_path, "tp4-b10-chat", backend="ref")
    assert placed["mesh"] == {"data": 1, "model": 4}
    for tree in ("params", "rp", "caches"):
        assert placed[tree]["leaves"] > 0
        assert placed[tree]["on_mesh"] == placed[tree]["leaves"], tree
    assert placed["params"]["split"] >= 12      # q/k/v/o, biases, MLP,
    assert placed["caches"]["split"] >= 2       # embedding, head; K and V
    assert res["correct"] is True, res["check"]
    assert res["check"]["backlog_left"]["value"] >= 1


@pytest.mark.parametrize("fault", [faults.token_altered,
                                   faults.state_unchanged],
                         ids=["token_altered", "state_unchanged"])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_turns_correct_false(tmp_path, cell, fault):
    res = one_run(tmp_path, cell, fault=fault, backend="ref")
    assert any(c["value"] > c["limit"] for c in gap_checks(res, cell))
    assert res["correct"] is False


def test_control_reads_wider(tmp_path):
    prog = one_run(tmp_path / "p", "paged-int8-rag-backlog", backend="ref")
    ctl = one_run(tmp_path / "c", "paged-int8-rag-backlog", "--control",
                  "fp8", backend="ref")
    assert ctl["check"]["max_gap"]["value"] > \
        3 * prog["check"]["max_gap"]["value"]
    assert ctl["check"]["far_share"]["value"] > \
        prog["check"]["far_share"]["value"]


@pytest.mark.parametrize("cell", ["elastic-b05-chat",
                                  "paged-int8-rag-backlog",
                                  "elastic-b10-offline"])
def test_control_is_not_correct(tmp_path, cell):
    """The float8 control, judged by the cell's own limits in the
    program's place, comes out not correct."""
    res = one_run(tmp_path, cell, "--control", "fp8", backend="ref")
    assert any(c["value"] > c["limit"] for c in gap_checks(res, cell))
    assert res["correct"] is False


def test_bf16_witness_passes(tmp_path):
    """The reference at the program's own precision, in its place, passes
    the limits that the float8 control fails."""
    res = one_run(tmp_path, "elastic-b10-offline", "--control", "bf16",
                  backend="ref")
    gaps = gap_checks(res, "elastic-b10-offline")
    assert gaps and all(c["value"] <= c["limit"] for c in gaps)


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "elastic-b05-chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_cpu():
    p = _cli(BENCH.parent)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_refuses_without_the_program(tmp_path):
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert "{" not in p.stdout


@pytest.mark.parametrize("config", ["qwen2-7b-elastic-ring",
                                    "qwen2-7b-paged-int8"])
def test_budget_rows_match_the_solver(config):
    """The head and expert top-k a configuration states for each routed
    budget are the ones the program's budget solver picks at its widths."""
    from smoke import _budget_row
    conf = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    for b, row in conf["elastic"]["budgets"].items():
        if row["routed"]:
            assert row == _budget_row(conf, float(b))

