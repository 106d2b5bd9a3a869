"""A copy of the benchmark's files at a size the CPU runs in seconds: the
real traffic mixes and configurations with every width and length cut,
laid out as a checkout (``BENCHMARK.json`` beside ``bench/``)."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
SMALL = {"hidden_size": 64, "intermediate_size": 176,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "num_hidden_layers": 4, "vocab_size": 2048}
# a configuration of a cell on 4 chips: as many kv heads as chips, so that
# each chip holds one of them and two q heads, as qwen2-7b's one and seven
SMALL_TP = dict(SMALL, hidden_size=128, num_attention_heads=8,
                num_key_value_heads=4)


def _budget_row(conf: dict, budget: float) -> dict:
    """The head and expert top-k the program's budget solver gives at these
    small widths (the configuration files state them for the real ones)."""
    import dataclasses
    from repro.configs import get_config, get_elastic
    from repro.core.policy import as_spec_policy, solve_budget
    D, H = conf["hidden_size"], conf["num_attention_heads"]
    cfg = dataclasses.replace(
        get_config(conf["registry_name"]), n_layers=conf["num_hidden_layers"],
        d_model=D, n_heads=H, n_kv_heads=conf["num_key_value_heads"],
        d_head=D // H, d_ff=conf["intermediate_size"],
        vocab_size=conf["vocab_size"])
    el = conf["elastic"]
    ecfg = dataclasses.replace(
        get_elastic(conf["registry_name"], cfg),
        mlp_n_experts=el["mlp_n_experts"] or None,
        mlp_expert_topk=el["mlp_expert_topk"] or None)
    pol = solve_budget(cfg, as_spec_policy(ecfg)[0], budget, static=True)
    row = {"routed": True, "head_topk": int(pol.mha_head_topk)}
    if el["mlp_n_experts"]:
        row["expert_topk"] = int(pol.mlp_expert_topk)
    return row


def smoke_checkout(tmp: Path, rate: float = 8.0) -> Path:
    """Write a small checkout under ``tmp``; returns its bench directory."""
    b = tmp / "bench"
    for sub in ("configs", "workloads"):
        (b / sub).mkdir(parents=True, exist_ok=True)
    for sub in ("metrics", "archs"):
        shutil.copytree(BENCH / sub, b / sub, dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(BENCH / "peaks.json", b / "peaks.json")
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp / "BENCHMARK.json")
    spec = json.loads((tmp / "BENCHMARK.json").read_text())
    tp = {w["config"] for w in spec["workloads"] if w["chips"] > 1}
    tp_cells = {w["traffic"] for w in spec["workloads"] if w["chips"] > 1}
    for f in (BENCH / "configs").glob("*.json"):
        c = json.loads(f.read_text())
        c.update(SMALL_TP if f.stem in tp else SMALL)
        c["engine"] = dict(c["engine"], slots=4, max_seq=96)
        for b_ in c["elastic"]["budgets"]:
            if c["elastic"]["budgets"][b_]["routed"]:
                c["elastic"]["budgets"][b_] = _budget_row(c, float(b_))
        (b / "configs" / f.name).write_text(json.dumps(c))
    for f in (BENCH / "workloads").glob("*.json"):
        w = json.loads(f.read_text())
        w["prompt"] = dict(w["prompt"], median=20, lo=8, hi=40)
        if w["prompt"].get("buckets"):
            w["prompt"]["buckets"] = [16, 32]
        w["output"] = dict(w["output"], median=6, lo=2, hi=12)
        for c in w.get("classes", []):
            if "output" in c:
                c["output"] = dict(w["output"])
        if w["arrivals"]["kind"] == "offline":
            # the four-chip cell's run is held to `correct`, which needs
            # requests left at the close: a 2 s window finishes some 64
            w["arrivals"]["backlog"] = 256 if f.stem in tp_cells else 64
        else:
            w["arrivals"]["rate"] = rate
        w["check"] = dict(w["check"], tokens=64, max_requests=8,
                          ref_length=64)
        (b / "workloads" / f.name).write_text(json.dumps(w))
    return b
