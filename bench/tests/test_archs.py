"""A configuration of another architecture takes only new files: its own
``archs/<model_type>/``, a configuration, a traffic mix and
``BENCHMARK.json`` entries; no file the benchmark has is edited."""
import hashlib
import io
import json
import shutil
from contextlib import redirect_stdout
from pathlib import Path

import pytest

import archs
import run
from conftest import BENCH
from smoke import smoke_checkout

TOY = Path(__file__).parent / "data" / "toy_arch"


def _files(root: Path) -> dict:
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_new_architecture_by_files_alone(tmp_path):
    b = smoke_checkout(tmp_path)
    before = _files(tmp_path)
    spec_before = json.loads((tmp_path / "BENCHMARK.json").read_text())
    added = _files(TOY)
    assert not any((b / rel).exists() for rel in added)
    shutil.copytree(TOY, b, dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "toy-llama", "source": "a test",
                            "file": "bench/configs/toy-llama.json",
                            "reduced": [], "why": "a test"})
    spec["workloads"].append({"name": "toy-llama-chat",
                              "config": "toy-llama",
                              "traffic": "toy-llama-chat", "chips": 1,
                              "why": "a test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    out = io.StringIO()
    with redirect_stdout(out):
        run.main(["--workload", "toy-llama-chat", "--seed", "3000000007",
                  "--seconds", "2", "--trace", "0"], require_tpu=False,
                 bench_dir=b, overrides={"kernel_backend": "ref"})
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    assert res["correct"] is True, res["check"]
    assert res["check"]["max_gap"]["value"] <= 0.35
    assert set(res["metrics"]) == {"itl_p50_ms", "setup_s"}

    # every file that was there is as it was, but for the entries added to
    # BENCHMARK.json; everything else is new
    after = _files(tmp_path)
    changed = {p for p in before if after.get(p) != before[p]}
    assert changed == {Path("BENCHMARK.json")}
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert spec[key][:len(spec_before[key])] == spec_before[key]
    assert set(after) - set(before) >= {Path("bench") / p for p in added}


def test_unknown_model_type_names_the_path(tmp_path):
    conf = {"name": "x", "model_type": "no-such-arch"}
    with pytest.raises(SystemExit, match=str(tmp_path / "archs" /
                                             "no-such-arch")):
        archs.load(conf, tmp_path)


def test_qwen2_modules_resolve_once():
    conf = json.loads((BENCH / "configs" / "qwen2-7b-paged-int8.json")
                      .read_text())
    a, b = archs.load(conf, BENCH), archs.load(conf, BENCH)
    assert a.weights is b.weights and a.reference is b.reference
    assert a.weights.dims(conf)["L"] == 8
