"""The lfm2_moe architecture's files: found by its ``model_type``, weights
in the program's own layout, the ``moe_gmm`` roofline reader, and a whole
run of the cell at a size the CPU holds."""
import dataclasses
import io
import json
from contextlib import redirect_stdout

import pytest

import archs
import faults
import run
from conftest import BENCH
from smoke import smoke_checkout

CELL = "lfm2moe-b10-chat"
SHRUNK = {"hidden_size": 64, "intermediate_size": 96,
          "moe_intermediate_size": 32, "num_experts": 8,
          "num_attention_heads": 4, "num_key_value_heads": 2,
          "num_hidden_layers": 10, "vocab_size": 512}


def conf(**kw):
    c = json.loads((BENCH / "configs" / "lfm2-8b-a1b-ring.json").read_text())
    c.update(kw)
    return c


def test_archs_load_finds_lfm2_moe():
    mods = archs.load(conf(), BENCH)
    d = mods.weights.dims(conf())
    assert (d["L"], d["L_moe"], d["L_attn"]) == (10, 8, 2)
    assert mods.program.__file__.endswith("lfm2_moe/program.py")


@pytest.mark.parametrize("layers", [10, 4, 7])
def test_weights_match_program_layout(layers):
    """The made weights have exactly the tree, shapes and dtypes of the
    program's ``model_init``/``router_init`` (lead layers, a scanned
    period, a tail)."""
    c = conf(**dict(SHRUNK, num_hidden_layers=layers))
    mods = archs.load(c, BENCH)
    cfg, ecfg = mods.program.model_config(c, {})
    params, rp = mods.weights.make_weights(c, 5)
    run.check_layout(cfg, ecfg, params, rp)
    assert ("lead" in params) and len(params["lead"]) == 2
    if layers == 10:
        assert len(params["scan"]) == 4 and not params["tail"]
        assert params["scan"][0]["mlp"]["wi"].shape == (2, 8, 64, 32)


def test_program_config_is_published_at_full_depth():
    """At the published depth the file's keys give the registry's config."""
    from repro.configs import get_config
    c = conf(num_hidden_layers=24)
    cfg, _ = archs.load(c, BENCH).program.model_config(c, {})
    full = get_config("lfm2-8b-a1b")
    assert dataclasses.replace(cfg, eos_id=full.eos_id) == full


def _reader():
    return run.load_reader("moe_gmm_roofline", BENCH)


def test_roofline_reader_hand_counted():
    """Two prompts of 128 and 256 tokens, two MoE layers of 4 experts of
    width 8 at D 16, top 2; a peak of 1e6 flop/s and 1e3 bytes/s."""
    d = {"D": 16, "Fe": 8, "E": 4, "k": 2, "L_moe": 2}
    peaks = {"bf16_flops": 1e6, "hbm_bytes_per_s": 1e3}
    ctx = {"cell": {"budget": 1.0}, "dims": d, "peaks": peaks}
    rec = {"steps": [{"admitted": [128]}, {"admitted": []},
                     {"admitted": [256]}]}
    red = {"kernel_s": {"moe_gmm": 10.0}}
    least = 0.0
    for p in (128, 256):
        flops = 2 * 3 * p * 2 * 16 * 8 * 2
        bytes_ = (2 * p * 2 * 16 + 3 * 4 * 16 * 8) * 2 * 2
        least += max(flops / 1e6, bytes_ / 1e3)
    assert _reader()(red, rec, ctx) == pytest.approx(100.0 * least / 10.0)
    assert _reader()(red, {"steps": [{"admitted": []}]}, ctx) is None
    assert _reader()(red, rec, dict(ctx, cell={"budget": 0.5})) is None


@pytest.mark.parametrize("fault", [None, faults.token_altered,
                                   faults.state_unchanged],
                         ids=["program", "token_altered", "state_unchanged"])
def test_shrunk_cell_is_correct(tmp_path, fault):
    """The shrunk cell runs through ``run.main`` and comes out correct; a
    fault planted under the timed path turns it false."""
    b = smoke_checkout(tmp_path)
    out = io.StringIO()
    with redirect_stdout(out):
        run.main(["--workload", CELL, "--seed", "3000000007", "--seconds",
                  "2", "--trace", "0"], require_tpu=False, bench_dir=b,
                 overrides={"kernel_backend": "ref"}, fault=fault)
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    assert res["correct"] is (fault is None), res["check"]
    assert set(res["metrics"]) == {"itl_p50_ms", "setup_s"}
    assert res["check"]["backlog_left"]["value"] >= 1
