"""counts.py against arithmetic done by hand at qwen2-7b's widths."""
import json

import pytest

import archs
import counts
from conftest import BENCH

CONF = json.loads((BENCH / "configs" / "qwen2-7b-elastic-ring.json")
                  .read_text())
D = archs.load(CONF, BENCH).weights.dims(CONF)
PEAKS = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]


def test_dims_are_qwen2_7b():
    assert (D["D"], D["H"], D["K"], D["Dh"], D["F"], D["V"], D["L"]) == \
        (3584, 28, 4, 128, 18944, 152064, 8)


def test_decode_attention_one_slot():
    # one slot at 1000 positions: K and V rows 1000 x 4 x 128 x 2 B each,
    # q in and context out 28 x 128 x 2 B each, per layer, 8 layers
    f, b = counts.decode_attention(D, [1000])
    assert f == 4 * 28 * 128 * 1000 * 8
    assert b == (2 * 1000 * 4 * 128 * 2 + 2 * 28 * 128 * 2) * 8
    # 16.7 MB at 819 GB/s: memory-bound
    t, bound = counts.roofline_seconds(f, b, PEAKS)
    assert bound == "memory" and t == pytest.approx(b / 819e9)


def test_paged_int8_adds_scales():
    f, b = counts.paged_decode_attention(D, [16])
    assert b == (2 * 16 * 4 * 128 + 2 * 16 * 4 * 4 + 2 * 28 * 128 * 2) * 8
    assert f == counts.decode_attention(D, [16])[0]


def test_flash_attention_causal():
    f, b = counts.flash_attention(D, 4)
    assert f == 4 * 28 * 128 * 10 * 8           # 4 + 3 + 2 + 1 pairs
    assert b == 4 * (56 + 8) * 128 * 2 * 8


def test_fused_mlp_weight_bytes():
    # 407 MB of bf16 weights a layer: 16 tokens take 0.497 ms at 819 GB/s
    f, b = counts.fused_mlp(D, 16)
    per_layer = 3 * 3584 * 18944 * 2
    assert per_layer == 407_371_776
    assert b == (per_layer + 2 * 16 * 3584 * 2) * 8
    assert f == 2 * 3 * 16 * 3584 * 18944 * 8
    t, bound = counts.roofline_seconds(f, b, PEAKS)
    assert bound == "memory"
    assert t / 8 == pytest.approx(0.497e-3, rel=0.01)


def test_token_and_prompt_flops():
    lin = 2 * 3584 * (56 + 8) * 128 + 6 * 3584 * 18944
    assert counts.token_flops(D, 10, False) == (lin + 4 * 28 * 128 * 10) * 8
    head = 2 * 3584 * 152064
    assert counts.token_flops(D, 10, True) - \
        counts.token_flops(D, 10, False) == head
    # a prompt of 3: contexts 1, 2, 3 and one LM head
    want = sum(counts.token_flops(D, c, False) for c in (1, 2, 3)) + head
    assert counts.prompt_flops(D, 3) == want
