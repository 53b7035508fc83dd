"""The dense family's work counts (``bench/families/dense.py``) against
counts worked out by hand at phi3-mini's published widths (the
benchmark's configuration file) and at phi3-medium's (GQA: 40 query
heads over 10 key/value heads), and the tests' MoE family's at its tiny
size."""
import json
from pathlib import Path

import pytest

from bench import spec
from bench.families import dense

step_mfu = spec.load_module(Path(__file__).resolve().parents[1] / "metrics"
                            / "step_mfu.py")

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
MEDIUM = {"hidden_size": 5120, "intermediate_size": 17920,
          "num_attention_heads": 40, "num_key_value_heads": 10,
          "num_hidden_layers": 10, "vocab_size": 32064}


def cfg(name):
    if name == "phi3-medium-14b":
        return MEDIUM
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_matmul_params():
    # per layer: q and o 3072x3072 each, k and v 3072x3072 each (MHA),
    # gate, up, down 3072x8192 each
    mini = 2 * 3072 * 3072 + 2 * 3072 * 3072 + 3 * 3072 * 8192
    assert dense.matmul_params(cfg("phi3-mini-3.8b")) == 32 * mini
    assert 32 * mini == 3_623_878_656
    # q and o 5120x5120, k and v 5120x1280 (10 kv heads of 128),
    # gate, up, down 5120x17920: 340.8 M a layer
    medium = 2 * 5120 * 5120 + 2 * 5120 * 1280 + 3 * 5120 * 17920
    assert medium == 340_787_200
    assert dense.matmul_params(cfg("phi3-medium-14b")) == 10 * medium


@pytest.mark.parametrize("name,lanes,flops,nbytes", [
    # one decode row at pos 100: it sees 101 keys.
    # mini: 32 layers x 4 x 32 heads x 96 x 101; bytes 32 x 2 B x 96 x
    # (2 x 32 kv x 101 rows + 2 x 32 heads x 1 row)
    ("phi3-mini-3.8b", [(100, 1)], 32 * 4 * 32 * 96 * 101,
     32 * 2 * 96 * (2 * 32 * 101 + 2 * 32 * 1)),
    # a 3-row prefill chunk at pos 16 sees 17 + 18 + 19 keys; an idle
    # lane counts nothing.  medium: 10 layers, 40 heads, 10 kv, 128
    ("phi3-medium-14b", [(16, 3), (0, 0)], 10 * 4 * 40 * 128 * (17 + 18 + 19),
     10 * 2 * 128 * (2 * 10 * 19 + 2 * 40 * 3)),
])
def test_paged_attention_work(name, lanes, flops, nbytes):
    c = cfg(name)
    assert dense.paged_attn_flops(c, lanes) == flops
    assert dense.paged_attn_bytes(c, lanes) == nbytes


def test_step_flops():
    c = cfg("phi3-medium-14b")
    lanes = [(16, 3), (40, 1), (0, 0)]
    want = (2 * 4 * 10 * 340_787_200                    # 4 fed rows
            + dense.paged_attn_flops(c, lanes)
            + 2 * 2 * 5120 * 32064)                      # head, 2 lanes
    assert step_mfu.step_flops(dense, c, lanes) == want


def test_moe_family_counts_the_router_and_top_k_experts():
    from bench.tests import tiny

    moe = spec.load_module(tiny.TESTS / "moe_family.py")
    c = tiny.MOE_CONFIG
    # d 128, 4 heads and 2 kv heads of 32, 8 experts of width 256, top-2:
    # q and o 128x128, k and v 128x64, router 128x8, two experts' gate,
    # up and down 128x256 each; 4 layers
    layer = 2 * 128 * 128 + 2 * 128 * 64 + 128 * 8 + 2 * 3 * 128 * 256
    assert layer == 246_784
    assert moe.matmul_params(c) == 4 * layer
    lanes = [(16, 3), (0, 0)]
    assert step_mfu.step_flops(moe, c, lanes) == (
        2 * 3 * 4 * layer + dense.paged_attn_flops(c, lanes) + 2 * 128 * 512)
