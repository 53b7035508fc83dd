"""A tiny afmoe cell for the CPU tests (``bench/tests/test_afmoe_cell.py``
and the program's ``tests/test_afmoe.py``): Trinity-Mini's layer at
small widths, in a temporary checkout root beside the real benchmark's
readers, references and families.

Eight layers (two dense, then six expert layers; three windowed to one
full, twice), 8 query heads over 2 kv heads (G = 4), 16 routed experts,
top-8, of which this chip holds 4 (experts 4-7) beside the shared one,
and a window of 16 positions at contexts up to 74, so the window binds.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

SERVING = {"slots": 4, "max_len": 96, "chunk": 8, "block_size": 8,
           "pool_pages": 64}
CONFIG = {
    "name": "tiny-afmoe", "source": "tests", "reference": "afmoe",
    "family": "afmoe",
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 8, "num_dense_layers": 2, "num_experts": 4,
    "num_experts_per_tok": 8, "num_shared_experts": 1, "vocab_size": 500,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 2,
    "sliding_window": 16, "global_attn_every_n_layers": 4,
    "score_func": "sigmoid", "n_group": 1, "route_norm": True,
    "route_scale": 2.826, "mup_enabled": True, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-05, "tie_word_embeddings": False,
    "expert_parallel": {"experts_routed": 16, "expert_offset": 4},
    "serving": SERVING,
    # CPU readings of the 1 s window on 8 seeds, positions within the
    # reference's TIE of a held expert's route left out: sound runs read
    # a widest gap of 0.19-0.39 and 0.32-1.03% of positions over 0.1; the
    # fp8 control 0.57-1.01 and 13.9-18.2%; the steps of test_afmoe_cell
    # without a mechanism (2 seeds each) 3.6-5.5 and 74-90%, softmax
    # routing 0.86-1.41 and 21%.  The share parts sound runs from the
    # control and sits at their geometric middle; the widest gap, as on
    # the chip (PERF.md), is twice the largest sound reading, a guard
    # against gross faults
    "limits": {"widest_logit_gap": 0.8,
               "share_over_gap": {"gap": 0.1, "percent": 4.0}},
}
MIX = {"arrivals": "backlog", "backlog_per_slot": 2, "requests": 256,
       "prompt_tokens": [20, 40], "output_tokens": [16, 34],
       "sizes_seed": 1}
CELL = "tiny-afmoe.backlog"


def make_root(tmp: Path) -> Path:
    """A checkout root holding the tiny afmoe cell and the real
    benchmark's readers, references and families."""
    root = Path(tmp)
    for sub in ("metrics", "references", "families"):
        shutil.copytree(BENCH / sub, root / "bench" / sub, dirs_exist_ok=True)
    (root / "bench" / "configs").mkdir(parents=True, exist_ok=True)
    (root / "bench" / "traffic").mkdir(parents=True, exist_ok=True)
    (root / "bench" / "traffic" / "tiny-long.json").write_text(
        json.dumps(MIX))
    (root / "bench" / "configs" / "tiny-afmoe.json").write_text(
        json.dumps(CONFIG))
    bench = {
        "configs": [{"name": CONFIG["name"],
                     "file": "bench/configs/tiny-afmoe.json"}],
        "workloads": [{"name": CELL, "config": CONFIG["name"],
                       "traffic": "tiny-long", "chips": 1}],
        "end_to_end": [{"name": m, "unit": "x"}
                       for m in ("output_tokens_per_s", "setup_s")],
        "per_layer": [],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
