"""Tiny cells for the CPU tests, each with its own configuration, in a
temporary checkout root beside the real benchmark's readers, references
and families, under one BENCHMARK.json and one mix:

- ``tiny.backlog``: a two-layer Phi-3-shaped model (GQA, head_dim 16),
  the ``dense`` family and the ``phi3`` reference;
- ``tiny-moe.backlog``: the program's ``reduced(GRANITE_MOE_1B_A400M)``
  (four layers, 8 experts, top-2, tied head), whose family and
  reference are added to the root as new files (``moe_family.py``,
  ``moe_reference.py``), as a configuration that is not dense would be.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
TESTS = Path(__file__).resolve().parent

SERVING = {"slots": 4, "max_len": 256, "chunk": 8, "block_size": 16,
           "pool_pages": 96}
CONFIG = {
    "name": "tiny", "source": "tests", "reference": "phi3",
    "family": "dense",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 500,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-05, "sliding_window": 2047,
    "tie_word_embeddings": False,
    "serving": SERVING,
    "limits": {"widest_logit_gap": 0.05},
}


def _moe_config() -> dict:
    from repro.configs.archs import GRANITE_MOE_1B_A400M
    from repro.configs.base import reduced

    m = reduced(GRANITE_MOE_1B_A400M)
    return {
        "name": "tiny-moe", "source": "tests", "reference": "moe",
        "family": "moe",
        "hidden_size": m.d_model, "intermediate_size": m.d_ff,
        "num_attention_heads": m.n_heads, "num_key_value_heads": m.n_kv_heads,
        "head_dim": m.head_dim, "num_hidden_layers": m.n_layers,
        "vocab_size": m.vocab_size, "num_local_experts": m.n_experts,
        "num_experts_per_tok": m.top_k, "rope_theta": m.rope_theta,
        "rms_norm_eps": m.norm_eps, "tie_word_embeddings": m.tie_embeddings,
        "serving": SERVING,
        # CPU readings of the 1 s window, 20-30 seeds, each position
        # where the reference's route lies within moe_reference.TIE of a
        # tie left out (8-18% of them): sound runs read a widest gap of
        # 0.008-1.06 and the broken steps of
        # test_a_broken_step_is_not_correct 3.34-4.87, so 2.0.  The fp8
        # control reads 0.59-2.57 there, inside the sound range: a route
        # flipped by rounding at an earlier row still reaches later rows
        # through attention.  The share of positions over a gap of 0.1
        # parts them: sound 0-1.66%, the control 8.41-25%, so 4%.
        "limits": {"widest_logit_gap": 2.0,
                   "share_over_gap": {"gap": 0.1, "percent": 4.0}},
    }


MOE_CONFIG = _moe_config()
BACKLOG = {"arrivals": "backlog", "backlog_per_slot": 2, "requests": 1024,
           "prompt_tokens": [20, 40], "output_tokens": [8, 24],
           "sizes_seed": 1}
CELLS = {"tiny.backlog": CONFIG, "tiny-moe.backlog": MOE_CONFIG}


def make_root(tmp: Path, metrics=("output_tokens_per_s", "itl_p90_ms",
                                  "setup_s")) -> Path:
    """A checkout root holding the cells of ``CELLS`` and the real
    benchmark's readers, references and families."""
    root = Path(tmp)
    for sub in ("metrics", "references", "families"):
        shutil.copytree(BENCH / sub, root / "bench" / sub, dirs_exist_ok=True)
    shutil.copy(TESTS / "moe_family.py",
                root / "bench" / "families" / "moe.py")
    shutil.copy(TESTS / "moe_reference.py",
                root / "bench" / "references" / "moe.py")
    (root / "bench" / "configs").mkdir(parents=True, exist_ok=True)
    (root / "bench" / "traffic").mkdir(parents=True, exist_ok=True)
    (root / "bench" / "traffic" / "tiny-backlog.json").write_text(
        json.dumps(BACKLOG))
    configs, workloads = [], []
    for cell, cfg in CELLS.items():
        file = f"bench/configs/{cfg['name']}.json"
        (root / file).write_text(json.dumps(cfg))
        configs.append({"name": cfg["name"], "file": file})
        workloads.append({"name": cell, "config": cfg["name"],
                          "traffic": "tiny-backlog", "chips": 1})
    bench = {
        "configs": configs,
        "workloads": workloads,
        "end_to_end": [{"name": m, "unit": "x"} for m in metrics],
        "per_layer": [],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
