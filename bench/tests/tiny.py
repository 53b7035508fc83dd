"""A tiny cell for the CPU tests: a two-layer Phi-3-shaped model (GQA,
head_dim 16) with its own BENCHMARK.json, configuration and mix, laid
out in a temporary checkout root beside the real benchmark's readers and
reference."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

CONFIG = {
    "name": "tiny", "source": "tests", "reference": "phi3",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 500,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-05, "sliding_window": 2047,
    "tie_word_embeddings": False,
    "serving": {"slots": 4, "max_len": 256, "chunk": 8, "block_size": 16,
                "pool_pages": 96},
    "limits": {"widest_logit_gap": 0.05},
}
BACKLOG = {"arrivals": "backlog", "backlog_per_slot": 2, "requests": 1024,
           "prompt_tokens": [20, 40], "output_tokens": [8, 24],
           "sizes_seed": 1}


def make_root(tmp: Path, metrics=("output_tokens_per_s", "itl_p90_ms",
                                  "setup_s")) -> Path:
    """A checkout root holding the tiny cell ``tiny.backlog`` and the
    real benchmark's readers and reference."""
    root = Path(tmp)
    for sub in ("metrics", "references"):
        shutil.copytree(BENCH / sub, root / "bench" / sub, dirs_exist_ok=True)
    (root / "bench" / "configs").mkdir(parents=True, exist_ok=True)
    (root / "bench" / "traffic").mkdir(parents=True, exist_ok=True)
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(CONFIG))
    (root / "bench" / "traffic" / "tiny-backlog.json").write_text(
        json.dumps(BACKLOG))
    bench = {
        "configs": [{"name": "tiny", "file": "bench/configs/tiny.json"}],
        "workloads": [
            {"name": "tiny.backlog", "config": "tiny",
             "traffic": "tiny-backlog", "chips": 1}],
        "end_to_end": [{"name": m, "unit": "x"} for m in metrics],
        "per_layer": [],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
