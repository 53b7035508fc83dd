"""A family module for the program's ``moe`` family: attention and a
routed mixture of gated-SiLU experts in every layer, the head tied to
the embedding (an untied configuration fails the layout check).
``tiny.make_root`` writes it into a temporary checkout as
``bench/families/moe.py``, beside ``moe_reference.py`` as
``bench/references/moe.py``, to show that a configuration that is not
dense enters the benchmark as added files alone.

The program drops the rows that overflow an expert's capacity,
``top_k * capacity_factor / n_experts`` of a step's rows.  Here
``capacity_factor`` is ``n_experts / top_k``: an expert can take every
row of a step, so no row is dropped and the reference need not know the
capacity.  Dropless routing in the program itself is for the change that
adds an MoE configuration to the benchmark.
"""
from __future__ import annotations

import jax.numpy as jnp

from bench import weights
from bench.families import dense

# attention is the dense family's: the same paged kernel over the same
# K/V rows, and the same context limit
paged_attn_flops = dense.paged_attn_flops
paged_attn_bytes = dense.paged_attn_bytes
longest_context = dense.longest_context


def model_config(cfg: dict):
    from repro.configs.base import ModelConfig

    e, k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    return ModelConfig(
        name=cfg["name"], family="moe",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        n_experts=e, top_k=k, capacity_factor=e / k,
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        source=cfg["source"])


def draw(reference, cfg: dict, want: dict):
    vpad = want["embed"]["tok"].shape[0]
    lt, ot = reference.layer_table(cfg), reference.outer_table(cfg)
    n_layers = cfg["num_hidden_layers"]

    def make(key):
        ly = weights.layers(key, lt, jnp.arange(n_layers))
        o = weights.outer(key, ot)
        v = o["embed"].shape[0]
        return {
            "embed": {"tok": jnp.pad(o["embed"], ((0, vpad - v), (0, 0)))},
            "final_norm": o["final_norm"],
            "layers": {
                "ln1": ly["attn_norm"],
                "attn": {k: ly[k] for k in ("wq", "wk", "wv", "wo")},
                "ln2": ly["mlp_norm"],
                # the program keeps its router in float32
                "moe": {"router": ly["router"].astype(jnp.float32),
                        "gate": ly["w_gate"], "up": ly["w_up"],
                        "down": ly["w_down"]},
            },
        }

    return make


def matmul_params(cfg: dict) -> int:
    """Weights a row goes through in the layers' matrix products: the
    attention projections, the router and its ``top_k`` experts."""
    d, h, kv = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    hd, f = cfg["head_dim"], cfg["intermediate_size"]
    per_layer = (2 * d * h * hd + 2 * d * kv * hd
                 + d * cfg["num_local_experts"]
                 + cfg["num_experts_per_tok"] * 3 * d * f)
    return cfg["num_hidden_layers"] * per_layer

