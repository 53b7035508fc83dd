"""The system under test, built from a benchmark configuration: the
repo's ``ModelConfig``, its weights (drawn by ``bench.weights`` and laid
into the program's parameter tree), and the paged serving engine built
as ``repro.launch.serve.run_serve`` builds it (paged kernel, prefix cache
on, swap tier on; greedy requests).

This is the one module of the benchmark that imports the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import weights


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a benchmark configuration file
    (Hugging Face key names), built here so that a later edit of the
    program's own architecture table cannot move the yardstick."""
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim")
        or cfg["hidden_size"] // cfg["num_attention_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], source=cfg["source"])


def build_model(cfg: dict):
    from repro.models import build

    return build(model_config(cfg))


def make_params(model, reference, cfg: dict, seed: int):
    """The seed's weights in the program's tree, made on the device in
    one jitted call (the seed's key is an argument, so every seed runs
    the same compiled program).  The program pads the vocabulary of its
    embedding and head; the padded rows and columns are zeros, so a
    padded id is never the largest logit and never an input."""
    want = jax.eval_shape(model.init_params, jax.random.key(0))
    vpad = want["embed"]["tok"].shape[0]
    lt, ot = reference.layer_table(cfg), reference.outer_table(cfg)
    n_layers = cfg["num_hidden_layers"]

    @jax.jit
    def make(key):
        ly = weights.layers(key, lt, jnp.arange(n_layers))
        o = weights.outer(key, ot)
        v = o["embed"].shape[0]
        return {
            "embed": {
                "tok": jnp.pad(o["embed"], ((0, vpad - v), (0, 0))),
                "head": jnp.pad(o["head"], ((0, 0), (0, vpad - v))),
            },
            "final_norm": o["final_norm"],
            "layers": {
                "ln1": ly["attn_norm"],
                "attn": {k: ly[k] for k in ("wq", "wk", "wv", "wo")},
                "ln2": ly["mlp_norm"],
                "mlp": {"gate": ly["w_gate"], "up": ly["w_up"],
                        "down": ly["w_down"]},
            },
        }

    got = jax.eval_shape(make, weights.base_key(seed))

    def layout(tree):
        return jax.tree.structure(tree), [(a.shape, a.dtype)
                                          for a in jax.tree.leaves(tree)]

    if layout(got) != layout(want):
        raise ValueError(f"the program's parameter tree changed: it wants "
                         f"{want}, the benchmark lays out {got}")
    return make(weights.base_key(seed))


def build_engine(model, params, serving: dict):
    from repro.serve import PagedServeEngine

    return PagedServeEngine(
        model, params, slots=serving["slots"], max_len=serving["max_len"],
        block_size=serving["block_size"], chunk=serving["chunk"],
        num_blocks=serving["pool_pages"], use_prefix_cache=True,
        kernel="paged", swap=True)


def request(rid: int, prompt: list[int], max_new: int):
    from repro.serve import Request

    return Request(rid=rid, prompt=prompt, max_new=max_new)
