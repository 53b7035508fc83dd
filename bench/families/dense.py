"""The dense decoder family: a configuration whose every layer is
attention and a gated MLP, served by the program's ``dense`` family.

What a family module gives the benchmark, found by the ``family`` key of
a configuration file (``bench/spec.py``):

- ``model_config(cfg)``: the program's ``ModelConfig``;
- ``draw(reference, cfg, want)``: a traceable function of the seed's key
  that draws the weights (``bench.weights``, the reference's shape
  tables) and lays them into the program's parameter tree, whose shapes
  are ``want`` (``bench.program.abstract_params``);
  ``bench.program.make_params`` puts it on the device, checked against
  the program's own layout;
- ``matmul_params``, ``paged_attn_flops`` and ``paged_attn_bytes``: the
  weights a fed row goes through and the attention's operations and
  bytes, from shapes alone, read by ``step_mfu`` (which composes a
  step's operations from them) and ``paged_attn_roofline``;
- ``longest_context(cfg, serving)``: the most positions a request of a
  cell of this configuration may fill.

A lane of a step is ``(pos, n_new)``: the rows already in its cache and
the fresh rows it feeds.  Only what the algorithm needs counts: padded
rows of the fixed ``[slots, chunk]`` step, idle lanes and the lane
padding of the pool do not.  Configuration keys are those of the
benchmark's configuration files (Hugging Face names).
"""
from __future__ import annotations

from typing import Iterable

import jax.numpy as jnp

from bench import weights

Lane = tuple[int, int]          # (pos, n_new)


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a benchmark configuration file,
    built here so that a later edit of the program's own architecture
    table cannot move the yardstick."""
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


def draw(reference, cfg: dict, want: dict):
    """The seed's key -> the program's tree: every layer drawn at once,
    the embedding and head padded with zeros to the program's vocabulary
    rows, so a padded id is never the largest logit and never an
    input."""
    vpad = want["embed"]["tok"].shape[0]
    lt, ot = reference.layer_table(cfg), reference.outer_table(cfg)
    n_layers = cfg["num_hidden_layers"]

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

    return make


def longest_context(cfg: dict, serving: dict) -> int:
    """A request may fill the engine's ``max_len`` less the row its next
    token would take, and no more than the configuration's window."""
    return min(serving["max_len"] - 1,
               cfg.get("sliding_window") or serving["max_len"])


def _dims(cfg: dict) -> tuple[int, int, int, int, int, int, int]:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // h
    return (d, h, cfg["num_key_value_heads"], hd, cfg["intermediate_size"],
            cfg["vocab_size"], cfg["num_hidden_layers"])


def matmul_params(cfg: dict) -> int:
    """Weights of the layers' matrix products (attention projections and
    the gated MLP); the embedding lookup, norms and head are apart."""
    d, h, kv, hd, f, _, layers = _dims(cfg)
    return layers * (2 * d * h * hd + 2 * d * kv * hd + 3 * d * f)


def _context_sum(lanes: Iterable[Lane]) -> int:
    """Keys attended over all fresh rows: row i of a lane sees
    ``pos + i + 1`` positions."""
    return sum(n * pos + n * (n + 1) // 2 for pos, n in lanes if n > 0)


def paged_attn_flops(cfg: dict, lanes: Iterable[Lane]) -> int:
    """QK^T and PV over each fresh row's causal context, every layer."""
    _, h, _, hd, _, _, layers = _dims(cfg)
    return layers * 4 * h * hd * _context_sum(lanes)


def paged_attn_bytes(cfg: dict, lanes: Iterable[Lane],
                     itemsize: int = 2) -> int:
    """Each active lane reads its K and V rows ``0 .. pos + n_new - 1``
    for every key/value head once, reads its queries and writes its
    outputs, every layer."""
    _, h, kv, hd, _, _, layers = _dims(cfg)
    lanes = [(p, n) for p, n in lanes if n > 0]
    kv_rows = sum(p + n for p, n in lanes)
    q_rows = sum(n for _, n in lanes)
    return layers * itemsize * hd * (2 * kv * kv_rows + 2 * h * q_rows)

