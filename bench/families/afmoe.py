"""The afmoe family (Arcee's Trinity models), served by the program's
``moe`` family: leading dense layers, then expert layers (sigmoid-routed
experts at this chip's share with a shared expert), gated grouped-query
attention on a pattern of windowed and full layers, and a norm on each
side of every branch.  ``bench/families/dense.py`` says what a family
module gives the benchmark; this one adds the held experts' work counts
read by ``bench/metrics/moe_ffn_roofline.py``.

A lane of a step is ``(pos, n_new)`` as in ``dense``.  The chip's share
is the configuration's ``expert_parallel`` block: ``num_experts`` held
of ``experts_routed`` from ``expert_offset`` on.
"""
from __future__ import annotations

from typing import Iterable

import jax
import jax.numpy as jnp

from bench import weights

Lane = tuple[int, int]          # (pos, n_new)


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for an afmoe configuration file; its
    layer pattern must be the file's ``layer_types``."""
    from repro.configs.base import ModelConfig

    ep = cfg["expert_parallel"]
    if cfg["score_func"] != "sigmoid" or cfg["n_group"] != 1:
        raise ValueError("the program routes by sigmoid scores with no "
                         "group limit")
    mc = ModelConfig(
        name=cfg["name"], family="moe",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["moe_intermediate_size"], vocab_size=cfg["vocab_size"],
        n_experts=ep["experts_routed"], top_k=cfg["num_experts_per_tok"],
        router="sigmoid", route_norm=cfg["route_norm"],
        route_scale=float(cfg["route_scale"]),
        n_shared_experts=cfg["num_shared_experts"],
        experts_held=cfg["num_experts"], expert_offset=ep["expert_offset"],
        n_dense_layers=cfg["num_dense_layers"],
        d_ff_dense=cfg["intermediate_size"],
        sliding_window=cfg["sliding_window"],
        global_every=cfg["global_attn_every_n_layers"], nope_global=True,
        attn_gate=True, sandwich_norm=True, embed_scale=cfg["mup_enabled"],
        qk_norm=True, rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], source=cfg["source"])
    if list(mc.layer_windows()) != _windows(cfg):
        raise ValueError("layer_types is not the program's pattern of a "
                         "full layer every global_attn_every_n_layers")
    return mc


def _windows(cfg: dict) -> list[int]:
    types = cfg["layer_types"][:cfg["num_hidden_layers"]]
    return [cfg["sliding_window"] if t == "sliding_attention" else 0
            for t in types]


def _layout(ly: dict, dense: bool) -> dict:
    """One stack of layers in the program's tree."""
    tree = {
        "ln1": ly["attn_norm"],
        "attn": {"wq": ly["wq"], "wk": ly["wk"], "wv": ly["wv"],
                 "wo": ly["wo"], "q_scale": ly["q_norm"],
                 "k_scale": ly["k_norm"], "wg": ly["w_gate_attn"]},
        "ln1_post": ly["attn_post_norm"],
        "ln2": ly["mlp_norm"],
        "ln2_post": ly["mlp_post_norm"],
    }
    if dense:
        tree["mlp"] = {"gate": ly["w_gate"], "up": ly["w_up"],
                       "down": ly["w_down"]}
    else:
        # the program keeps its router and selection bias in float32
        tree["moe"] = {
            "router": ly["router"].astype(jnp.float32),
            "bias": ly["expert_bias"].astype(jnp.float32),
            "gate": ly["expert_gate"], "up": ly["expert_up"],
            "down": ly["expert_down"],
            "shared": {"gate": ly["shared_gate"], "up": ly["shared_up"],
                       "down": ly["shared_down"]}}
    return tree


def draw(reference, cfg: dict, want: dict):
    """The seed's key -> the program's tree: the dense layers, then every
    expert layer at once (``reference.draw_layer``), the embedding and
    head padded with zeros to the program's vocabulary rows."""
    vpad = want["embed"]["tok"].shape[0]
    ot = reference.outer_table(cfg)
    n_dense, n_layers = cfg["num_dense_layers"], cfg["num_hidden_layers"]

    def make(key):
        dense = [reference.draw_layer(cfg, key, i) for i in range(n_dense)]
        dense = jax.tree.map(lambda *a: jnp.stack(a), *dense)
        moe = jax.vmap(lambda i: reference.draw_layer(cfg, key, i))(
            jnp.arange(n_dense, n_layers))
        o = weights.outer(key, ot)
        v = o["embed"].shape[0]
        return {
            "embed": {
                "tok": jnp.pad(o["embed"], ((0, vpad - v), (0, 0))),
                "head": jnp.pad(o["head"], ((0, 0), (0, vpad - v))),
            },
            "final_norm": o["final_norm"],
            "dense_layers": _layout(dense, True),
            "layers": _layout(moe, False),
        }

    return make


def longest_context(cfg: dict, serving: dict) -> int:
    """A request may fill the engine's ``max_len`` less the row its next
    token would take: the window binds the sliding layers alone."""
    return serving["max_len"] - 1


def _routed_rows(cfg: dict) -> float:
    """Rows a fed row sends to the held experts, expected:
    ``num_experts_per_tok`` x held / routed."""
    ep = cfg["expert_parallel"]
    return cfg["num_experts_per_tok"] * cfg["num_experts"] / ep["experts_routed"]


def matmul_params(cfg: dict) -> int:
    """Weights a fed row goes through in the layers' matrix products:
    the attention's projections with its output gate, every layer; the
    dense layers' MLP; the router, the shared expert and the expected
    held experts (:func:`_routed_rows`) of each expert layer."""
    d, h, kv, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    fe, ep = cfg["moe_intermediate_size"], cfg["expert_parallel"]
    n_dense = cfg["num_dense_layers"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    attn = 3 * d * h * hd + 2 * d * kv * hd
    dense = 3 * d * cfg["intermediate_size"]
    moe = (d * ep["experts_routed"] + 3 * d * fe * cfg["num_shared_experts"]
           + round(_routed_rows(cfg) * 3 * d * fe))
    return cfg["num_hidden_layers"] * attn + n_dense * dense + n_moe * moe


def _keys(pos: int, n: int, window: int) -> int:
    """Keys the fresh rows of a lane see: row ``i`` the last
    ``min(pos + i + 1, window)`` positions (window 0: all)."""
    return sum(min(pos + i + 1, window) if window else pos + i + 1
               for i in range(n))


def paged_attn_flops(cfg: dict, lanes: Iterable[Lane]) -> int:
    """QK^T and PV over each fresh row's keys, every layer: a windowed
    layer's row sees ``min(context, sliding_window)`` of them."""
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    lanes = [(p, n) for p, n in lanes if n > 0]
    return 4 * h * hd * sum(_keys(p, n, w) for w in _windows(cfg)
                            for p, n in lanes)


def paged_attn_bytes(cfg: dict, lanes: Iterable[Lane],
                     itemsize: int = 2) -> int:
    """Each active lane reads its K and V rows for every key/value head
    once (a windowed layer from its first row's window on: ``pos + n_new
    - max(pos - window + 1, 0)`` rows), reads its queries and writes its
    outputs, every layer."""
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    lanes = [(p, n) for p, n in lanes if n > 0]
    q_rows = sum(n for _, n in lanes)
    total = 0
    for w in _windows(cfg):
        kv_rows = sum(p + n - (max(p - w + 1, 0) if w else 0)
                      for p, n in lanes)
        total += 2 * kv * kv_rows + 2 * h * q_rows
    return itemsize * hd * total


def moe_ffn_flops(cfg: dict, lanes: Iterable[Lane]) -> int:
    """The held experts' gated MLPs over the rows routed to them,
    ``6 * d * moe_intermediate_size`` a row, at the expected
    :func:`_routed_rows` a fed row, every expert layer."""
    rows = sum(n for _, n in lanes if n > 0)
    n_moe = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    return round(n_moe * rows * _routed_rows(cfg) * 6 * cfg["hidden_size"]
                 * cfg["moe_intermediate_size"])


def moe_ffn_bytes(cfg: dict, lanes: Iterable[Lane],
                  itemsize: int = 2) -> int:
    """Every expert layer: the weights of each held expert a row is
    routed to, once (the expected number of them, each held expert taking
    a fed row with chance ``num_experts_per_tok`` / routed), and each
    routed row read and its output written."""
    d, fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = sum(n for _, n in lanes if n > 0)
    held, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    routed = cfg["expert_parallel"]["experts_routed"]
    hit = held * (1.0 - (1.0 - k / routed) ** rows) if rows else 0.0
    n_moe = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    return round(n_moe * itemsize * (hit * 3 * d * fe
                                     + rows * _routed_rows(cfg) * 2 * d))
