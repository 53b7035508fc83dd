"""Operations and bytes that the served work needs, from shapes alone.

A lane of a step is ``(pos, n_new)``: the rows already in its cache and
the fresh rows it feeds.  Only what the algorithm needs counts: padded
rows of the fixed ``[slots, chunk]`` step, idle lanes and the lane
padding of the pool do not.  Configuration keys are those of the
benchmark's configuration files (Hugging Face names).
"""
from __future__ import annotations

from typing import Iterable

Lane = tuple[int, int]          # (pos, n_new)


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


def step_flops(cfg: dict, lanes: Iterable[Lane]) -> int:
    """One step's useful operations: every fed row through the layers'
    matrix products, attention over its real context, and the head at
    each active lane's last row."""
    d, *_, vocab, _ = _dims(cfg)
    lanes = [(p, n) for p, n in lanes if n > 0]
    rows = sum(n for _, n in lanes)
    return (2 * rows * matmul_params(cfg) + paged_attn_flops(cfg, lanes)
            + 2 * len(lanes) * d * vocab)
