"""Plain reference of the Phi-3 decoder (arXiv:2404.14219; the
``Phi3ForCausalLM`` of Hugging Face transformers), in float32 at the
highest matmul precision, with no cache, kernel or batching.

Per layer: RMSNorm, attention with rotary position embedding (the
``rotate_half`` form, theta from the configuration) and grouped key/value
heads (query head ``j`` reads key/value head ``j // group``), causal
within the configuration's sliding window; residual add; RMSNorm, the
gated MLP ``down(silu(gate(x)) * up(x))``; residual add.  Then RMSNorm
and the untied head.  Phi-3 stores ``qkv_proj`` and ``gate_up_proj``
fused; they are kept apart here, which is the same arithmetic.

It takes nothing from the system under test: the weights are drawn again
from the seed's key (``bench.weights``), one layer at a time, so that a
layer's float32 copy is all that is held beside the residual streams.
``quantize`` rounds every weight matrix first (the control).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

HIGHEST = jax.lax.Precision.HIGHEST


def dims(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // h
    return dict(d=d, h=h, kv=cfg["num_key_value_heads"], hd=hd,
                f=cfg["intermediate_size"], v=cfg["vocab_size"],
                layers=cfg["num_hidden_layers"])


def layer_table(cfg: dict) -> dict:
    """Name -> (shape, fan_in) of one layer; fan_in None: a norm."""
    n = dims(cfg)
    d, q, kv, f = n["d"], n["h"] * n["hd"], n["kv"] * n["hd"], n["f"]
    return {
        "attn_norm": ((d,), None),
        "wq": ((d, q), d), "wk": ((d, kv), d), "wv": ((d, kv), d),
        "wo": ((q, d), q),
        "mlp_norm": ((d,), None),
        "w_gate": ((d, f), d), "w_up": ((d, f), d), "w_down": ((f, d), f),
    }


def outer_table(cfg: dict) -> dict:
    n = dims(cfg)
    return {"embed": ((n["v"], n["d"]), n["d"]),
            "head": ((n["d"], n["v"]), n["d"]),
            "final_norm": ((n["d"],), None)}


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x [S, H, hd], pos [S]."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg: dict, p: dict, x: jax.Array) -> jax.Array:
    """One decoder layer over one sequence x [S, d] (float32)."""
    n = dims(cfg)
    s, h, kv, hd = x.shape[0], n["h"], n["kv"], n["hd"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    pos = jnp.arange(s)
    a = _rmsnorm(x, p["attn_norm"], eps)
    q = _rope(_mm(a, p["wq"]).reshape(s, h, hd), pos, theta)
    k = _rope(_mm(a, p["wk"]).reshape(s, kv, hd), pos, theta)
    v = _mm(a, p["wv"]).reshape(s, kv, hd)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * hd ** -0.5
    window = cfg.get("sliding_window") or s
    seen = ((pos[None, :] <= pos[:, None])
            & (pos[None, :] > pos[:, None] - window))
    scores = jnp.where(seen[None], scores, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v,
                   precision=HIGHEST)
    x = x + _mm(o.reshape(s, h * hd), p["wo"])
    m = _rmsnorm(x, p["mlp_norm"], eps)
    return x + _mm(jax.nn.silu(_mm(m, p["w_gate"])) * _mm(m, p["w_up"]),
                   p["w_down"])


def logits(cfg: dict, seed: int, tokens: list[list[int]],
           positions: list[list[int]],
           quantize: Callable[[jax.Array], jax.Array] | None = None
           ) -> list[np.ndarray]:
    """float32 logits [len(positions[b]), vocab] of each sequence
    ``tokens[b]`` at ``positions[b]``: the reference's forward over the
    whole sequence, causal, so the row at position ``t`` predicts token
    ``t + 1``.  Sequences run one at a time, padded at the end to one
    length (padding after a position cannot change it)."""
    n = dims(cfg)
    width = -(-max(len(t) for t in tokens) // 128) * 128
    base = weights.base_key(seed)
    lt, ot = layer_table(cfg), outer_table(cfg)
    q = quantize or (lambda w: w)

    def f32(tree):
        return {k: q(w.astype(jnp.float32)) for k, w in tree.items()}

    gen_layer = jax.jit(lambda key, i: jax.tree.map(
        lambda a: a[0], weights.layers(key, lt, i[None])))
    run_layer = jax.jit(lambda p, x: _layer(cfg, f32(p), x))

    @jax.jit
    def embed(key, toks):
        return f32(weights.outer(key, ot))["embed"][toks]

    @jax.jit
    def head(key, x, rows):
        o = f32(weights.outer(key, ot))
        return _mm(_rmsnorm(x[rows], o["final_norm"], cfg["rms_norm_eps"]),
                   o["head"])

    xs = []
    for t in tokens:
        padded = np.zeros((width,), np.int32)
        padded[:len(t)] = t
        xs.append(embed(base, jnp.asarray(padded)))
    for i in range(n["layers"]):
        p = gen_layer(base, jnp.int32(i))
        xs = [run_layer(p, x) for x in xs]
        del p
    out = []
    for x, r in zip(xs, positions):
        # rows padded to a multiple of 128, so that few shapes compile
        rows = np.zeros((-(-len(r) // 128) * 128,), np.int32)
        rows[:len(r)] = r
        out.append(np.asarray(head(base, x, jnp.asarray(rows)))[:len(r)])
    return out
