"""Plain float32 reference of the program's ``moe`` decoder family, at
the highest matmul precision, with no cache, kernel, batching or expert
capacity (``tiny.make_root`` writes it as ``bench/references/moe.py``).

Per layer: RMSNorm, attention with rotary position embedding (the
``rotate_half`` form) and grouped key/value heads, causal; residual add;
RMSNorm, then the router's softmax over every expert, the ``top_k``
largest kept and renormalised to sum to 1, and the sum of the chosen
experts' gated MLPs ``down(silu(gate(x)) * up(x))`` under those weights;
residual add.  Then RMSNorm and the head tied to the embedding.  The
weights are drawn again from the seed's key (``bench.weights``), one
layer at a time.  ``quantize`` rounds every weight matrix first, each
expert's on its own (the control).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

HIGHEST = jax.lax.Precision.HIGHEST
# A row whose ``top_k``-th router logit lies less than this above the next
# one, in any layer, is routed by rounding: the program's bfloat16
# activations flip such a route on some seeds and move the row's logits
# by up to about 1.5.  Its position is left undecided (a row of NaN).  On
# the CPU fixture every flipped route read a margin of 0.0002-0.0091
# against a typical 0.2, and 0.01 leaves 8-18% of positions undecided.
TIE = 0.01


def layer_table(cfg: dict) -> dict:
    """Name -> (shape, fan_in) of one layer; fan_in None: a norm."""
    d, e, f = (cfg["hidden_size"], cfg["num_local_experts"],
               cfg["intermediate_size"])
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return {
        "attn_norm": ((d,), None),
        "wq": ((d, q), d), "wk": ((d, kv), d), "wv": ((d, kv), d),
        "wo": ((q, d), q),
        "mlp_norm": ((d,), None),
        "router": ((d, e), d),
        "w_gate": ((e, d, f), d), "w_up": ((e, d, f), d),
        "w_down": ((e, f, d), f),
    }


def outer_table(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    return {"embed": ((cfg["vocab_size"], d), d), "final_norm": ((d,), None)}


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


def _experts(cfg: dict, p: dict, m: jax.Array
             ) -> tuple[jax.Array, jax.Array]:
    """The routed experts' sum over rows m [S, d], and each row's margin:
    how far its ``top_k``-th router logit lies above the next."""
    k = cfg["num_experts_per_tok"]
    scores = _mm(m, p["router"])
    ranked = jax.lax.top_k(scores, k + 1)[0]
    probs = jax.nn.softmax(scores, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    gates = jnp.zeros_like(probs).at[jnp.arange(m.shape[0])[:, None],
                                     top_i].set(
        top_p / jnp.sum(top_p, axis=-1, keepdims=True))
    h = (jax.nn.silu(jnp.einsum("sd,edf->esf", m, p["w_gate"],
                                precision=HIGHEST))
         * jnp.einsum("sd,edf->esf", m, p["w_up"], precision=HIGHEST))
    y = jnp.einsum("esf,efd->esd", h, p["w_down"], precision=HIGHEST)
    return (jnp.einsum("se,esd->sd", gates, y, precision=HIGHEST),
            ranked[:, k - 1] - ranked[:, k])


def _layer(cfg: dict, p: dict, x: jax.Array
           ) -> tuple[jax.Array, jax.Array]:
    """One decoder layer over one sequence x [S, d] (float32), and each
    row's routing margin."""
    s, h = x.shape[0], cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd, eps, theta = cfg["head_dim"], cfg["rms_norm_eps"], cfg["rope_theta"]
    pos = jnp.arange(s)
    a = _rmsnorm(x, p["attn_norm"], eps)
    q = _rope(_mm(a, p["wq"]).reshape(s, h, hd), pos, theta)
    k = jnp.repeat(_rope(_mm(a, p["wk"]).reshape(s, kv, hd), pos, theta),
                   h // kv, axis=1)
    v = jnp.repeat(_mm(a, p["wv"]).reshape(s, kv, hd), h // kv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * hd ** -0.5
    scores = jnp.where((pos[None, :] <= pos[:, None])[None], scores, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v,
                   precision=HIGHEST)
    x = x + _mm(o.reshape(s, h * hd), p["wo"])
    y, margin = _experts(cfg, p, _rmsnorm(x, p["mlp_norm"], eps))
    return x + y, margin


def logits(cfg: dict, seed: int, tokens: list[list[int]],
           positions: list[list[int]],
           quantize: Callable[[jax.Array], jax.Array] | None = None
           ) -> list[np.ndarray]:
    """float32 logits [len(positions[b]), vocab] of each sequence
    ``tokens[b]`` at ``positions[b]``, causal, as ``phi3.logits``.  Without
    ``quantize``, a position whose own row's route lies within ``TIE`` of
    a tie in some layer is a row of NaN: ``bench.check`` leaves it
    out.  A flipped route of an earlier row still reaches later rows
    through attention; that is not marked."""
    width = -(-max(len(t) for t in tokens) // 128) * 128
    base = weights.base_key(seed)
    lt, ot = layer_table(cfg), outer_table(cfg)
    q = quantize or (lambda w: w)

    def f32(tree):
        return {k: (jax.vmap(q) if w.ndim == 3 else q)(w.astype(jnp.float32))
                for k, w in tree.items()}

    gen_layer = jax.jit(lambda key, i: jax.tree.map(
        lambda a: a[0], weights.layers(key, lt, i[None])))
    run_layer = jax.jit(lambda p, x: _layer(cfg, f32(p), x))

    @jax.jit
    def embed(key, toks):
        return f32(weights.outer(key, ot))["embed"][toks]

    @jax.jit
    def head(key, x, rows):
        o = weights.outer(key, ot)
        w = q(o["embed"].astype(jnp.float32).T)
        return _mm(_rmsnorm(x[rows], o["final_norm"].astype(jnp.float32),
                            cfg["rms_norm_eps"]), w)

    xs = []
    for t in tokens:
        padded = np.zeros((width,), np.int32)
        padded[:len(t)] = t
        xs.append(embed(base, jnp.asarray(padded)))
    margins = [np.full((width,), np.inf, np.float32) for _ in xs]
    for i in range(cfg["num_hidden_layers"]):
        p = gen_layer(base, jnp.int32(i))
        for b, x in enumerate(xs):
            xs[b], m = run_layer(p, x)
            margins[b] = np.minimum(margins[b], np.asarray(m))
        del p
    out = []
    for x, r, m in zip(xs, positions, margins):
        rows = np.zeros((-(-len(r) // 128) * 128,), np.int32)
        rows[:len(r)] = r
        o = np.array(head(base, x, jnp.asarray(rows)))[:len(r)]
        if quantize is None:
            o[m[np.asarray(r, np.int64)] < TIE] = np.nan
        out.append(o)
    return out
