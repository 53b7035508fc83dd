"""Plain reference of the afmoe decoder (Arcee's Trinity models; the
``AfmoeForCausalLM`` of their published modeling code, as recalled: the
installed transformers has no such module, and the configuration file
lists each recalled equation under ``assumed``), in float32 at the
highest matmul precision, with no cache, kernel, batching or expert
capacity.

The embedding is scaled by ``sqrt(hidden_size)`` (``mup_enabled``).
Every layer, with a norm on each side of each branch (four RMSNorms):

    x += post_attn_norm(attn(input_norm(x)))
    x += post_mlp_norm(mlp(pre_mlp_norm(x)))

Attention: q, k, v projections; per-head RMSNorm on q and on k; rotary
position embedding (``rotate_half``, theta from the configuration) on
the ``sliding_attention`` layers only, none on ``full_attention`` ones;
grouped key/value heads (query head ``j`` reads key/value head
``j // group``) at scale ``head_dim ** -0.5``; a sliding layer's query at
``q`` sees keys ``q - sliding_window < k <= q``, a full one ``k <= q``;
the output times ``sigmoid(x @ w_gate_attn)`` (x the branch's normed
input), then ``o_proj``.  Computed in blocks of queries, so that a
sequence of some thousands of positions fits.

The first ``num_dense_layers`` layers' mlp is a gated MLP of
``intermediate_size``.  Every later layer's is the expert layer: scores
``s = sigmoid(x @ router)`` over every routed expert (the published
``num_experts``), the ``num_experts_per_tok`` chosen by ``s +
expert_bias`` (selection only), each chosen expert weighted ``s_e / sum
of the chosen s * route_scale``; output the shared expert's plus the
weighted sum of the chosen experts', each ``down(silu(gate(x)) *
up(x))`` of ``moe_intermediate_size``.

The chip's share (``expert_parallel`` in the configuration): of the
routed experts only those from ``expert_offset`` on, ``num_experts`` of
them, are held, and the layer adds only their part (with the shared
expert), exactly as the program does; the router keeps every output.
Each expert's weights are drawn from its own key, by its global id, so
the experts one share holds are those of the uncut layer.

It takes nothing from the system under test: the weights are drawn
again from the seed's key (``bench.weights``), one layer at a time.
``quantize`` rounds every weight matrix first, each expert's on its own
(the control).  A position whose own row's route turns on rounding in
some layer (:data:`TIE`) is left undecided, a row of NaN.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

HIGHEST = jax.lax.Precision.HIGHEST
#: A row whose ``num_experts_per_tok``-th selection score (score plus
#: bias) lies less than this above the next, where one of the two is a
#: held expert, is routed by rounding in some layer: its position is left
#: undecided.  On the chip (trinity-mini.decode-long, 12 seeds) the
#: served positions whose gap passed 0.1 read margins down from 0 to
#: 0.0003 and typical ones 0.002 (the smallest of 14 layers); 0.0005
#: leaves 15-17% of positions undecided, 0.002 about 48% (PERF.md).
TIE = 0.0005
#: The selection bias's draw: ``bench.weights.draw`` at this fan-in, a
#: standard deviation of 1/64, about the gaps between neighbouring
#: sigmoid scores at the top of 128, so that selection differs from the
#: scores' own order.
BIAS_FAN_IN = 4096
#: Queries per attention block.
BLOCK = 512
#: Sequences are padded to a multiple of this, so that few lengths
#: compile.
PAD = 1024


def dims(cfg: dict) -> dict:
    ep = cfg["expert_parallel"]
    return dict(d=cfg["hidden_size"], h=cfg["num_attention_heads"],
                kv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
                f=cfg["intermediate_size"], fe=cfg["moe_intermediate_size"],
                v=cfg["vocab_size"], layers=cfg["num_hidden_layers"],
                dense=cfg["num_dense_layers"], routed=ep["experts_routed"],
                held=cfg["num_experts"], offset=ep["expert_offset"],
                k=cfg["num_experts_per_tok"],
                shared=cfg["num_shared_experts"])


def windows(cfg: dict) -> list[int]:
    """Each held layer's window; 0 for a full layer."""
    types = cfg["layer_types"][:cfg["num_hidden_layers"]]
    return [cfg["sliding_window"] if t == "sliding_attention" else 0
            for t in types]


def layer_table(cfg: dict, dense: bool) -> dict:
    """Name -> (shape, fan_in) of one layer's tensors other than the
    routed experts; fan_in None: a norm."""
    n = dims(cfg)
    d, q, kv = n["d"], n["h"] * n["hd"], n["kv"] * n["hd"]
    table = {
        "attn_norm": ((d,), None),
        "wq": ((d, q), d), "wk": ((d, kv), d), "wv": ((d, kv), d),
        "q_norm": ((n["hd"],), None), "k_norm": ((n["hd"],), None),
        "w_gate_attn": ((d, q), d),
        "wo": ((q, d), q),
        "attn_post_norm": ((d,), None),
        "mlp_norm": ((d,), None),
        "mlp_post_norm": ((d,), None),
    }
    if dense:
        f = n["f"]
        table.update(w_gate=((d, f), d), w_up=((d, f), d),
                     w_down=((f, d), f))
    else:
        fs = n["fe"] * n["shared"]
        table.update(router=((d, n["routed"]), d),
                     expert_bias=((n["routed"],), BIAS_FAN_IN),
                     shared_gate=((d, fs), d), shared_up=((d, fs), d),
                     shared_down=((fs, d), fs))
    return table


def expert_table(cfg: dict) -> dict:
    """Name -> (shape, fan_in) of one routed expert."""
    d, fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return {"expert_gate": ((d, fe), d), "expert_up": ((d, fe), d),
            "expert_down": ((fe, d), fe)}


def outer_table(cfg: dict) -> dict:
    n = dims(cfg)
    return {"embed": ((n["v"], n["d"]), n["d"]),
            "head": ((n["d"], n["v"]), n["d"]),
            "final_norm": ((n["d"],), None)}


def draw_layer(cfg: dict, base: jax.Array, i) -> dict:
    """Layer ``i`` (traced or not) of the model keyed by ``base``: its
    table's tensors as ``bench.weights.make`` draws them from
    ``fold_in(base, 1 + i)``, and for an expert layer the held experts,
    expert ``e`` of tensor ``j`` (after the table's) from
    ``fold_in(fold_in(key, j), e)``, stacked ``[held, ...]``."""
    n = dims(cfg)
    key = jax.random.fold_in(base, 1 + jnp.asarray(i, jnp.int32))
    dense = isinstance(i, int) and i < n["dense"]
    table = layer_table(cfg, dense)
    out = weights.make(key, table)
    if dense:
        return out
    ids = n["offset"] + jnp.arange(n["held"])
    for j, (name, (shape, fan)) in enumerate(expert_table(cfg).items(),
                                             start=len(table)):
        kj = jax.random.fold_in(key, j)
        out[name] = jax.vmap(lambda e: weights.draw(
            jax.random.fold_in(kj, e), shape, fan))(ids)
    return out


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


def _attention(cfg: dict, p: dict, a: jax.Array, window, rope
               ) -> jax.Array:
    """The attention branch over one sequence's normed rows a [S, d];
    ``window`` (0: none) and ``rope`` traced, so every layer of a length
    runs one program."""
    n = dims(cfg)
    s, h, kv, hd = a.shape[0], n["h"], n["kv"], n["hd"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    pos = jnp.arange(s)
    q = _rmsnorm(_mm(a, p["wq"]).reshape(s, h, hd), p["q_norm"], eps)
    k = _rmsnorm(_mm(a, p["wk"]).reshape(s, kv, hd), p["k_norm"], eps)
    v = _mm(a, p["wv"]).reshape(s, kv, hd)
    q = jnp.where(rope, _rope(q, pos, theta), q)
    k = jnp.where(rope, _rope(k, pos, theta), k)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    reach = jnp.where(window > 0, window, s)

    def block(qp):
        qb, pb = qp                                  # [B, h, hd], [B]
        scores = jnp.einsum("qhd,khd->hqk", qb, k,
                            precision=HIGHEST) * hd ** -0.5
        seen = ((pos[None, :] <= pb[:, None])
                & (pos[None, :] > pb[:, None] - reach))
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1),
                          v, precision=HIGHEST)

    nb = s // BLOCK if s % BLOCK == 0 else 1
    o = jax.lax.map(block, (q.reshape(nb, s // nb, h, hd),
                            pos.reshape(nb, s // nb))).reshape(s, h * hd)
    o = o * jax.nn.sigmoid(_mm(a, p["w_gate_attn"]))
    return _mm(o, p["wo"])


def _experts(cfg: dict, p: dict, m: jax.Array
             ) -> tuple[jax.Array, jax.Array]:
    """The expert layer over rows m [S, d] at this chip's share, and
    each row's margin: how far its ``k``-th selection score lies above
    the next where either is held (infinity where neither is)."""
    n = dims(cfg)
    k = n["k"]
    scores = jax.nn.sigmoid(_mm(m, p["router"]))
    sel = scores + p["expert_bias"]
    ranked, ranked_i = jax.lax.top_k(sel, k + 1)
    top_i = ranked_i[:, :k]
    top_s = jnp.take_along_axis(scores, top_i, axis=1)
    w = top_s / jnp.sum(top_s, axis=-1, keepdims=True) * cfg["route_scale"]
    held = jnp.arange(n["held"]) + n["offset"]
    gates = jnp.sum(jnp.where(top_i[:, :, None] == held, w[:, :, None], 0.0),
                    axis=1)                                   # [S, held]
    h = (jax.nn.silu(jnp.einsum("sd,edf->esf", m, p["expert_gate"],
                                precision=HIGHEST))
         * jnp.einsum("sd,edf->esf", m, p["expert_up"], precision=HIGHEST))
    y = jnp.einsum("esf,efd->esd", h, p["expert_down"], precision=HIGHEST)
    y = jnp.einsum("se,esd->sd", gates, y, precision=HIGHEST)
    shared = _mm(jax.nn.silu(_mm(m, p["shared_gate"]))
                 * _mm(m, p["shared_up"]), p["shared_down"])
    edge = ranked_i[:, k - 1:k + 1]
    touches = jnp.any((edge >= n["offset"])
                      & (edge < n["offset"] + n["held"]), axis=1)
    margin = jnp.where(touches, ranked[:, k - 1] - ranked[:, k], jnp.inf)
    return shared + y, margin


def _layer(cfg: dict, p: dict, x: jax.Array, window, rope, dense: bool
           ) -> tuple[jax.Array, jax.Array]:
    """One decoder layer over one sequence x [S, d] (float32), and each
    row's routing margin (infinity in a dense layer)."""
    eps = cfg["rms_norm_eps"]
    a = _attention(cfg, p, _rmsnorm(x, p["attn_norm"], eps), window, rope)
    x = x + _rmsnorm(a, p["attn_post_norm"], eps)
    m = _rmsnorm(x, p["mlp_norm"], eps)
    if dense:
        y = _mm(jax.nn.silu(_mm(m, p["w_gate"])) * _mm(m, p["w_up"]),
                p["w_down"])
        margin = jnp.full(x.shape[:1], jnp.inf)
    else:
        y, margin = _experts(cfg, p, m)
    return x + _rmsnorm(y, p["mlp_post_norm"], eps), margin


def forward(cfg: dict, seed: int, tokens: list[list[int]],
            positions: list[list[int]],
            quantize: Callable[[jax.Array], jax.Array] | None = None,
            layer_fn=_layer) -> tuple[list[np.ndarray], list[np.ndarray]]:
    """float32 logits [len(positions[b]), vocab] of each sequence
    ``tokens[b]`` at ``positions[b]`` (causal: the row at position ``t``
    predicts token ``t + 1``), and each such row's smallest routing
    margin over the layers.  Sequences run one at a time, each padded at
    the end to a multiple of :data:`PAD` (padding after a position
    cannot change it).  ``layer_fn`` stands in for :func:`_layer` (the
    tests' broken references)."""
    n = dims(cfg)
    base = weights.base_key(seed)
    ot = outer_table(cfg)
    q = quantize or (lambda w: w)
    wins = windows(cfg)

    def f32(tree):
        return {k: (jax.vmap(q) if w.ndim == 3 else q)(w.astype(jnp.float32))
                for k, w in tree.items()}

    gen_dense = jax.jit(lambda key, i: draw_layer(cfg, key, int(i)),
                        static_argnums=1)
    gen_moe = jax.jit(lambda key, i: draw_layer(cfg, key, i))
    run = {dense: jax.jit(lambda p, x, w, r, dense=dense: layer_fn(
        cfg, f32(p), x, w, r, dense)) for dense in (True, False)}

    @jax.jit
    def embed(key, toks):
        e = f32(weights.outer(key, ot))["embed"][toks]
        return e * jnp.float32(n["d"]) ** 0.5

    @jax.jit
    def head(key, x, rows):
        o = f32(weights.outer(key, ot))
        return _mm(_rmsnorm(x[rows], o["final_norm"], cfg["rms_norm_eps"]),
                   o["head"])

    xs, margins = [], []
    for t in tokens:
        padded = np.zeros((-(-len(t) // PAD) * PAD,), np.int32)
        padded[:len(t)] = t
        xs.append(embed(base, jnp.asarray(padded)))
        margins.append(np.full(padded.shape, np.inf, np.float32))
    for i in range(n["layers"]):
        dense = i < n["dense"]
        p = gen_dense(base, i) if dense else gen_moe(base, jnp.int32(i))
        w, r = jnp.int32(wins[i]), jnp.bool_(wins[i] > 0)
        for b, x in enumerate(xs):
            xs[b], m = run[dense](p, x, w, r)
            margins[b] = np.minimum(margins[b], np.asarray(m))
        del p
    out, kept = [], []
    for x, r, m in zip(xs, positions, margins):
        # rows padded to a multiple of 128, so that few shapes compile
        rows = np.zeros((-(-len(r) // 128) * 128,), np.int32)
        rows[:len(r)] = r
        out.append(np.array(head(base, x, jnp.asarray(rows)))[:len(r)])
        kept.append(m[np.asarray(r, np.int64)])
    return out, kept


def logits(cfg: dict, seed: int, tokens: list[list[int]],
           positions: list[list[int]],
           quantize: Callable[[jax.Array], jax.Array] | None = None
           ) -> list[np.ndarray]:
    """:func:`forward`'s logits; without ``quantize``, a position whose
    own row's margin is under :data:`TIE` in some layer is a row of NaN,
    which ``bench.check`` leaves out.  A route of an earlier row that
    rounding flipped still reaches later rows through attention; that is
    not marked."""
    out, margins = forward(cfg, seed, tokens, positions, quantize)
    if quantize is None:
        for o, m in zip(out, margins):
            o[m < TIE] = np.nan
    return out
