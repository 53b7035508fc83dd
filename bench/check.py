"""The comparison that decides ``correct``: served tokens against the
plain reference.

Once the window has closed and the program's state is freed, every
request that was served a token (the in-flight lanes, the window's own
admissions, one on every slot) is run through the reference, once over
its prompt with its served tokens.  At each served position the number
read is how far the served token's reference logit lies below the
reference's best logit there; greedy decoding by a sound program puts
it at 0 except at near-ties, where bfloat16 rounding may pick the
runner-up.  The widest such gap is compared with the configuration's
limit, ``limits.widest_logit_gap``, set in ``PERF.md`` from the
program's readings on the chip over a dozen seeds and the control's.

A reference may leave a position undecided, a row of NaN, where its
own answer turns on rounding (a routed model's near-tie between two
experts): that position is not compared, and ``compared_tokens`` counts
the positions that are.  A configuration whose widest gap cannot part
sound runs from the control (a routed model, where a near-tie of an
earlier row still reaches later rows through attention) also states
``limits.share_over_gap``, ``{"gap": g, "percent": p}``: at most ``p``
percent of the compared positions may read a gap over ``g``.

The control (``judge(..., quantize=fp8_weights)``) puts the reference
with float8 weights in the program's place: at each of the same
positions it chooses the token that its own logits put first.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

# the fewest served tokens a run must compare
MIN_COMPARED = 64


@dataclass
class Served:
    rid: int
    prompt: list[int]
    out: list[int]


def gaps(logits: list[np.ndarray], chosen: list[list[int]]) -> np.ndarray:
    """Per position, the reference's best logit minus its logit of the
    token chosen there.  A token outside the vocabulary reads infinity,
    an undecided position (a row of NaN) NaN."""
    out = []
    for rows, toks in zip(logits, chosen):
        toks = np.asarray(toks)
        inside = (toks >= 0) & (toks < rows.shape[1])
        safe = np.where(inside, toks, 0)
        g = rows.max(axis=1) - rows[np.arange(len(rows)), safe]
        # a NaN in a row the reference did answer is no reading: it fails
        g = np.where(np.isnan(g), np.inf, g)
        g = np.where(np.isnan(rows).all(axis=1), np.nan, g)
        out.append(np.where(inside, g, np.inf))
    return np.concatenate(out) if out else np.zeros(0)


def reference_inputs(served: list[Served]):
    """Each sequence fed to the reference (prompt, then every served
    token but the last) and the positions whose logits chose a served
    token."""
    tokens = [s.prompt + s.out[:-1] for s in served]
    positions = [[len(s.prompt) - 1 + t for t in range(len(s.out))]
                 for s in served]
    return tokens, positions


def judge(reference, cfg: dict, seed: int, served: list[Served],
          quantize=None) -> dict:
    """The numbers compared, each beside its limit.  With ``quantize``
    the reference with rounded weights chooses the tokens (the
    control)."""
    compared, gap, g = 0, float("inf"), np.zeros(0)
    if served:
        tokens, positions = reference_inputs(served)
        exact = reference.logits(cfg, seed, tokens, positions)
        chosen = ([s.out for s in served] if quantize is None else
                  [r.argmax(axis=1) for r in reference.logits(
                      cfg, seed, tokens, positions, quantize=quantize)])
        g = gaps(exact, chosen)
        g = g[~np.isnan(g)]
        compared = len(g)
        if compared:
            gap = float(g.max())
    checks = {
        "widest_logit_gap": {"value": gap,
                             "limit": cfg["limits"]["widest_logit_gap"],
                             "at_most": True},
        "compared_tokens": {"value": compared, "limit": MIN_COMPARED,
                            "at_most": False},
    }
    share = cfg["limits"].get("share_over_gap")
    if share is not None:
        over = float(np.mean(g > share["gap"])) * 100 if compared else 100.0
        checks["share_over_gap"] = {"value": over, "limit": share["percent"],
                                    "at_most": True}
    return checks


def fp8_weights(w):
    """The control's rounding: each weight matrix to float8 e4m3 with
    one scale per output column, the weight-only fp8 that a later change
    would be tempted to serve (one step below the bfloat16 the
    configurations state)."""
    import jax.numpy as jnp

    if w.ndim < 2:
        return w
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def passed(checks: dict) -> bool:
    return all((c["value"] <= c["limit"]) if c["at_most"]
               else (c["value"] >= c["limit"]) for c in checks.values())
