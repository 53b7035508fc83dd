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
    token chosen there.  A token outside the vocabulary reads infinity."""
    out = []
    for rows, toks in zip(logits, chosen):
        toks = np.asarray(toks)
        inside = (toks >= 0) & (toks < rows.shape[1])
        safe = np.where(inside, toks, 0)
        g = rows.max(axis=1) - rows[np.arange(len(rows)), safe]
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
    compared = sum(len(s.out) for s in served)
    gap = float("inf")
    if served:
        tokens, positions = reference_inputs(served)
        exact = reference.logits(cfg, seed, tokens, positions)
        chosen = ([s.out for s in served] if quantize is None else
                  [r.argmax(axis=1) for r in reference.logits(
                      cfg, seed, tokens, positions, quantize=quantize)])
        gap = float(gaps(exact, chosen).max())
    return {
        "widest_logit_gap": {"value": gap,
                             "limit": cfg["limits"]["widest_logit_gap"],
                             "at_most": True},
        "compared_tokens": {"value": compared, "limit": MIN_COMPARED,
                            "at_most": False},
    }


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
