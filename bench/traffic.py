"""The one traffic generator.  A mix is a JSON file of parameters under
``bench/traffic/``; this module turns it and a seed into requests.

Keys of a mix (lengths are inclusive ``[lo, hi]`` token ranges, drawn
uniformly):

- ``arrivals``: ``"backlog"``, an offline batch: every request is due
  at the start, and the harness keeps ``backlog_per_slot`` x slots
  requests waiting;
- ``requests``: how many requests the backlog holds, drawn on in order;
- ``prompt_tokens``: the lengths of the unshared prompts;
- ``output_tokens``: how many tokens each request asks for (greedy);
- ``sizes_seed``: the seed of every size.

The batch has been running for a while when the window opens: the
first ``lanes`` requests (one per engine slot) are in flight.  Lane
``i`` of ``lanes`` has answered ``(i + 1/2) / lanes`` of its request:
those tokens join its prompt (the harness prefills them during set-up)
and its request asks for the rest.  So the window sees contexts spread
over the answer range and lanes finishing and refilled, not every lane
starting its answer in lockstep.

Every run seed serves the same sizes: the run seed only orders them
(the in-flight requests among themselves, the backlog among itself) and
draws the token ids.  Runs on different seeds then do the same work, and
their spread is the system's, not the workload's.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Item:
    rid: int
    prompt: list[int]
    max_new: int
    answered: int = 0     # answer tokens already in the prompt (in flight)


def _sizes(rng: np.random.Generator, span, n: int) -> np.ndarray:
    lo, hi = span
    return rng.integers(lo, hi + 1, size=n)


def longest_context(mix: dict) -> int:
    """The most positions any request of the mix can fill."""
    return mix["prompt_tokens"][1] + mix["output_tokens"][1]


def generate(mix: dict, seed: int, vocab: int, lanes: int) -> list[Item]:
    if mix["arrivals"] != "backlog":
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    n = mix["requests"]
    fixed = np.random.default_rng(mix["sizes_seed"])
    prompts = _sizes(fixed, mix["prompt_tokens"], n)
    outputs = _sizes(fixed, mix["output_tokens"], n)
    answered = [int((i + 0.5) / lanes * outputs[i]) if i < lanes else 0
                for i in range(n)]
    run = np.random.default_rng(seed)
    order = np.concatenate([run.permutation(lanes),
                            lanes + run.permutation(n - lanes)])
    items = []
    for rid, i in enumerate(order):
        own = int(prompts[i]) + answered[i]
        items.append(Item(rid, run.integers(0, vocab, own).tolist(),
                          int(outputs[i]) - answered[i], answered[i]))
    return items
