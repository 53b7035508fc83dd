"""The traffic generator: deterministic by seed, lengths inside their
ranges, the in-flight lanes part-way through their answers, and every
seed the same sizes in another order."""
import json
from pathlib import Path

import pytest

from bench import traffic

MIXES = Path(__file__).resolve().parents[1] / "traffic"
VOCAB = 32064
LANES = 8
SEEDS = [0, 2**31 + 5, 2**40 + 3]


def mix(name="decode-batch"):
    return json.loads((MIXES / f"{name}.json").read_text())


def test_deterministic_by_seed():
    a = traffic.generate(mix(), 11, VOCAB, LANES)
    b = traffic.generate(mix(), 11, VOCAB, LANES)
    c = traffic.generate(mix(), 12, VOCAB, LANES)
    assert [(x.prompt, x.max_new) for x in a] == \
        [(x.prompt, x.max_new) for x in b]
    assert [x.prompt for x in a] != [x.prompt for x in c]


def test_backlog_lengths():
    m = mix()
    lo, hi = m["output_tokens"]
    for seed in SEEDS:
        items = traffic.generate(m, seed, VOCAB, LANES)
        assert len(items) == m["requests"]
        assert [x.rid for x in items] == list(range(len(items)))
        for x in items:
            own = len(x.prompt) - x.answered
            assert m["prompt_tokens"][0] <= own <= m["prompt_tokens"][1]
            assert lo <= x.max_new + x.answered <= hi
            assert x.max_new >= 1
            assert len(x.prompt) + x.max_new <= traffic.longest_context(m)
        assert all(0 <= t < VOCAB for x in items for t in x.prompt)


def test_in_flight_lanes_are_staggered_through_their_answers():
    items = traffic.generate(mix(), 3, VOCAB, LANES)
    head, rest = items[:LANES], items[LANES:]
    assert all(x.answered == 0 for x in rest)
    shares = sorted(x.answered / (x.answered + x.max_new) for x in head)
    for i, share in enumerate(shares):
        assert share == pytest.approx((i + 0.5) / LANES, abs=1 / 256)


def test_every_seed_serves_the_same_sizes():
    runs = [traffic.generate(mix(), s, VOCAB, LANES) for s in SEEDS]
    for part in (slice(0, LANES), slice(LANES, None)):
        for size in (lambda x: x.max_new, lambda x: len(x.prompt),
                     lambda x: x.answered):
            lists = [sorted(map(size, items[part])) for items in runs]
            assert lists[0] == lists[1] == lists[2]
    orders = [[x.max_new for x in items] for items in runs]
    assert orders[0] != orders[1]


def test_only_a_backlog_is_known():
    with pytest.raises(ValueError):
        traffic.generate(dict(mix(), arrivals="poisson"), 0, VOCAB, LANES)
