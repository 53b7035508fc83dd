"""The seeded weights and the plain references: the draw of one layer
is the stacked draw's layer bit for bit, the program gets the weights in
its own tree through the configuration's family, and each reference (the
benchmark's ``phi3`` and the tests' MoE) computes what the program's own
float32 forward computes over those weights."""
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, program, spec, weights
from bench.families import dense
from bench.references import phi3
from bench.tests import tiny

SEED = 2**31 + 99


def test_one_layer_draw_matches_the_stack():
    lt = phi3.layer_table(tiny.CONFIG)
    key = weights.base_key(SEED)
    stack = jax.jit(lambda k: weights.layers(k, lt, jnp.arange(2)))(key)
    one = jax.jit(lambda k, i: weights.layers(k, lt, i[None]))(
        key, jnp.int32(1))
    for name in lt:
        assert np.array_equal(np.asarray(stack[name][1]),
                              np.asarray(one[name][0])), name


def test_seeds_past_32_bits_differ():
    a = np.asarray(jax.random.key_data(weights.base_key(5)))
    b = np.asarray(jax.random.key_data(weights.base_key(2**32 + 5)))
    assert not np.array_equal(a, b)
    with pytest.raises(ValueError):
        weights.base_key(-1)


def test_draw_is_scaled_and_exact_in_bf16():
    w = weights.draw(jax.random.key(0), (256, 512), 3072)
    assert w.dtype == jnp.bfloat16
    std = float(jnp.std(w.astype(jnp.float32)))
    assert 0.5 * 3072 ** -0.5 < std < 2 * 3072 ** -0.5


def _family_and_reference(name):
    if name == "tiny":
        return dense, phi3, tiny.CONFIG
    return (spec.load_module(tiny.TESTS / "moe_family.py"),
            spec.load_module(tiny.TESTS / "moe_reference.py"),
            tiny.MOE_CONFIG)


@pytest.mark.parametrize("name", ["tiny", "tiny-moe"])
def test_reference_matches_the_programs_float32_forward(name):
    family, ref, cfg = _family_and_reference(name)
    model = program.build_model(family.model_config(cfg))
    params = program.make_params(model, family, ref, cfg, SEED)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg["vocab_size"], size=(2, 40)).astype(np.int32)
    pos = np.array([[5, 39], [0, 20]], np.int32)
    want = np.asarray(model.reference_prefill(params, jnp.asarray(toks),
                                              jnp.asarray(pos)))
    got = ref.logits(cfg, SEED, toks.tolist(), pos.tolist())
    for b in range(2):
        # the program's padded vocabulary columns are zeros
        assert np.all(want[b, :, cfg["vocab_size"]:] == 0)
        np.testing.assert_allclose(got[b], want[b, :, :cfg["vocab_size"]],
                                   rtol=2e-4, atol=2e-4)


def test_gaps_read_undecided_positions_apart_and_fail_broken_rows():
    rows = np.array([[0.0, 1.0, 0.5],
                     [np.nan, np.nan, np.nan],      # undecided
                     [0.2, np.nan, 0.1]], np.float32)  # no reading
    g = check.gaps([rows], [[2, 0, 0]])
    assert g[0] == pytest.approx(0.5)
    assert np.isnan(g[1]) and g[2] == np.inf
    # a token outside the vocabulary, undecided or not
    assert list(check.gaps([rows[:2]], [[3, 3]])) == [np.inf, np.inf]


def test_judge_compares_the_decided_positions_alone():
    def logits(cfg, seed, tokens, positions, quantize=None):
        out = []
        for r in positions:
            o = np.zeros((len(r), 4), np.float32)
            o[:, 1] = 1.0
            if quantize is None:
                o[0] = np.nan
            out.append(o)
        return out

    cfg = {"limits": {"widest_logit_gap": 0.5}}
    served = [check.Served(0, [1, 2], [1, 1, 3])]
    c = check.judge(NS(logits=logits), cfg, 0, served)
    assert c["compared_tokens"]["value"] == 2
    assert c["widest_logit_gap"]["value"] == pytest.approx(1.0)
    assert not check.passed(c)
    # the control is judged at the same positions
    ctl = check.judge(NS(logits=logits), cfg, 0, served, quantize=lambda w: w)
    assert ctl["compared_tokens"]["value"] == 2
    assert ctl["widest_logit_gap"]["value"] == 0.0
