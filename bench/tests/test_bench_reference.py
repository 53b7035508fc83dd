"""The seeded weights and the plain reference: the draw of one layer is
the stacked draw's layer bit for bit, the program gets the weights in
its own tree, and the reference computes what the program's own float32
forward computes over those weights."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import program, weights
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


def test_reference_matches_the_programs_float32_forward():
    cfg = tiny.CONFIG
    model = program.build_model(cfg)
    params = program.make_params(model, phi3, cfg, SEED)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg["vocab_size"], size=(2, 40)).astype(np.int32)
    pos = np.array([[5, 39], [0, 20]], np.int32)
    want = np.asarray(model.reference_prefill(params, jnp.asarray(toks),
                                              jnp.asarray(pos)))
    got = phi3.logits(cfg, SEED, toks.tolist(), pos.tolist())
    for b in range(2):
        # the program's padded vocabulary columns are zeros
        assert np.all(want[b, :, cfg["vocab_size"]:] == 0)
        np.testing.assert_allclose(got[b], want[b, :, :cfg["vocab_size"]],
                                   rtol=2e-4, atol=2e-4)
