"""Seeded weights, made by the benchmark and handed to the system under
test, and made again layer by layer for the plain reference.

Every value is exact in bfloat16 and independent of how XLA fuses the
program that draws it: 16 random bits become an integer on
``[-2^15, 2^15)``, its conversion to bfloat16 rounds once to nearest
even, and the scale is a power of two.  So the stacked draw of all
layers (one jitted call on the device) and the reference's draw of one
layer give the same bits, on any backend.

The distribution is uniform with the standard deviation of the usual
``fan_in ** -0.5`` initialisation, to the nearest power of two.  Norm
weights are ones.  Keys: ``fold_in(base, 0)`` for the embedding, head
and final norm, ``fold_in(base, 1 + layer)`` for a layer, then one
``fold_in`` per tensor in the order of its shape table.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

UNIFORM_STD = 2.0 ** 15 / math.sqrt(3.0)   # std of the integers drawn


def base_key(seed: int) -> jax.Array:
    """A key from any whole seed: ``jax.random.key`` keeps only 32 bits
    of a Python int, so the high word is folded in."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def scale_exponent(fan_in: int) -> int:
    """``k`` such that the draw times ``2**-k`` has about the standard
    deviation ``fan_in ** -0.5``."""
    return round(math.log2(UNIFORM_STD * math.sqrt(fan_in)))


def draw(key: jax.Array, shape: tuple[int, ...], fan_in: int) -> jax.Array:
    bits = jax.random.bits(key, shape, jnp.uint32)
    ints = (bits >> 16).astype(jnp.int32) - 2 ** 15
    return ints.astype(jnp.bfloat16) * jnp.bfloat16(2.0 ** -scale_exponent(fan_in))


def make(key: jax.Array, table: dict[str, tuple[tuple[int, ...], int | None]]
         ) -> dict[str, jax.Array]:
    """Tensors of one shape table ``name -> (shape, fan_in)``; a fan-in
    of ``None`` marks a norm weight (ones)."""
    out = {}
    for j, (name, (shape, fan_in)) in enumerate(table.items()):
        out[name] = (jnp.ones(shape, jnp.bfloat16) if fan_in is None
                     else draw(jax.random.fold_in(key, j), shape, fan_in))
    return out


def layers(base: jax.Array, table: dict, index: np.ndarray | jax.Array
           ) -> dict:
    """The layers ``index`` of the model keyed by ``base`` (see
    :func:`base_key`), stacked on a leading axis.  Traceable: call under
    ``jit`` with the key as an argument, so one program serves every
    seed."""
    return jax.vmap(lambda i: make(jax.random.fold_in(base, 1 + i), table))(
        jnp.asarray(index, jnp.int32))


def outer(base: jax.Array, table: dict) -> dict:
    """Embedding, head and final norm (traceable, as :func:`layers`)."""
    return make(jax.random.fold_in(base, 0), table)
