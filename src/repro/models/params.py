"""Parameter specification trees.

Every model declares its parameters once as a pytree of ``ParamSpec`` —
shape, dtype, logical axes, initializer.  From that single declaration we
derive:

  * ``abstract(specs)``   → ShapeDtypeStruct tree (dry-run: no allocation)
  * ``initialize(specs)`` → materialized arrays (smoke tests / real runs)
  * ``partition(specs)``  → PartitionSpec tree via the bound rule set
  * ``count(specs)``      → analytic parameter count

This is the "version-pinned package list" of the environment manifest: the
model's state is fully described independently of any host binding.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.parallel import ctx as shardctx


@dataclass(frozen=True)
class ParamSpec:
    shape: tuple[int, ...]
    axes: tuple[Any, ...]               # logical axis name or None per dim
    dtype: Any = jnp.bfloat16
    init: str = "normal"                # normal | zeros | ones | embed
    fan_in_axes: tuple[int, ...] = ()   # dims treated as fan-in for scaling

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_spec(x: Any) -> bool:
    return isinstance(x, ParamSpec)


def tree_map(fn: Callable[[ParamSpec], Any], specs: Any) -> Any:
    return jax.tree.map(fn, specs, is_leaf=is_spec)


def abstract(specs: Any) -> Any:
    return tree_map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), specs)


def partition(specs: Any) -> Any:
    """PartitionSpec tree under the currently bound shard context."""
    return tree_map(lambda s: shardctx.resolve(s.axes, s.shape), specs)


def shardings(specs: Any, mesh) -> Any:
    from jax.sharding import NamedSharding

    return tree_map(
        lambda s: NamedSharding(mesh, shardctx.resolve(s.axes, s.shape)), specs
    )


def count(specs: Any) -> int:
    total = 0
    for leaf in jax.tree.leaves(specs, is_leaf=is_spec):
        n = 1
        for d in leaf.shape:
            n *= d
        total += n
    return total


@functools.partial(jax.jit, static_argnums=0)
def _init_one(spec: ParamSpec, key: jax.Array) -> jax.Array:
    """One leaf, generated under ``jit``: the float32 draw and its scaled
    copy live only inside the program (fused into the bf16 output), so a
    published-width leaf such as a stacked ``[32, 3072, 8192]`` MLP weight
    holds one float32 draw and no second scaled float32 copy.  Same
    values as the eager draw at the same key."""
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    if spec.init == "embed":
        scale = spec.shape[-1] ** -0.5  # keeps tied-head logits O(1)
    else:
        fan_axes = spec.fan_in_axes or tuple(
            i for i in range(len(spec.shape) - 1)
            if spec.axes[i] not in ("layers", "groups")
        )
        fan_in = max(int(np.prod([spec.shape[i] for i in fan_axes])), 1)
        scale = fan_in ** -0.5
    # the barrier keeps XLA from folding ``scale`` into the draw's own
    # constants, which would move a few elements across a bf16 rounding
    # boundary: the scale-and-cast then fuses exactly as the eager ops do
    draw = jax.lax.optimization_barrier(
        jax.random.normal(key, spec.shape, jnp.float32))
    return (draw * scale).astype(spec.dtype)


def initialize(specs: Any, key: jax.Array) -> Any:
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_spec)
    keys = jax.random.split(key, max(len(leaves), 1))
    return jax.tree.unflatten(treedef, [_init_one(s, k) for s, k in zip(leaves, keys)])


# ---- spec constructors --------------------------------------------------

def dense(d_in: int, d_out: int, in_axis: str | None, out_axis: str | None,
          layers: int | None = None, dtype=jnp.bfloat16) -> ParamSpec:
    """[L?, d_in, d_out] projection."""
    shape: tuple[int, ...] = (d_in, d_out)
    axes: tuple[Any, ...] = (in_axis, out_axis)
    if layers is not None:
        shape = (layers,) + shape
        axes = ("layers",) + axes
    return ParamSpec(shape, axes, dtype)


def scale(d: int, layers: int | None = None, init: str = "ones") -> ParamSpec:
    shape: tuple[int, ...] = (d,)
    axes: tuple[Any, ...] = (None,)
    if layers is not None:
        shape = (layers,) + shape
        axes = ("layers",) + axes
    return ParamSpec(shape, axes, jnp.bfloat16, init=init)


def vec(shape: tuple[int, ...], axes: tuple[Any, ...], init: str = "zeros",
        dtype=jnp.float32) -> ParamSpec:
    return ParamSpec(shape, axes, dtype, init=init)
