"""Sharding context: the late "host binding" for model code.

Model code never names mesh axes directly; it annotates activations with
*logical* axis names (``constrain(x, ("act_batch", "act_seq", None))``).
The binding from logical names to physical mesh axes is installed by the
step factory for the duration of tracing — the same model code lowers
against any mesh, which is exactly the paper's portable-image property.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()


def _current():
    return getattr(_state, "ctx", None)


class ShardCtx:
    def __init__(self, mesh: Mesh, rules: dict[str, tuple[str, ...] | str | None]):
        self.mesh = mesh
        self.rules = rules
        self.axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def resolve(self, logical: Sequence[str | None], shape: Sequence[int] | None = None) -> P:
        """Map logical axis names to a PartitionSpec, dropping any mapping
        that would not divide the corresponding dimension evenly and
        de-duplicating mesh axes (first use wins)."""
        used: set[str] = set()
        parts = []
        for i, name in enumerate(logical):
            spec = self.rules.get(name) if name else None
            if spec is None:
                parts.append(None)
                continue
            axes = (spec,) if isinstance(spec, str) else tuple(spec)
            axes = tuple(a for a in axes if a in self.axis_sizes and a not in used)
            if not axes:
                parts.append(None)
                continue
            size = 1
            for a in axes:
                size *= self.axis_sizes[a]
            if shape is not None and shape[i] % size != 0:
                # Uneven — replicate rather than let GSPMD pad implicitly.
                parts.append(None)
                continue
            used.update(axes)
            parts.append(axes[0] if len(axes) == 1 else axes)
        return P(*parts)


@contextlib.contextmanager
def bind(mesh: Mesh, rules: dict):
    prev = _current()
    _state.ctx = ShardCtx(mesh, rules)
    try:
        yield _state.ctx
    finally:
        _state.ctx = prev


def mesh_of(shape, axes):
    """A mesh over the first ``prod(shape)`` devices with every axis
    Auto-sharded (GSPMD propagates through the logical constraints)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def constrain(x: jax.Array, logical: Sequence[str | None]) -> jax.Array:
    """Annotate ``x`` with the sharding its logical axes resolve to.
    No-op when no context is bound (single-device smoke tests)."""
    ctx = _current()
    if ctx is None:
        return x
    spec = ctx.resolve(logical, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, spec))


def resolve(logical: Sequence[str | None], shape: Sequence[int] | None = None) -> P:
    ctx = _current()
    if ctx is None:
        return P(*([None] * len(logical)))
    return ctx.resolve(logical, shape)


def sharding_for(logical: Sequence[str | None], shape: Sequence[int] | None = None):
    ctx = _current()
    if ctx is None:
        return None
    return NamedSharding(ctx.mesh, ctx.resolve(logical, shape))
