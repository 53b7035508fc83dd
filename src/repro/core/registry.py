"""Architecture/config registry: ``--arch <id>`` resolution."""
from __future__ import annotations

from repro.configs import ALL_ARCHS, SHAPES, applicable_shapes
from repro.configs.archs import SERVING_ARCHS
from repro.configs.base import ModelConfig, RunConfig, ShapeConfig, reduced


def resolve_arch(name: str) -> ModelConfig:
    """An assigned architecture (``ALL_ARCHS``) or one served through the
    paged engine alone (``SERVING_ARCHS``)."""
    known = {**ALL_ARCHS, **SERVING_ARCHS}
    if name in known:
        return known[name]
    # tolerate module-style ids (dots/dashes vs underscores)
    norm = name.replace("_", "-")
    for k in known:
        if k.replace(".", "-") == norm or k == norm:
            return known[k]
    raise KeyError(f"unknown arch {name!r}; known: {sorted(known)}")


def resolve_shape(name: str) -> ShapeConfig:
    if name not in SHAPES:
        raise KeyError(f"unknown shape {name!r}; known: {sorted(SHAPES)}")
    return SHAPES[name]


def all_cells() -> list[tuple[str, str]]:
    """Every assignment cell (arch, shape), skips included as per DESIGN §6."""
    cells = []
    for arch in ALL_ARCHS:
        for shape in applicable_shapes(arch):
            cells.append((arch, shape))
    return cells


def smoke_config(name: str) -> ModelConfig:
    return reduced(resolve_arch(name))
