"""The system under test: the program's model, its weights checked
against the program's parameter tree and placed on the device, and the
paged serving engine built as ``repro.launch.serve.run_serve`` builds it
(paged kernel, prefix cache on, swap tier on; greedy requests).  What a
configuration maps onto the program (its ``ModelConfig`` and the layout
of its weights) is its family's (``bench/families/<family>.py``).

This is the one module of the benchmark that imports the program, apart
from the lazy imports inside family modules.
"""
from __future__ import annotations

import jax

from bench import weights


def build_model(model_cfg):
    """The program's model for the ``ModelConfig`` that a configuration's
    family builds (``bench/families/<family>.py``)."""
    from repro.models import build

    return build(model_cfg)


def abstract_params(model) -> dict:
    """Shapes and types of the program's parameter tree."""
    return jax.eval_shape(model.init_params, jax.random.key(0))


def lay_out(want: dict, make, seed: int):
    """The seed's weights, made on the device in one jitted call of a
    family's ``make(key)`` (the seed's key is an argument, so every seed
    runs the same compiled program), once its tree is shown to be the
    program's ``want`` leaf for leaf."""
    make = jax.jit(make)
    got = jax.eval_shape(make, weights.base_key(seed))

    def layout(tree):
        return jax.tree.structure(tree), [(a.shape, a.dtype)
                                          for a in jax.tree.leaves(tree)]

    if layout(got) != layout(want):
        raise ValueError(f"the program's parameter tree changed: it wants "
                         f"{want}, the benchmark lays out {got}")
    return make(weights.base_key(seed))


def make_params(model, family, reference, cfg: dict, seed: int):
    """The seed's weights in the program's tree, drawn by the family's
    ``draw`` and checked by :func:`lay_out`."""
    want = abstract_params(model)
    return lay_out(want, family.draw(reference, cfg, want), seed)


def build_engine(model, params, serving: dict):
    from repro.serve import PagedServeEngine

    return PagedServeEngine(
        model, params, slots=serving["slots"], max_len=serving["max_len"],
        block_size=serving["block_size"], chunk=serving["chunk"],
        num_blocks=serving["pool_pages"], use_prefix_cache=True,
        kernel="paged", swap=True)


def request(rid: int, prompt: list[int], max_new: int):
    from repro.serve import Request

    return Request(rid=rid, prompt=prompt, max_new=max_new)
