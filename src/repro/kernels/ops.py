"""Jitted public wrappers for the Pallas kernels.

On a TPU backend the kernels compile natively.  Interpret mode (the
kernel body run in Python per grid step, on any backend) is a test-only
choice: the test suite sets ``FORCE_INTERPRET`` to exercise kernel bodies
on the CPU.  Production code never falls into it; a kernel called off the
TPU without it fails to lower.  Model code calls these via
``use_pallas=True``; the default model path uses the jnp oracles.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.hh_neuron import hh_step_pallas
from repro.kernels.paged_attention import (kernel_blocks,
                                           paged_attention_pallas,
                                           paged_attention_ref,
                                           pool_head_dim, walk_blocks)
from repro.kernels.ssd_scan import ssd_scan_pallas

#: Run Pallas kernels in interpret mode (test-only: tests/conftest.py sets
#: it off-accelerator so tier-1 exercises the kernel bodies on CPU CI).
FORCE_INTERPRET = False

#: Select the Pallas paged-attention kernel for the serving step even
#: off the TPU (test-only: it then needs ``FORCE_INTERPRET``).  Tests use
#: this to drive the kernel through the full engine on CPU.
FORCE_PAGED_KERNEL = False

#: The two implementations of the paged step's attention.
PAGED_ATTENTION_IMPLS = ("pallas", "reference")


def _interpret() -> bool:
    return FORCE_INTERPRET


def paged_attention_impl() -> str:
    """Which attention the paged serving step lowers: the Pallas kernel
    on a TPU (or when a test forces it), the pure-JAX page-table
    reference elsewhere.  The engine reads this once at construction,
    passes it to its jitted step as a static argument and reports it, so
    the choice is visible, never a silent trace-time branch."""
    if FORCE_PAGED_KERNEL or jax.default_backend() == "tpu":
        return "pallas"
    return "reference"


def hh_step(v0, m, h, n, g_syn, i_axial, dt, i_ext):
    """Signature-compatible with neuro.cable.hh_soma_update."""
    return hh_step_pallas(v0, m, h, n, g_syn, i_axial, i_ext,
                          dt=float(dt), interpret=_interpret())


def flash_attention(q, k, v, *, causal: bool = True,
                    block_q: int = 128, block_k: int = 128):
    return flash_attention_pallas(q, k, v, causal=causal, block_q=block_q,
                                  block_k=block_k, interpret=_interpret())


def ssd_scan(x, dt, a, b_in, c_in, chunk: int):
    return ssd_scan_pallas(x, dt, a, b_in, c_in, chunk,
                           interpret=_interpret())


def paged_attention_blocks(work, windows, *, q_shape, q_dtype, pool_shape,
                           pool_dtype, n_pages: int) -> int:
    """Compute blocks the Pallas kernel walks for the lanes ``work``
    (``(pos, n_new)`` each) in layers of ``windows`` (0: the whole
    context), summed over lanes, layers and kv-head blocks, for queries
    ``q_shape`` over a pool ``pool_shape`` with a page table
    ``n_pages`` wide.  Host arithmetic only, with the kernel's own block
    sizes and loop bounds."""
    kvb, pages = kernel_blocks(q_shape, pool_shape,
                               jnp.dtype(q_dtype).itemsize,
                               jnp.dtype(pool_dtype).itemsize, n_pages)
    bs = pool_shape[-2]
    per_layer = {w: sum(int(walk_blocks(p, n, w or None, bs, pages, n_pages))
                        for p, n in work) for w in set(windows)}
    return q_shape[2] // kvb * sum(per_layer[w] for w in windows)


def paged_pool_width(head_dim: int, impl: str) -> int:
    """The head width of a paged pool that ``impl`` attends: the Pallas
    kernel copies pages only at whole 128-lane tiles
    (``paged_attention.pool_head_dim``); the reference reads any."""
    return pool_head_dim(head_dim) if impl == "pallas" else head_dim


def paged_attention(q, k_pool, v_pool, page_table, pos, n_new, *,
                    layer=None, window=None, impl: str):
    """Decode/chunk attention through the device page table (the paged
    serving engine's hot path), by the named implementation."""
    if impl == "pallas":
        return paged_attention_pallas(q, k_pool, v_pool, page_table, pos,
                                      n_new, layer=layer, window=window,
                                      interpret=_interpret())
    if impl == "reference":
        return paged_attention_ref(q, k_pool, v_pool, page_table, pos, n_new,
                                   layer=layer, window=window)
    raise ValueError(f"paged attention impl must be one of "
                     f"{PAGED_ATTENTION_IMPLS}, got {impl!r}")
