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

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.hh_neuron import hh_step_pallas
from repro.kernels.paged_attention import (paged_attention_pallas,
                                           paged_attention_ref)
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


def paged_attention(q, k_pool, v_pool, page_table, pos, n_new, *,
                    layer=None, impl: str):
    """Decode/chunk attention through the device page table (the paged
    serving engine's hot path), by the named implementation."""
    if impl == "pallas":
        return paged_attention_pallas(q, k_pool, v_pool, page_table, pos,
                                      n_new, layer=layer,
                                      interpret=_interpret())
    if impl == "reference":
        return paged_attention_ref(q, k_pool, v_pool, page_table, pos, n_new,
                                   layer=layer)
    raise ValueError(f"paged attention impl must be one of "
                     f"{PAGED_ATTENTION_IMPLS}, got {impl!r}")
