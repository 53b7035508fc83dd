"""Compile rehearsals for the TPU v5e, without the chip.

The TPU compiler is installed beside JAX and compiles for a described,
unattached chip (``jax.experimental.topologies``).  These tests compile
the serving path's Pallas paged-attention kernel and the jitted paged
step at published widths for one v5e chip: what Mosaic or XLA would
refuse there fails here, at no chip time.  Nothing runs, so they say
nothing about results or speed.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, so every
pytest worker must collect the same tests and only the worker that runs
this file may load it.  All compile rehearsals stay in this one file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import ALL_ARCHS
from repro.kernels.paged_attention import paged_attention_pallas

pytestmark = pytest.mark.kernel


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_kernel(one_chip, *, slots, chunk, kv, group, hd, bs, pages,
                    num_blocks):
    bf16, i32 = jnp.bfloat16, jnp.int32
    args = (
        _sds((slots, chunk, kv, group, hd), bf16, one_chip),      # q
        _sds((num_blocks, kv, bs, hd), bf16, one_chip),            # k pool
        _sds((num_blocks, kv, bs, hd), bf16, one_chip),            # v pool
        _sds((slots, pages), i32, one_chip),                       # table
        _sds((slots,), i32, one_chip),                             # pos
        _sds((slots,), i32, one_chip),                             # n_new
    )
    return jax.jit(paged_attention_pallas).lower(*args).compile()


@pytest.mark.parametrize("shape", [
    # phi3-mini-3.8b: 32 kv heads (no GQA), head_dim 96 — not a multiple
    # of 128, so the block's minor dim is the whole axis
    dict(kv=32, group=1, hd=96),
    # a GQA width: 8 kv heads of 4 query heads each, head_dim 128
    dict(kv=8, group=4, hd=128),
], ids=["phi3-kv32-hd96", "gqa-kv8-g4-hd128"])
def test_paged_kernel_compiles_for_v5e(one_chip, shape):
    compiled = _compile_kernel(one_chip, slots=4, chunk=32, bs=16, pages=20,
                               num_blocks=64, **shape)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def paged_step(topo, one_chip):
    """The engine's jitted greedy step (``decode_paged_greedy_chunk`` with
    the Pallas kernel, built as the engine builds it) at phi3-mini-3.8b's
    published widths, cut to 2 layers, compiled for one v5e: (compiled,
    bytes of one layer's K and V pools)."""
    import dataclasses

    from repro.kernels import ops
    from repro.models import build
    from repro.models import params as P
    from repro.serve.engine import _chunk_fn_for
    from repro.serve.paging import pool_format

    cfg = dataclasses.replace(ALL_ARCHS["phi3-mini-3.8b"], n_layers=2)
    model = build(cfg)
    # the pool the chip run holds: a copy of it would dwarf every other
    # temporary of the step
    slots, chunk, bs, num_blocks, pages = 4, 32, 16, 512, 20
    fmt = pool_format(topo.devices[0])

    def place(tree, sharding=one_chip):
        return jax.tree.map(
            lambda a: _sds(a.shape, a.dtype, sharding), tree)

    params = place(jax.eval_shape(model.init_params,
                                  jax.random.PRNGKey(0)))
    cache = place(P.abstract(model.paged_cache_specs(num_blocks, bs)), fmt)
    step = _chunk_fn_for(model, False, "pallas", fmt)
    i32 = jnp.int32
    # the suite runs kernels in interpret mode on the CPU; this compile
    # is for the described chip, where the kernel lowers natively
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "FORCE_INTERPRET", False)
        compiled = step.lower(
            params, cache, _sds((slots, chunk), i32, one_chip),
            _sds((slots,), i32, one_chip), _sds((slots,), i32, one_chip),
            _sds((slots, pages), i32, one_chip)).compile()
    return compiled, cache["paged"]["k"].size // cfg.n_layers * 2


def test_paged_step_compiles_at_published_width(paged_step):
    """The step compiles, lowers the kernel, and updates the donated page
    pool in place — no temporary as large as one layer's pool (an XLA
    relayout of the pool around the kernel call shows up here as a
    whole-pool temporary)."""
    compiled, layer_pool = paged_step
    assert "tpu_custom_call" in compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < layer_pool, (temp, layer_pool)


# what a profile's reader looks for to find the paged kernel's operations
# by name or label (the benchmark's ``paged_attn_roofline``)
KERNEL_MARKS = ("paged_attention", "pallas_call", "_paged_kernel",
                "tpu_custom_call")


def test_paged_step_names_its_program_kernel_and_kv_write(paged_step):
    """The program is ``jit_paged_greedy_step``, the kernel's custom call
    is ``paged_attention``, and the KV write sits under ``kv_append``:
    no other instruction — the layer loop and the KV write above all —
    carries a mark by which a profile's reader finds the kernel."""
    import re

    compiled, _ = paged_step
    text = compiled.as_text()
    assert text.startswith("HloModule jit_paged_greedy_step")
    kernel_scope = re.compile(r'op_name="[^"]*/paged_attention/pallas_call"')
    marked, kv_append, calls = [], 0, 0
    for line in text.splitlines():
        head, eq, _ = line.strip().partition(" = ")
        if not eq:
            continue
        if 'custom_call_target="tpu_custom_call"' in line:
            calls += 1
            assert head.removeprefix("ROOT ").startswith(
                "%paged_attention"), head
        elif any(m in line for m in KERNEL_MARKS):
            marked.append(line.strip())
        kv_append += "/kv_append/" in line
    assert calls == 1 and kv_append
    # what else carries a mark is inside the kernel's own scope
    assert all(kernel_scope.search(m) for m in marked), [
        m[:200] for m in marked if not kernel_scope.search(m)]
    loops = [ln for ln in text.splitlines() if " while(" in ln]
    assert loops and not any(m in ln for ln in loops for m in KERNEL_MARKS)
