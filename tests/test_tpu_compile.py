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
from repro.kernels.paged_attention import (heads_per_block, kernel_blocks,
                                           paged_attention_pallas,
                                           pool_head_dim)

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
                    num_blocks, layers=1, window=False):
    """The kernel over a stacked pool of ``layers`` (heads at the pool's
    width) at a traced layer, as the layer scan calls it."""
    bf16, i32 = jnp.bfloat16, jnp.int32
    pool = (layers, num_blocks, kv, bs, pool_head_dim(hd))
    args = (
        _sds((slots, chunk, kv, group, hd), bf16, one_chip),      # q
        _sds(pool, bf16, one_chip),                                # k pool
        _sds(pool, bf16, one_chip),                                # v pool
        _sds((slots, pages), i32, one_chip),                       # table
        _sds((slots,), i32, one_chip),                             # pos
        _sds((slots,), i32, one_chip),                             # n_new
        _sds((), i32, one_chip),                                   # layer
    )
    if window:
        return jax.jit(lambda *a: paged_attention_pallas(
            *a[:6], layer=a[6], window=a[7])).lower(
                *args, _sds((), i32, one_chip)).compile()
    return jax.jit(lambda *a: paged_attention_pallas(*a[:6], layer=a[6])
                   ).lower(*args).compile()


# the two cells' geometries: phi3-mini.decode-batch (8 slots of 32 MHA
# heads of 96, a 640-page pool of 32 layers, 256 pages a lane) and
# trinity-mini.decode-long (32 slots, 4 kv heads of 8 query heads, 128
# wide, a 12,288-page pool of 16 layers, 512 pages a lane)
PHI3_CELL = dict(slots=8, chunk=32, kv=32, group=1, hd=96, bs=16,
                 pages=256, num_blocks=640, layers=32)
TRINITY_CELL = dict(slots=32, chunk=32, kv=4, group=8, hd=128, bs=16,
                    pages=512, num_blocks=12288, layers=16)


def test_windowed_paged_kernel_compiles_for_v5e(one_chip):
    """The kernel with a window at both cells' geometries: all kv heads
    in one grid step (256 query rows a head on trinity), several pages
    a compute block."""
    assert heads_per_block(4, 32 * 8, 16, 128, 2, 2) == 4
    for cell, blocks in ((TRINITY_CELL, (4, 16)), (PHI3_CELL, (32, 8))):
        q = (cell["slots"], cell["chunk"], cell["kv"], cell["group"],
             cell["hd"])
        pool = (cell["layers"], cell["num_blocks"], cell["kv"], cell["bs"],
                pool_head_dim(cell["hd"]))
        assert kernel_blocks(q, pool, 2, 2, cell["pages"]) == blocks
        compiled = _compile_kernel(one_chip, window=True, **cell)
        assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shape", [
    # phi3-mini-3.8b: 32 kv heads (no GQA), head_dim 96 — not a multiple
    # of 128, so the pool's heads are 128 wide and the queries padded
    dict(kv=32, group=1, hd=96),
    # a GQA width: 8 kv heads of 4 query heads each, head_dim 128
    dict(kv=8, group=4, hd=128),
    # the phi3-mini.decode-batch cell's own geometry: the blocks' VMEM
    # at the served size
    PHI3_CELL,
    # phi3 served at chunk 512 (``launch.serve --chunk``): 32 heads' blocks
    # overrun the VMEM budget, so a grid step holds 2 of them
    dict(kv=32, group=1, hd=96, chunk=512),
    # the trinity-mini.decode-long cell's own geometry, no window
    TRINITY_CELL,
], ids=["phi3-kv32-hd96", "gqa-kv8-g4-hd128", "phi3-cell-8slots-256pages",
        "phi3-chunk512-split-heads", "trinity-cell-32slots-512pages"])
def test_paged_kernel_compiles_for_v5e(one_chip, shape):
    geometry = dict(slots=4, chunk=32, bs=16, pages=20, num_blocks=64)
    geometry |= shape
    if geometry["chunk"] == 512:
        assert heads_per_block(32, 512, 16, 128, 2, 2) == 2
    compiled = _compile_kernel(one_chip, **geometry)
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
    carries a mark, in its own name or its op_name, by which a profile's
    reader finds the kernel."""
    import re

    compiled, _ = paged_step
    text = compiled.as_text()
    assert text.startswith("HloModule jit_paged_greedy_step")
    op_name = re.compile(r'op_name="([^"]*)"')
    kernel_scope = "/paged_attention/pallas_call"
    marked, kv_append, calls = [], 0, 0
    for line in text.splitlines():
        head, eq, _ = line.strip().partition(" = ")
        if not eq:
            continue
        name = head.removeprefix("ROOT ")
        scope = op_name.search(line)
        scope = scope.group(1) if scope else ""
        if 'custom_call_target="tpu_custom_call"' in line:
            calls += 1
            assert name.startswith("%paged_attention"), head
        elif any(m in name or m in scope for m in KERNEL_MARKS):
            marked.append((name, scope))
        kv_append += "/kv_append/" in scope
    assert calls == 1 and kv_append
    # what else carries a mark is inside the kernel's own scope
    assert all(s.endswith(kernel_scope) for _, s in marked), [
        m for m in marked if not m[1].endswith(kernel_scope)]
    loops = [ln for ln in text.splitlines() if " while(" in ln]
    assert loops and not any(m in ln for ln in loops for m in KERNEL_MARKS)
