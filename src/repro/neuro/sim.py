"""Bulk-synchronous distributed ring simulation (Arbor's execution model).

Arbor advances all cells independently for one min-delay window, then
exchanges the generated spikes with a global MPI_Allgather (§6.2.1 of the
paper).  The JAX-native mapping:

  MPI rank            -> shard_map shard over a 1D 'cells' mesh axis
  local cell update   -> inner lax.scan over dt steps (HH kernel hotspot)
  MPI_Allgather       -> jax.lax.all_gather of the epoch's spike matrix
  axonal delay        -> the exchange epoch length (spikes generated in
                         epoch k are applied in epoch k+1)

The same function runs single-device (tests) and sharded (benchmarks,
dry-run at production meshes) — the paper's portable-image property.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from repro.neuro import cable
from repro.neuro.ring import RingConfig, is_ring_head, source_of


@dataclass
class SimResult:
    spike_counts: Any          # [N] int32 — spikes per cell
    total_spikes: int
    wavefront: Any             # [n_epochs] int32 — furthest spiking cell per epoch
    wall_s: float
    state: cable.CellState


def _epoch_fn(cfg: RingConfig, n_loc: int, axis: str | None,
              use_pallas: bool):
    heads_g = is_ring_head(cfg)
    sources_g = source_of(cfg)
    steps = cfg.delay_steps
    dt = cfg.cell.dt
    stim_steps = int(round(cfg.stim_ms / dt))

    def epoch(carry, epoch_idx):
        state, incoming = carry  # incoming: [steps, n_loc]
        if axis is not None:
            my_start = jax.lax.axis_index(axis) * n_loc
        else:
            my_start = 0
        heads = jax.lax.dynamic_slice(heads_g, (my_start,), (n_loc,))
        base_step = epoch_idx * steps

        def substep(st, inp):
            step_in_epoch, spikes_in = inp
            t_step = base_step + step_in_epoch
            i_ext = jnp.where(heads & (t_step < stim_steps),
                              cfg.stim_current, 0.0).astype(jnp.float32)
            st, spiked = cable.step(st, cfg.cell, spikes_in, i_ext,
                                    use_pallas=use_pallas)
            return st, spiked

        state, spiked = jax.lax.scan(
            substep, state, (jnp.arange(steps), incoming))
        # spikes travel as int8 (the paper's MPI_Allgather moves compact
        # spike records too): 4x less exchange traffic than f32 flags
        spiked_i = spiked.astype(jnp.int8)  # [steps, n_loc]

        # --- spike exchange (MPI_Allgather analogue) ---
        if axis is not None:
            gathered = jax.lax.all_gather(
                spiked_i, axis, axis=1, tiled=True)  # [steps, N]
        else:
            gathered = spiked_i
        src_ids = jax.lax.dynamic_slice(sources_g, (my_start,), (n_loc,))
        incoming_next = jnp.take(gathered, src_ids, axis=1).astype(jnp.float32)

        counts = jnp.sum(spiked, axis=0).astype(jnp.int32)  # [n_loc]
        front = jnp.max(jnp.where(
            jnp.any(spiked, axis=0), my_start + jnp.arange(n_loc), -1))
        if axis is not None:
            front = jax.lax.pmax(front, axis)
        return (state, incoming_next), (counts, front)

    return epoch


def _run_local(cfg: RingConfig, n_loc: int, axis: str | None,
               use_pallas: bool):
    epoch = _epoch_fn(cfg, n_loc, axis, use_pallas)

    def run(state: cable.CellState):
        incoming = jnp.zeros((cfg.delay_steps, n_loc), jnp.float32)
        (state, _), (counts, fronts) = jax.lax.scan(
            epoch, (state, incoming), jnp.arange(cfg.n_epochs))
        return state, jnp.sum(counts, axis=0), fronts

    return run


def simulate(cfg: RingConfig, *, mesh=None, axis: str = "cells",
             use_pallas: bool = False, jit: bool = True) -> SimResult:
    """Run the ring network.  ``mesh``: optional 1D Mesh to distribute
    cells over (n_cells must divide evenly); None = single device."""
    if mesh is not None:
        n_shards = mesh.devices.size
        assert cfg.n_cells % n_shards == 0
        n_loc = cfg.n_cells // n_shards
        run = _run_local(cfg, n_loc, axis, use_pallas)
        spec = jax.sharding.PartitionSpec(axis)
        state_specs = cable.CellState(
            v=spec, m=spec, h=spec, n=spec, g_syn=spec)
        fn = jax.shard_map(
            run, mesh=mesh, in_specs=(state_specs,),
            out_specs=(state_specs, spec, jax.sharding.PartitionSpec()),
            check_vma=False)
    else:
        n_loc = cfg.n_cells
        fn = _run_local(cfg, n_loc, None, use_pallas)

    if jit:
        fn = jax.jit(fn)
    state0 = cable.init_state(cfg.n_cells, cfg.cell)
    if mesh is not None:
        sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(axis))
        state0 = jax.tree.map(lambda x: jax.device_put(x, sh), state0)

    # compile (excluded from wall time, reported separately by benchmarks)
    out = fn(state0)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    state, counts, fronts = fn(state0)
    jax.block_until_ready(counts)
    wall = time.perf_counter() - t0

    return SimResult(
        spike_counts=counts,
        total_spikes=int(jnp.sum(counts)),
        wavefront=fronts,
        wall_s=wall,
        state=state,
    )
