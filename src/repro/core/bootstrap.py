"""Distributed wire-up — the PMIx analogue.

In the paper, containerized MPI ranks resolve endpoints by querying the
host's Slurm-side PMIx server; the container carries its own complete MPI
stack and only the wire-up protocol crosses the boundary.  The JAX
equivalent of that boundary is ``jax.distributed.initialize``: each host
process knows only (coordinator_address, num_processes, process_id) — the
exact PMIx triple — and everything else (device discovery, mesh
construction, GSPMD partitioning) happens inside the "image".

This module provides:
  * setup_compile_cache() — the persistent XLA compilation cache every
    entry point turns on first;
  * WireUp — the endpoint-resolution dataclass + env/Slurm detection
    (``--mpi=pmix`` analogue: SLURM_* variables → wire-up triple);
  * init_distributed() — binds it (no-op single-process, real
    jax.distributed otherwise);
  * init_benchmark() — the ``osu_init`` analogue: wall-clock of
    wire-up + mesh construction + first-collective compile, the costs the
    paper measures in Fig. 1.
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass
from pathlib import Path

#: The compile cache's home when ``JAX_COMPILATION_CACHE_DIR`` is unset: a
#: fixed directory in the checkout (gitignored).  The path is part of the
#: cache key, so it must not move between runs — never a temp name.
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache lives in the checkout's
    ``.jax_cache``.  Call before the first compile (every entry point:
    ``chip_smoke.py``, ``launch.serve``, ``launch.train``)."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)


@dataclass(frozen=True)
class WireUp:
    coordinator: str
    num_processes: int
    process_id: int
    local_device_count: int | None = None

    @classmethod
    def from_env(cls) -> "WireUp":
        """Resolve the wire-up triple the way srun --mpi=pmix publishes it."""
        if "SLURM_NTASKS" in os.environ and int(os.environ["SLURM_NTASKS"]) > 1:
            nodelist = os.environ.get("SLURM_STEP_NODELIST", "localhost")
            head = nodelist.split(",")[0].split("[")[0]
            port = os.environ.get("REPRO_COORD_PORT", "9876")
            return cls(
                coordinator=f"{head}:{port}",
                num_processes=int(os.environ["SLURM_NTASKS"]),
                process_id=int(os.environ.get("SLURM_PROCID", "0")),
            )
        return cls(coordinator="localhost:9876", num_processes=1, process_id=0)

    @property
    def is_distributed(self) -> bool:
        return self.num_processes > 1


def init_distributed(wireup: WireUp | None = None) -> WireUp:
    """Bind the process into the cluster.  Single-process: no-op."""
    import jax

    w = wireup or WireUp.from_env()
    if w.is_distributed:
        jax.distributed.initialize(
            coordinator_address=w.coordinator,
            num_processes=w.num_processes,
            process_id=w.process_id,
            local_device_count=w.local_device_count,
        )
    return w


def init_benchmark(mesh_shape: tuple[int, ...], axes: tuple[str, ...],
                   repeats: int = 3) -> dict:
    """osu_init analogue: time the runtime's transition to a communicable
    state — (1) wire-up/mesh construction (PMIx exchange + fabric
    discovery), (2) first-collective compile (endpoint/transport setup),
    (3) steady-state collective issue."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    out: dict = {"mesh_shape": mesh_shape, "axes": axes}

    from repro.parallel.ctx import mesh_of

    t0 = time.perf_counter()
    mesh = mesh_of(mesh_shape, axes)
    out["mesh_construct_s"] = time.perf_counter() - t0

    n = mesh.devices.size
    x = jnp.arange(n * 128, dtype=jnp.float32).reshape(n, 128)

    def allreduce_sum(v):
        return jax.lax.with_sharding_constraint(
            jnp.broadcast_to(v.sum(axis=0, keepdims=True), v.shape),
            NamedSharding(mesh, P(axes[0])))

    t0 = time.perf_counter()
    xs = jax.device_put(x, NamedSharding(mesh, P(axes[0])))
    fn = jax.jit(allreduce_sum)
    fn(xs).block_until_ready()
    out["first_collective_s"] = time.perf_counter() - t0

    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn(xs).block_until_ready()
        times.append(time.perf_counter() - t0)
    out["steady_collective_s"] = min(times)
    out["steady_collective_max_s"] = max(times)
    return out
