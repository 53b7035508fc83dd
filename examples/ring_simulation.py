"""The paper's application workload: a Hodgkin–Huxley ring network
(Arbor ring benchmark) — watch the action potential propagate one cell per
axonal-delay epoch, then compare the jnp path against the Pallas HH-kernel
path (the dual-environment check on real physiology).

    PYTHONPATH=src python examples/ring_simulation.py
"""
import jax
import numpy as np

from repro.kernels import ops
from repro.neuro.cable import CellConfig
from repro.neuro.ring import RingConfig
from repro.neuro.sim import simulate

cfg = RingConfig(n_cells=48, t_end_ms=45.0,
                 cell=CellConfig(n_compartments=8))
r = simulate(cfg)
front = np.asarray(r.wavefront)
print(f"cells={cfg.n_cells}  epochs={cfg.n_epochs}  "
      f"delay={cfg.delay_ms}ms  dt={cfg.cell.dt}ms")
print(f"total spikes: {r.total_spikes}")
print("wavefront per epoch:", front.tolist())
reached = front[front >= 0]  # -1 = no spike that epoch (EPSP rise time can
# push the last hop past t_end — the wave continues, the clock stops)
assert (np.diff(reached) >= 0).all(), "wave must advance monotonically"
assert r.total_spikes == int(reached[-1]) + 1

# off the TPU the HH kernel runs in interpret mode, by explicit choice
ops.FORCE_INTERPRET = jax.default_backend() != "tpu"
rk = simulate(cfg, use_pallas=True)
assert np.array_equal(np.asarray(r.spike_counts),
                      np.asarray(rk.spike_counts)), "kernel parity"
print("pallas HH kernel path: spike-for-spike identical")
print("OK")
