"""Benchmark helpers: subprocess multi-device runs + timing."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time
from typing import Any, Callable

from repro.core.peaks import chip_peaks

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_devices(code: str, n_devices: int, timeout: int = 600) -> dict:
    """Run `code` in a subprocess with n placeholder CPU devices; the code
    must print one JSON object on its last line.  The child is pinned to
    the CPU platform: it models the fabric on placeholder devices and must
    never contend with its parent for an attached accelerator."""
    pre = "import sys; sys.path.insert(0, 'src')\n"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_devices}")
    out = subprocess.run(
        [sys.executable, "-c", pre + textwrap.dedent(code)],
        capture_output=True, text=True, cwd=REPO, timeout=timeout, env=env)
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-2000:])
    return json.loads(out.stdout.strip().splitlines()[-1])


def time_call(fn: Callable[[], Any], repeats: int = 5, warmup: int = 2) -> dict:
    for _ in range(warmup):
        fn()
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return {"mean_s": sum(times) / len(times), "min_s": min(times),
            "max_s": max(times)}


# The dry-run cost models target TPU v5e: its published peaks, from the
# one device-kind table (core.peaks)
_V5E = chip_peaks("TPU v5 lite")
PEAK_FLOPS = _V5E.bf16_flops
HBM_BW = _V5E.hbm_bw
ICI_BW = _V5E.ici_link_bw
ICI_LAT = 1e-6   # assumed per-hop latency (not a published figure)
