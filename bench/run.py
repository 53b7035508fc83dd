"""Runs one cell of the benchmark once and prints its result as the last
line of standard output:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are found by name
from ``BENCHMARK.json`` at the root of the checkout (``bench/spec.py``).
With ``--trace 0`` the result carries the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read from a profiler trace of
the window.  The numbers that decide ``correct`` are printed beside
their limits as the last lines of standard error and under ``checks``,
the last key of the result.

It runs only on a TPU with at least the chips the cell asks for, and only
in a checkout that holds the program (``src/repro``); otherwise it exits
non-zero and prints no result.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def fail(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no program under {ROOT / 'src' / 'repro'}: run from a "
             f"checkout of the repository")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import spec

    cell = spec.load(ROOT, args.workload, bool(args.trace))
    from repro.core.bootstrap import setup_compile_cache

    cache = setup_compile_cache()
    import jax

    # every program goes into the cache, so only a checkout's first run
    # of a cell compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        fail(f"needs {cell.chips} TPU chip(s); JAX finds {len(devs)} "
             f"{devs[0].platform!r} device(s)")
    print(f"bench: {cell.name} on {len(devs)} x {devs[0].device_kind}, "
          f"seed {args.seed}, compile cache {cache}", file=sys.stderr,
          flush=True)
    from bench import harness

    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      T_PROCESS)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
