"""Readings that set a cell's limit: for each seed, one run of the cell
at its own load, then on the same served requests the control's checks
(the reference with fp8 weights in the program's place,
``bench.check.judge(..., quantize=fp8_weights)``) and whether they come
out correct.  Prints one JSON line per seed: the program's widest logit
gap, the control's, and both verdicts.  Not part of the benchmark's
runs.

    python3 bench/tools/control.py <cell> <seconds> <seed> [<seed> ...]
"""
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> None:
    from bench import check, harness, spec
    from repro.core.bootstrap import setup_compile_cache

    setup_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    name, seconds = sys.argv[1], float(sys.argv[2])
    cell = spec.load(ROOT, name, False)
    for seed in map(int, sys.argv[3:]):
        served: list = []
        out = harness.run(cell, seed, seconds, False, time.perf_counter(),
                          keep=served)
        t = time.perf_counter()
        ctl = check.judge(cell.reference, cell.config, seed, served,
                          quantize=check.fp8_weights)
        print(json.dumps({
            "cell": name, "seed": seed,
            "program": out["checks"]["widest_logit_gap"]["value"],
            "control": ctl["widest_logit_gap"]["value"],
            "compared": ctl["compared_tokens"]["value"],
            "requests": len(served),
            "correct": out["correct"],
            "control_correct": check.passed(ctl),
            "output_tokens_per_s": out["metrics"].get(
                "output_tokens_per_s", {}).get("value"),
            "control_s": time.perf_counter() - t}), flush=True)


if __name__ == "__main__":
    main()
