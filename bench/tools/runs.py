"""Runs a cell as the benchmark's check does, one process per run, and
summarises the spread.  Not part of the benchmark's runs.

    python3 bench/tools/runs.py <cell> <seconds> <trace> <seed> [<seed> ...]

Each seed runs ``bench/run.py`` once, in order; to make the two sets of
the same seeds, pass the seeds twice.  Every result line goes to
``bench_out/runs_<cell>.jsonl`` (with the run's tail of standard error
when it fails); the last line printed is, per metric, the median and the
quartile spread (interquartile range over the median, Python's
``statistics.quantiles``) of each half of the runs.
"""
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def spread(values: list[float]) -> float | None:
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main() -> None:
    cell, seconds, trace = sys.argv[1], sys.argv[2], sys.argv[3]
    seeds = sys.argv[4:]
    out = ROOT / "bench_out" / f"runs_{cell}.jsonl"
    out.parent.mkdir(exist_ok=True)
    results = []
    for seed in seeds:
        t = time.perf_counter()
        p = subprocess.run(
            [sys.executable, "bench/run.py", "--workload", cell, "--seed",
             seed, "--seconds", seconds, "--trace", trace], cwd=ROOT,
            capture_output=True, text=True)
        wall = time.perf_counter() - t
        lines = p.stdout.strip().splitlines()
        try:
            res = json.loads(lines[-1])
        except (IndexError, json.JSONDecodeError):
            res = {"error": p.stderr[-3000:]}
        res.update(seed=int(seed), rc=p.returncode, wall_s=wall)
        if p.returncode or not res.get("correct"):
            res["stderr"] = p.stderr[-3000:]
        results.append(res)
        with out.open("a") as f:
            f.write(json.dumps(res) + "\n")
        print(json.dumps({k: res.get(k) for k in (
            "seed", "rc", "correct", "wall_s", "checks")}
            | {m: v["value"] for m, v in res.get("metrics", {}).items()}),
            flush=True)
    half = len(results) // 2
    summary = {}
    for name in sorted({m for r in results for m in r.get("metrics", {})}):
        sets = [[r["metrics"][name]["value"] for r in part
                 if name in r.get("metrics", {})]
                for part in (results[:half], results[half:])]
        summary[name] = [{"median": statistics.median(v) if v else None,
                          "spread": spread(v)} for v in sets]
    print(json.dumps({"cell": cell, "summary": summary}), flush=True)


if __name__ == "__main__":
    main()
