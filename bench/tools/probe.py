"""Chip probe for building the benchmark: runs cells in one process at
short windows and writes what the profiler trace holds (planes, lines,
the heaviest operations with their statistics) to
``bench_out/probe_trace_<cell>.txt``, so the trace reduction can be
checked against a trace read by hand.

    python3 bench/tools/probe.py <cell>:<seconds>:<trace> ...
"""
import json
import sys
import time
from collections import Counter, defaultdict
from pathlib import Path

T = time.perf_counter()
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def dump(trace_dir, out: Path) -> None:
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    lines = [f"files {files} sizes {[f.stat().st_size for f in files]}"]
    data = ProfileData.from_file(str(files[-1]))
    for plane in data.planes:
        lines.append(f"PLANE {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            lines.append(f"  LINE {line.name!r}: {len(evs)} events, first "
                         f"{evs[0].start_ns if evs else None} last "
                         f"{evs[-1].end_ns if evs else None}")
            if plane.name.startswith("/device") or line.name == "python":
                tot, cnt = defaultdict(float), Counter()
                for e in evs:
                    tot[e.name] += e.duration_ns
                    cnt[e.name] += 1
                for name, t in sorted(tot.items(), key=lambda kv: -kv[1])[:25]:
                    ex = next(e for e in evs if e.name == name)
                    stats = {k: str(v)[:160] for k, v in ex.stats}
                    lines.append(f"    {name!r}: {t * 1e-9:.6f} s over "
                                 f"{cnt[name]}; stats {stats}")
    out.write_text("\n".join(lines))


def main() -> None:
    from bench import harness, spec
    from bench import trace as tracing
    from repro.core.bootstrap import setup_compile_cache

    setup_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = jax.devices()[0]
    print("device", dev.device_kind, len(jax.devices()),
          {k: v for k, v in (dev.memory_stats() or {}).items()
           if k in ("bytes_limit", "bytes_in_use", "peak_bytes_in_use")},
          flush=True)
    out_dir = ROOT / "bench_out"
    out_dir.mkdir(exist_ok=True)
    read = tracing.read
    for arg in sys.argv[1:]:
        name, seconds, traced = arg.split(":")
        tracing.read = lambda d, name=name: (
            dump(d, out_dir / f"probe_trace_{name}.txt"), read(d))[1]
        cell = spec.load(ROOT, name, traced == "1")
        t = time.perf_counter()
        try:
            res = harness.run(cell, 7 + len(name), float(seconds),
                              traced == "1", t)
        except Exception as e:  # noqa: BLE001 - a probe reports and goes on
            import traceback
            traceback.print_exc()
            res = {"error": repr(e)}
        print(f"RESULT {name} {json.dumps(res)}", flush=True)
        print("memory", {k: v for k, v in (dev.memory_stats() or {}).items()
                         if k in ("bytes_limit", "bytes_in_use",
                                  "peak_bytes_in_use")}, flush=True)


if __name__ == "__main__":
    main()
