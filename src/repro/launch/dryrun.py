import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The lines above MUST run before any jax import: jax locks the device
# count at first backend init.  This module is the only place the 512
# placeholder devices exist — tests and benches see the real single device
# — and it lowers on the CPU platform even where a TPU is attached.

import argparse
import json
import time
import traceback
from pathlib import Path

import jax

from repro.audit.trace import Tracer
from repro.configs import ALL_ARCHS, SHAPES, applicable_shapes
from repro.configs.base import RunConfig, TrainConfig
from repro.core.inspector import hlo_cost, parse_hlo
from repro.launch.bind import abstract_cell
from repro.launch.mesh import make_production_mesh, mesh_config
from repro.models import build
from repro.models.stack import nonembedding_param_count, param_count
from repro.parallel import bind as ctx_bind, rules_for


HBM_BYTES = 16 * 2**30  # TPU v5e


def _default_microbatches(cfg, shape) -> int:
    """Pick gradient-accumulation depth so the per-device saved-activation
    stack (≈ L·D·tokens_dev·2B ×2.9 measured slope, see EXPERIMENTS §Dry-run)
    targets <12 GiB.  Powers of two only."""
    if not shape.is_train:
        return 0
    est_gib = 7.4 * (cfg.n_layers * cfg.d_model) / 98304.0
    mb = 1
    while est_gib / mb > 11.0 and mb < 16:
        mb *= 2
    return mb if mb > 1 else 0


def dryrun_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
                rules: str = "auto", remat: str = "full",
                microbatches: int | None = None,
                out_dir: str | None = None, verbose: bool = True,
                tracer: Tracer | None = None) -> dict:
    """Lower + compile one (arch × shape × mesh) cell; return the record.
    The cell's trace (lower/compile spans, error events) is dumped into
    the artifact so a failed or slow sweep can be audited offline."""
    cfg = ALL_ARCHS[arch]
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mb = (_default_microbatches(cfg, shape)
          if microbatches is None else microbatches)
    run = RunConfig(model=cfg, shape=shape, mesh=mesh_config(multi_pod=multi_pod),
                    rules=rules, train=TrainConfig(remat=remat, microbatches=mb))
    model = build(cfg)
    n_dev = mesh.devices.size
    trace = tracer or Tracer(capacity=256)
    trace_start = trace.emitted      # dump only this cell's events below

    rec: dict = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "axes": list(mesh.axis_names),
        "rules": run.rules, "remat": remat, "microbatches": mb,
        "params": param_count(cfg),
        "params_active": param_count(cfg, active_only=True),
        "params_nonembed_active": nonembedding_param_count(cfg, True),
        "status": "ok",
    }
    t0 = time.time()
    try:
        with ctx_bind(mesh, rules_for(run)):
            fn, args, shards, out_shards, donate = abstract_cell(model, run, mesh)
            with trace.span("dryrun-lower", arch=arch, shape=shape_name,
                            mesh=rec["mesh"], rules=run.rules):
                lowered = jax.jit(fn, in_shardings=shards,
                                  out_shardings=out_shards,
                                  donate_argnums=donate).lower(*args)
            rec["lower_s"] = round(time.time() - t0, 2)
            t1 = time.time()
            with trace.span("dryrun-compile", arch=arch, shape=shape_name):
                compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 2)

        mem = compiled.memory_analysis()
        if mem is not None:
            rec["memory"] = {
                k: int(getattr(mem, k))
                for k in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "generated_code_size_in_bytes",
                          "alias_size_in_bytes")
                if hasattr(mem, k)
            }
            args_b = rec["memory"].get("argument_size_in_bytes", 0)
            alias_b = rec["memory"].get("alias_size_in_bytes", 0)
            tmp_b = rec["memory"].get("temp_size_in_bytes", 0)
            out_b = rec["memory"].get("output_size_in_bytes", 0)
            rec["memory"]["per_device_total"] = args_b + tmp_b + (out_b - alias_b)
        cost = compiled.cost_analysis() or {}
        if isinstance(cost, (list, tuple)):  # older jax: one dict per program
            cost = cost[0] if cost else {}
        rec["cost"] = {k: float(v) for k, v in cost.items()
                       if isinstance(v, (int, float)) and (
                           "flops" in k or "bytes" in k or k in ("transcendentals",))}

        hlo = compiled.as_text()
        report = parse_hlo(hlo, n_partitions=n_dev)
        rec["collectives"] = report.summary()
        # execution-weighted (loop-trip-aware) flops/bytes — XLA's own
        # cost_analysis counts while bodies once (see inspector.hlo_cost)
        rec["hlo_cost"] = hlo_cost(hlo)
        rec["hlo_bytes"] = len(hlo)
    except Exception as e:  # noqa: BLE001 — record the failure, don't crash the sweep
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        trace.emit("dryrun-error", arch=arch, shape=shape_name,
                   error=rec["error"].splitlines()[0][:200])

    # ring dump into the artifact: the audit convention applied to the
    # launcher itself (ROADMAP PR 2 follow-up) — what lowered/compiled,
    # how long each stage took, and any error, machine-readable.  A
    # shared tracer only contributes this cell's events to this artifact.
    rec["trace"] = {"summary": trace.summary(),
                    "events": [e.to_dict() for e in trace.events()
                               if e.seq >= trace_start]}

    if verbose:
        flops = rec.get("cost", {}).get("flops", 0)
        mem_b = rec.get("memory", {}).get("per_device_total", 0)
        print(f"[{rec['status']:5s}] {arch} × {shape_name} × {rec['mesh']} "
              f"rules={run.rules} lower={rec.get('lower_s', 0):.1f}s "
              f"compile={rec.get('compile_s', 0):.1f}s "
              f"flops/dev={flops:.3e} mem/dev={mem_b/2**30:.2f}GiB "
              f"coll={rec.get('collectives', {}).get('total_moved_bytes', 0):.3e}B")
        if rec["status"] == "error":
            print("   ", rec["error"].splitlines()[0][:200])

    if out_dir:
        path = Path(out_dir)
        path.mkdir(parents=True, exist_ok=True)
        pod = "mp" if multi_pod else "sp"
        fname = f"{arch}__{shape_name}__{pod}__{run.rules}__{remat}.json"
        (path / fname).write_text(json.dumps(rec, indent=1))
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None, help="arch id (default: all)")
    ap.add_argument("--shape", default=None, help="shape name (default: all applicable)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--rules", default="auto")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--out", default="EXPERIMENTS/dryrun")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ALL_ARCHS)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = 0
    for arch in archs:
        shapes = [args.shape] if args.shape else applicable_shapes(arch)
        for shape in shapes:
            for mp in meshes:
                rec = dryrun_cell(arch, shape, multi_pod=mp, rules=args.rules,
                                  remat=args.remat, out_dir=args.out)
                failures += rec["status"] != "ok"
    if failures:
        raise SystemExit(f"{failures} cell(s) failed")


if __name__ == "__main__":
    main()
