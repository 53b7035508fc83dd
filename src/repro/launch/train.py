"""End-to-end training driver.

Wire-up (PMIx analogue) → mesh bind → manifest capture → data pipeline →
jitted train step → checkpoint/restart loop with health + straggler
tracking.  Run directly for the CPU-scale example:

    PYTHONPATH=src python -m repro.launch.train --arch granite-moe-1b-a400m \
        --smoke --steps 20 --ckpt-every 10 --out /tmp/run
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import numpy as np

from repro.audit import AuditContext, RunAudit
from repro.configs import SHAPES, TINY_MESH
from repro.configs.base import RunConfig, ShapeConfig, TrainConfig, reduced
from repro.core import Diagnostics, Manifest, PortableEnv, parse_hlo
from repro.core.bootstrap import WireUp, init_distributed, setup_compile_cache
from repro.core.registry import resolve_arch
from repro.data.pipeline import DataConfig, DataPipeline
from repro.launch import bind as B
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.models import build
from repro.parallel import bind as ctx_bind, rules_for
from repro.runtime.checkpoint import CheckpointManager
from repro.runtime.straggler import StragglerTracker
from repro.train.step import init_train_state, make_train_step


def train(arch: str, *, smoke: bool = True, steps: int = 20,
          seq_len: int = 128, global_batch: int = 8, ckpt_every: int = 10,
          out_dir: str = "/tmp/repro_train", production_mesh: bool = False,
          resume: bool = False, seed: int = 0,
          total_steps: int | None = None) -> dict:
    wireup = init_distributed(WireUp.from_env())
    cfg = reduced(resolve_arch(arch)) if smoke else resolve_arch(arch)
    shape = ShapeConfig("train", "train", seq_len, global_batch)
    horizon = total_steps or steps  # LR schedule horizon: fixed across
    # restarts so a resumed run follows the identical schedule
    tc = TrainConfig(total_steps=horizon, warmup_steps=max(horizon // 10, 1),
                     remat="full", seed=seed)
    run = RunConfig(model=cfg, shape=shape, train=tc)

    mesh = (make_production_mesh() if production_mesh
            else make_mesh(TINY_MESH))
    model = build(cfg)
    manifest = Manifest(PortableEnv.capture(cfg, shape, tc, run.rules)).bind(mesh)

    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    ckpt = CheckpointManager(out / "ckpt")
    diag = Diagnostics()
    audit = RunAudit(AuditContext(workload="train", family=cfg.family,
                                  arch=cfg.name,
                                  mesh=tuple(mesh.devices.shape)))

    with ctx_bind(mesh, rules_for(run)):
        step_fn = make_train_step(model, run)
        st_sh = B.state_shardings(model, mesh)
        b_sh = B.batch_shardings(model, shape, mesh)
        jitted = jax.jit(step_fn, in_shardings=(st_sh, b_sh),
                         out_shardings=(st_sh, None), donate_argnums=(0,))

        start_step = 0
        state = init_train_state(model, jax.random.PRNGKey(seed))
        if resume and ckpt.latest_step() is not None:
            start_step = ckpt.latest_step()
            state = ckpt.restore(start_step, like=state, shardings=st_sh)
            audit.tracer.emit("ckpt-restore", step=start_step)
            print(f"[train] resumed from step {start_step}")
        state = jax.device_put(state, st_sh)

        # attest the compiled program (transport inspection on first step)
        lowered = jax.jit(step_fn, in_shardings=(st_sh, b_sh),
                          out_shardings=(st_sh, None), donate_argnums=(0,)
                          ).lower(jax.eval_shape(lambda: state),
                                  model.input_specs(shape))
        compiled = lowered.compile()
        report = parse_hlo(compiled.as_text(), mesh.devices.size)
        manifest.attest(hlo_text=compiled.as_text(),
                        collectives=report.summary())
        diag.extend(report.findings, "train-step-hlo")
        (out / "manifest.json").write_text(manifest.to_json())

        data = DataPipeline(
            DataConfig(cfg.vocab_size, seq_len, global_batch, seed=seed,
                       n_hosts=jax.process_count(),
                       host_id=jax.process_index()),
            start_step=start_step)
        tracker = StragglerTracker(n_hosts=max(jax.process_count(), 1))

        losses = []
        t_start = time.time()
        for _ in range(start_step, steps):
            step_id, host_batch = next(data)
            batch = jax.device_put(host_batch, b_sh)
            t0 = time.perf_counter()
            with audit.tracer.span("train-step", step=step_id) as ev:
                state, metrics = jitted(state, batch)
                loss = float(metrics["loss"])
                ev["loss"] = loss
            dt = time.perf_counter() - t0
            tracker.observe({jax.process_index(): dt})
            losses.append(loss)
            if (step_id + 1) % ckpt_every == 0 or step_id + 1 == steps:
                with audit.tracer.span("ckpt-save", step=step_id + 1):
                    ckpt.save(step_id + 1, state,
                              extra={"loss": loss,
                                     "image_hash":
                                     manifest.portable.image_hash})
        data.close()
        # pathway expectations over the attested transport report: the
        # same HLO the manifest records is judged against what this
        # (family, mesh, workload) should emit
        audit.finish(diag, transport=report, source="train-audit")

    result = {
        "arch": cfg.name,
        "steps": steps,
        "first_loss": losses[0] if losses else None,
        "last_loss": losses[-1] if losses else None,
        "loss_decreased": bool(losses and losses[-1] < losses[0]),
        "wall_s": round(time.time() - t_start, 2),
        "fleet_efficiency": tracker.fleet_efficiency(),
        "diagnostics": diag.worst,
        "audit": {
            "trace": audit.tracer.summary()["counts"],
            "findings": diag.findings,
            "gate_ok": diag.gate(),
        },
        "image_hash": manifest.portable.image_hash,
        "wireup": vars(wireup),
    }
    (out / "result.json").write_text(json.dumps(result, indent=1, default=str))
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--out", default="/tmp/repro_train")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--production-mesh", action="store_true")
    args = ap.parse_args()
    setup_compile_cache()
    res = train(args.arch, smoke=args.smoke, steps=args.steps,
                seq_len=args.seq_len, global_batch=args.global_batch,
                ckpt_every=args.ckpt_every, out_dir=args.out,
                resume=args.resume, production_mesh=args.production_mesh)
    print(json.dumps(res, indent=1, default=str))


if __name__ == "__main__":
    main()
