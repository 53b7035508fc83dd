"""Perf-iteration probe: lower one cell under a rules/remat variant, print
the three roofline terms + the top collectives with their HLO context.

    PYTHONPATH=src python scripts/perf_probe.py ARCH SHAPE [--rules X]
        [--remat X] [--mb N] [--top N] [--save-hlo PATH]
"""
import os

# a CPU-only lowering tool: 512 placeholder devices, never the chip
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import sys

import jax

from repro.configs import ALL_ARCHS, SHAPES
from repro.configs.base import RunConfig, TrainConfig
from repro.core.inspector import hlo_cost, parse_hlo
from repro.launch.bind import abstract_cell
from repro.launch.dryrun import _default_microbatches
from repro.launch.mesh import make_production_mesh, mesh_config
from repro.models import build
from repro.parallel import bind as ctx_bind, rules_for

from repro.core.peaks import chip_peaks

_V5E = chip_peaks("TPU v5 lite")      # the chip the cells are lowered for
PEAK, HBM, ICI = _V5E.bf16_flops, _V5E.hbm_bw, _V5E.ici_link_bw


def probe(arch, shape_name, rules="auto", remat="full", mb=None,
          multi_pod=False, top=10, save_hlo=None):
    cfg = ALL_ARCHS[arch]
    shape = SHAPES[shape_name]
    if mb is None:
        mb = _default_microbatches(cfg, shape)
    run = RunConfig(model=cfg, shape=shape,
                    mesh=mesh_config(multi_pod=multi_pod), rules=rules,
                    train=TrainConfig(remat=remat, microbatches=mb))
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = build(cfg)
    with ctx_bind(mesh, rules_for(run)):
        fn, args, shards, out_sh, donate = abstract_cell(model, run, mesh)
        compiled = jax.jit(fn, in_shardings=shards, out_shardings=out_sh,
                           donate_argnums=donate).lower(*args).compile()
    hlo = compiled.as_text()
    if save_hlo:
        open(save_hlo, "w").write(hlo)
    m = compiled.memory_analysis()
    mem = (m.argument_size_in_bytes + m.temp_size_in_bytes
           + m.output_size_in_bytes - m.alias_size_in_bytes)
    hc = hlo_cost(hlo)
    rep = parse_hlo(hlo, mesh.devices.size)
    t_c, t_m, t_x = (hc["dot_flops"] / PEAK, hc["bytes"] / HBM,
                     rep.total_moved_bytes / ICI)
    print(f"== {arch} × {shape_name} rules={rules} remat={remat} mb={mb} "
          f"{'mp' if multi_pod else 'sp'} ==")
    print(f"terms: compute={t_c:.3f}s memory={t_m:.3f}s "
          f"collective={t_x:.3f}s  mem/dev={mem/2**30:.2f}GiB")
    print(f"moved by kind: "
          f"{ {k: f'{v/2**30:.1f}GiB' for k, v in rep.by_kind().items()} }")
    ops = sorted(rep.ops, key=lambda o: -o.moved_bytes)[:top]
    for o in ops:
        print(f"  {o.kind:18s} {o.payload_bytes/2**20:9.1f}MiB g={o.group_size:3d} "
              f"x{o.trips:4d} -> {o.moved_bytes/2**30:7.2f}GiB  "
              f"{o.computation[:34]:34s} {o.name}")
    return dict(t_c=t_c, t_m=t_m, t_x=t_x, mem=mem)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("arch")
    ap.add_argument("shape")
    ap.add_argument("--rules", default="auto")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--mb", type=int, default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--top", type=int, default=10)
    ap.add_argument("--save-hlo", default=None)
    a = ap.parse_args()
    probe(a.arch, a.shape, a.rules, a.remat, a.mb, a.multi_pod, a.top,
          a.save_hlo)
