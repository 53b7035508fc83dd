"""Compile rehearsal without the chip: each configuration's greedy paged
step at its serving geometry, and the benchmark's weight-drawing program,
compiled for a described TPU v5e; prints ``memory_analysis()``.

    JAX_PLATFORMS=cpu python3 bench/tools/rehearse.py

Nothing runs, so this says nothing about results or times.  Not a test:
one 32-layer compile takes up to a minute and a half.
"""
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import program, spec
    from repro.models import params as P
    from repro.serve.engine import _chunk_fn_for
    from repro.serve.paging import pages_for, pool_format

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    fmt = pool_format(topo.devices[0])
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for entry in bench["configs"]:
        cfg = json.loads((ROOT / entry["file"]).read_text())
        ref = spec.load_module(ROOT / "bench" / "references"
                               / f"{cfg['reference']}.py")
        family = spec.load_module(ROOT / "bench" / "families"
                                  / f"{cfg['family']}.py")
        sv = cfg["serving"]
        model = program.build_model(family.model_config(cfg))

        def place(tree, sharding=one):
            return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=sharding), tree)

        want = program.abstract_params(model)
        params = place(want)
        cache = place(P.abstract(model.paged_cache_specs(
            sv["pool_pages"], sv["block_size"])), fmt)
        step = _chunk_fn_for(model, False, "pallas", fmt)
        i32 = jnp.int32
        s, c = sv["slots"], sv["chunk"]
        pages = pages_for(sv["max_len"], sv["block_size"])
        compiled = step.lower(
            params, cache, jax.ShapeDtypeStruct((s, c), i32, sharding=one),
            jax.ShapeDtypeStruct((s,), i32, sharding=one),
            jax.ShapeDtypeStruct((s,), i32, sharding=one),
            jax.ShapeDtypeStruct((s, pages), i32, sharding=one)).compile()
        mem = compiled.memory_analysis()
        print(f"{cfg['name']} step: arguments {mem.argument_size_in_bytes} "
              f"B, outputs {mem.output_size_in_bytes} B, temporaries "
              f"{mem.temp_size_in_bytes} B, aliased "
              f"{mem.alias_size_in_bytes} B, kernel "
              f"{'tpu_custom_call' in compiled.as_text()}", flush=True)
        # the weight-drawing program, as program.make_params jits it
        make = family.draw(ref, cfg, want)
        key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=one)
        mem = jax.jit(make).lower(key).compile().memory_analysis()
        print(f"{cfg['name']} weights: outputs {mem.output_size_in_bytes} "
              f"B, temporaries {mem.temp_size_in_bytes} B", flush=True)


if __name__ == "__main__":
    main()
