"""Dev harness: 8-device sharded lower+compile+run for reduced configs,
and numeric parity sharded-vs-single-device.  Run in a subprocess."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"   # 8 placeholder CPU devices
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

import jax
import jax.numpy as jnp

from repro.configs import ALL_ARCHS, reduced, ShapeConfig
from repro.configs.base import RunConfig, TrainConfig
from repro.launch.bind import abstract_cell, batch_shardings, param_shardings
from repro.models import build
from repro.parallel import bind as ctx_bind, rules_for
from repro.train.step import init_train_state, make_train_step

names = sys.argv[1:] or list(ALL_ARCHS)
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 3)

for name in names:
    cfg = reduced(ALL_ARCHS[name])
    model = build(cfg)
    key = jax.random.PRNGKey(0)

    # ---- single-device reference ----
    shape = ShapeConfig("t", "train", 32, 4)
    batch = model.sample_batch(shape, key)
    params = model.init_params(key)
    ref_loss, _ = jax.jit(lambda p, b: model.loss(p, b))(params, batch)

    # ---- sharded ----
    run = RunConfig(model=cfg, shape=shape, train=TrainConfig(remat="full"))
    with ctx_bind(mesh, rules_for(run)):
        psh = param_shardings(model, mesh)
        bsh = batch_shardings(model, shape, mesh)
        params_s = jax.device_put(params, psh)
        batch_s = jax.device_put(batch, bsh)
        loss_s, _ = jax.jit(lambda p, b: model.loss(p, b))(params_s, batch_s)
        # full train step compile + run
        state = init_train_state(model, key)
        fn, args, shards, out_shards, donate = abstract_cell(model, run, mesh)
        step = jax.jit(fn, in_shardings=shards, out_shardings=out_shards,
                       donate_argnums=donate)
        state_s = jax.device_put(state, shards[0])
        st2, m = step(state_s, batch_s)
        # decode cell
        drun = RunConfig(model=cfg, shape=ShapeConfig("d", "decode", 32, 8),
                         rules="serve")
        with ctx_bind(mesh, rules_for(drun)):
            fn, dargs, dshards, dout, ddonate = abstract_cell(model, drun, mesh)
            lowered = jax.jit(fn, in_shardings=dshards, out_shardings=dout,
                              donate_argnums=ddonate).lower(*dargs)
            compiled = lowered.compile()

    err = abs(float(loss_s) - float(ref_loss))
    status = "OK " if err < 2e-2 else "FAIL"
    print(f"{status} {name:24s} ref={float(ref_loss):.4f} "
          f"sharded={float(loss_s):.4f} err={err:.2e} "
          f"step_loss={float(m['loss']):.4f}")
print("DONE")
