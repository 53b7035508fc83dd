"""Integration tests: sharded-vs-single-device parity (the dual-environment
methodology applied to the framework itself), end-to-end train/resume, the
serving engine, and launch-script emission.  Multi-device cases run in
subprocesses so the main test process keeps the real single-device view."""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.verify import BF16_LOGITS_TOL, rel_l2

REPO = Path(__file__).resolve().parents[1]


def _sub(code: str) -> subprocess.CompletedProcess:
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, cwd=REPO)


@pytest.mark.parametrize("arch", ["phi3-mini-3.8b", "qwen3-moe-30b-a3b",
                                  "mamba2-2.7b", "zamba2-2.7b",
                                  "whisper-medium", "llama-3.2-vision-11b"])
def test_sharded_loss_parity(arch):
    """Loss under the production rule set on a (2,2,2) pod×data×model mesh
    must equal the single-device loss (the paper's native == container)."""
    out = _sub(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, "src")
        import jax
        from repro.configs import ALL_ARCHS, reduced, ShapeConfig
        from repro.configs.base import RunConfig, TrainConfig
        from repro.launch.bind import batch_shardings, param_shardings
        from repro.models import build
        from repro.parallel import bind, rules_for
        from repro.launch.mesh import mesh_of
        mesh = mesh_of((2, 2, 2), ("pod", "data", "model"))
        cfg = reduced(ALL_ARCHS["{arch}"])
        model = build(cfg)
        shape = ShapeConfig("t", "train", 32, 4)
        key = jax.random.PRNGKey(0)
        params = model.init_params(key)
        batch = model.sample_batch(shape, key)
        ref, _ = jax.jit(lambda p, b: model.loss(p, b))(params, batch)
        run = RunConfig(model=cfg, shape=shape)
        with bind(mesh, rules_for(run)):
            ps = jax.device_put(params, param_shardings(model, mesh))
            bs = jax.device_put(batch, batch_shardings(model, shape, mesh))
            sh, _ = jax.jit(lambda p, b: model.loss(p, b))(ps, bs)
        err = abs(float(sh) - float(ref))
        assert err < 2e-2, (float(ref), float(sh))
        print("PARITY", err)
    """)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PARITY" in out.stdout


def test_train_checkpoint_restart_bitexact(tmp_path):
    """Kill-and-restart must continue the loss curve exactly: train 8 steps
    in one run vs 4 + resume 4 (same data, same final loss)."""
    from repro.launch.train import train

    r_full = train("granite-moe-1b-a400m", steps=8, ckpt_every=4,
                   out_dir=str(tmp_path / "full"), seed=3, total_steps=8)
    r_half = train("granite-moe-1b-a400m", steps=4, ckpt_every=4,
                   out_dir=str(tmp_path / "resume"), seed=3, total_steps=8)
    r_res = train("granite-moe-1b-a400m", steps=8, ckpt_every=4,
                  out_dir=str(tmp_path / "resume"), resume=True, seed=3,
                  total_steps=8)
    assert r_res["last_loss"] == pytest.approx(r_full["last_loss"], rel=1e-4)
    assert r_full["loss_decreased"]


def test_serve_engine_continuous_batching():
    from repro.launch.serve import serve

    res = serve("granite-moe-1b-a400m", reduced=True, n_requests=5, slots=2,
                max_len=64, max_new=8)
    assert res["served"] == 5
    assert res["tokens_out"] >= 5 * 8 - 5
    assert 1.0 <= res["mean_batch_occupancy"] <= 2.0


def test_paged_engine_matches_contiguous_oracle():
    """The paged engine's correctness proof: on a batch of
    overlapping-prefix prompts, the paged path (prefix-cache reuse +
    chunked prefill + paged admission) must produce exactly the greedy
    token streams of the seed contiguous engine, asserted through a
    core.verify dual-environment verdict — the same methodology the paper
    uses for native-vs-container parity."""
    from repro.configs import ALL_ARCHS, reduced
    from repro.models import build
    from repro.serve.engine import PagedServeEngine, Request, compare_engines

    cfg = reduced(ALL_ARCHS["deepseek-7b"])
    model = build(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    shared = rng.integers(0, cfg.vocab_size, size=18).tolist()
    tails = [rng.integers(0, cfg.vocab_size, size=4 + i).tolist()
             for i in range(4)]

    def make():
        return [Request(rid=i, prompt=shared + tails[i], max_new=8)
                for i in range(4)]

    report = compare_engines(model, params, make, slots=2, max_len=64,
                             block_size=8, chunk=4)
    assert report.ok, report.summary()
    [verdict] = report.verdicts
    assert verdict.kind == "numeric" and verdict.measured == 0.0

    # the parity must come with actual page reuse, not a degenerate cache
    eng = PagedServeEngine(model, params, slots=2, max_len=64,
                           block_size=8, chunk=4)
    eng.run(make())
    assert eng.pstats.cached_tokens > 0
    assert eng.report()["prefix_hit_rate"] > 0
    eng.alloc.check()
    # and the production default is the page-table kernel pathway: KV
    # lives in the device page pool, no dense working cache, no host pool
    assert eng.report()["kernel"] == "paged"
    assert eng.pool is None and "paged" in eng.cache


def test_kernel_and_gather_pathways_both_match_oracle():
    """The oracle holds with the KV pathway pinned explicitly either way
    (engine_kwargs passthrough): the Pallas page-table mode and the dense
    gather fallback each reproduce the contiguous streams, greedy and
    sampled — the ISSUE's end-to-end kernel-enabled oracle."""
    from repro.configs import ALL_ARCHS, reduced
    from repro.models import build
    from repro.serve import SamplingParams
    from repro.serve.engine import Request, compare_engines

    cfg = reduced(ALL_ARCHS["deepseek-7b"])
    model = build(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(11)
    shared = rng.integers(0, cfg.vocab_size, size=16).tolist()
    tails = [rng.integers(0, cfg.vocab_size, size=3 + i).tolist()
             for i in range(4)]

    def make():
        return [Request(rid=i, prompt=shared + tails[i], max_new=6)
                for i in range(4)]

    sampled = SamplingParams(temperature=0.8, top_k=16, top_p=0.9, seed=2)
    for kernel in ("paged", "gather"):
        for sp in (None, sampled):
            report = compare_engines(
                model, params, make, slots=2, max_len=64, block_size=8,
                chunk=4, sampling=sp,
                engine_kwargs={"paged": {"kernel": kernel}})
            assert report.ok, (kernel, sp, report.summary())


def test_decode_matches_prefill_continuation():
    """Greedy continuation via decode_step must match re-running prefill
    over the extended sequence (cache correctness, all families with
    attention caches rely on the same path — dense covers it)."""
    from repro.configs import ALL_ARCHS, reduced, ShapeConfig
    from repro.models import build

    cfg = reduced(ALL_ARCHS["deepseek-7b"])
    model = build(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init_params(key)
    s = 12
    batch = model.sample_batch(ShapeConfig("p", "prefill", s, 2), key)

    logits, cache = jax.jit(
        lambda p, b: model.prefill(p, b, cache_len=s + 4))(params, batch)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    dec_logits, _ = jax.jit(model.decode_step)(
        params, cache, tok, jnp.full((2,), s, jnp.int32))

    ext = jnp.concatenate([batch["tokens"], tok], axis=1)
    ref = jax.jit(model.reference_prefill)(params, ext)
    bf16 = jax.jit(lambda p, t: model.prefill(p, {"tokens": t}))(params, ext)
    # Both bf16 paths are judged against the float32 reference, not
    # against each other: they round at different points (prefill rounds
    # softmax weights to bf16 before PV, decode keeps them f32), so an
    # elementwise band between the two sits in the tail of two error
    # distributions (the old rtol=atol=3e-2 band failed 2 of 1024 logits
    # by 0.011).  Bound: relative L2 error per row <= BF16_LOGITS_TOL
    # (3e-2, reason at its definition).
    for got in (dec_logits, bf16[0]):
        v = cfg.vocab_size
        assert rel_l2(np.asarray(got)[:, :v],
                      np.asarray(ref)[:, :v]) <= BF16_LOGITS_TOL


def test_slurm_script_emission(tmp_path):
    from repro.launch.slurm import emit_all, emit_sbatch

    p = emit_sbatch("phi3-mini-3.8b", "train_4k", nodes=64,
                    container_image="esd.sif", out_dir=tmp_path)
    text = p.read_text()
    assert "apptainer exec --nv esd.sif" in text
    assert "REPRO_COORD_PORT" in text
    assert "--nodes=64" in text

    paths = emit_all(out_dir=tmp_path)
    assert len(paths) == 32  # every applicable assignment cell


def test_dryrun_cell_smoke_via_subprocess():
    """One real dry-run cell end to end through the CLI (production mesh,
    512 placeholder devices, multi-pod)."""
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "granite-moe-1b-a400m", "--shape", "decode_32k", "--multi-pod",
         "--out", "/tmp/dryrun_pytest"],
        capture_output=True, text=True, cwd="/root/repo",
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root"})
    assert out.returncode == 0, (out.stdout[-1000:], out.stderr[-2000:])
    assert "[ok   ]" in out.stdout
    rec = json.loads(next(Path("/tmp/dryrun_pytest").glob("*.json")).read_text())
    assert rec["mesh"] == "2x16x16"
    assert rec["collectives"]["total_moved_bytes"] > 0
    assert rec["hlo_cost"]["dot_flops"] > 0


def test_reference_prefill_positions_match_truncated_prefills():
    """``reference_prefill(tokens, positions)`` returns, for each row,
    the logits a cache-free forward over that row's prefix ending at the
    position gives: right padding never reaches back (causal)."""
    from repro.configs import ALL_ARCHS, reduced
    from repro.models import build

    cfg = reduced(ALL_ARCHS["phi3-mini-3.8b"], n_layers=2)
    model = build(cfg)
    params = model.init_params(jax.random.PRNGKey(1))
    rng = np.random.default_rng(2)
    tokens = rng.integers(0, cfg.vocab_size, size=(2, 12)).astype(np.int32)
    positions = np.array([[3, 7], [5, 11]], np.int32)
    got = np.asarray(jax.jit(model.reference_prefill)(
        params, jnp.asarray(tokens), jnp.asarray(positions)))
    assert got.shape == (2, 2, cfg.padded_vocab) and got.dtype == np.float32
    for b in range(2):
        for k in range(2):
            p = int(positions[b, k])
            want = np.asarray(model.reference_prefill(
                params, jnp.asarray(tokens[b:b + 1, :p + 1])))[0]
            np.testing.assert_allclose(got[b, k], want, rtol=1e-5, atol=1e-5)


def test_jitted_init_draws_the_eager_values():
    """Weights are generated leaf by leaf under jit (no float32
    temporary beside the weights at published widths) and equal the
    eager draw bit for bit at the same key."""
    from repro.models import params as P

    spec = P.dense(64, 96, "embed", "mlp", layers=3)
    key = jax.random.PRNGKey(7)
    eager = (jax.random.normal(key, spec.shape, jnp.float32)
             * 64 ** -0.5).astype(spec.dtype)
    got = P._init_one(spec, key)
    assert got.dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(eager, np.float32))
