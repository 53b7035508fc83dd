"""Tests for the paper-core layer: manifest, inspector (including the §8
diagnostic-tool claim: seeded misconfigurations must be detected), verify,
bootstrap, diagnostics."""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_ARCHS, SHAPES
from repro.configs.base import TrainConfig
from repro.core import (Diagnostics, DualEnvHarness, Manifest, PortableEnv,
                        WireUp, constant_vs_scaling_overhead, diff,
                        init_benchmark, parse_hlo)
from repro.core.inspector import hlo_cost
from repro.launch.mesh import mesh_of


# ------------------------------------------------------------ manifest


def test_manifest_roundtrip_and_hash_stability():
    env = PortableEnv.capture(ALL_ARCHS["phi3-mini-3.8b"], SHAPES["train_4k"])
    m = Manifest(env)
    m2 = Manifest.from_json(m.to_json())
    assert m2.portable.image_hash == env.image_hash
    # identical capture -> identical hash (the image is content-addressed)
    env2 = PortableEnv.capture(ALL_ARCHS["phi3-mini-3.8b"], SHAPES["train_4k"])
    assert env2.image_hash == env.image_hash


def test_manifest_diff_classifies_portable_vs_host():
    a = Manifest(PortableEnv.capture(ALL_ARCHS["deepseek-7b"], SHAPES["train_4k"]))
    b = Manifest(PortableEnv.capture(ALL_ARCHS["deepseek-7b"], SHAPES["decode_32k"]))
    lines = diff(a, b)
    assert any("portable.shape" in line for line in lines)

    mesh = mesh_of((1, 1), ("data", "model"))
    a.bind(mesh)
    b2 = Manifest.from_json(a.to_json())
    assert diff(a, b2) == []


def test_manifest_attestation_detects_program_change():
    env = PortableEnv.capture(ALL_ARCHS["deepseek-7b"], SHAPES["train_4k"])
    a = Manifest(env).attest(hlo_text="HloModule A ...")
    b = Manifest(env).attest(hlo_text="HloModule B ...")
    lines = diff(a, b)
    assert any("hlo_fingerprint" in line and "UNEXPECTED" in line
               for line in lines)


# ------------------------------------------------------------ inspector


def _lower_hlo(fn, *args, n_dev=8):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_inspector_finds_collectives_in_real_module():
    """Compile a genuinely sharded program on a tiny in-process mesh and
    check the inspector sees its collectives."""
    import subprocess, sys, textwrap
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        import sys; sys.path.insert(0, "src")
        from repro.core.inspector import parse_hlo
        from repro.launch.mesh import mesh_of
        mesh = mesh_of((8,), ("d",))
        x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        f = lambda x, w: (x @ w).sum()
        lowered = jax.jit(f, in_shardings=(NamedSharding(mesh, P("d", None)),
                                           NamedSharding(mesh, P(None, "d")))
                          ).lower(x, w)
        hlo = lowered.compile().as_text()
        rep = parse_hlo(hlo, 8)
        kinds = set(op.kind for op in rep.ops)
        assert len(rep.ops) >= 1, hlo[:500]
        print("KINDS", sorted(kinds))
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd="/root/repo")
    assert out.returncode == 0, out.stderr[-2000:]
    assert "KINDS" in out.stdout


def test_inspector_flags_monolithic_all_reduce():
    """§8 claim: a seeded pathway misconfiguration must be detected."""
    hlo = """HloModule bad

ENTRY %main (a: f32[268435456]) -> f32[268435456] {
  %a = f32[268435456] parameter(0)
  ROOT %ar = f32[268435456] all-reduce(%a), replica_groups={{0,1,2,3,4,5,6,7,8,9,10,11,12,13,14,15}}, to_apply=%add
}
"""
    rep = parse_hlo(hlo, 16)
    assert any(f["kind"] == "monolithic-all-reduce" for f in rep.findings)


def test_inspector_flags_host_transfer():
    hlo = """HloModule ht
ENTRY %main () -> f32[1] {
  %tok = token[] after-all()
  %o = token[] outfeed(%c, %tok)
}
"""
    rep = parse_hlo(hlo, 1)
    assert any(f["kind"] == "host-transfer" for f in rep.findings)


def test_hlo_cost_counts_dot_flops():
    hlo = _lower_hlo(lambda a, b: a @ b,
                     jax.ShapeDtypeStruct((128, 256), jnp.float32),
                     jax.ShapeDtypeStruct((256, 64), jnp.float32))
    cost = hlo_cost(hlo)
    expect = 2 * 128 * 256 * 64
    assert abs(cost["dot_flops"] - expect) / expect < 1e-6


def test_hlo_cost_scan_trips():
    def f(x, ws):
        return jax.lax.scan(lambda c, w: (jnp.tanh(c @ w), None), x, ws)[0]

    hlo = _lower_hlo(f, jax.ShapeDtypeStruct((64, 64), jnp.float32),
                     jax.ShapeDtypeStruct((12, 64, 64), jnp.float32))
    cost = hlo_cost(hlo)
    expect = 12 * 2 * 64**3
    assert abs(cost["dot_flops"] - expect) / expect < 0.01


# ------------------------------------------------------------- verify


def test_dual_env_agreement_and_divergence():
    h = DualEnvHarness(repeats=2, warmup=0)
    x = np.linspace(0, 1, 64)
    rep = h.compare("native", lambda: np.sin(x),
                    "container", lambda: np.sin(x) + 1e-9)
    assert rep.ok

    rep_bad = h.compare("native", lambda: np.sin(x),
                        "container", lambda: np.sin(x) * 1.5)
    assert not rep_bad.ok


def test_overhead_classification():
    # the paper's GPU-Arbor case: constant 17% at all scales
    assert constant_vs_scaling_overhead({1: 0.17, 8: 0.166, 64: 0.17}) \
        == "constant-overhead"
    # a communication penalty grows with scale
    assert constant_vs_scaling_overhead({1: 0.05, 8: 0.2, 64: 0.8}) \
        == "scaling-overhead"
    assert constant_vs_scaling_overhead({1: 0.001, 64: 0.01}) == "negligible"


# ------------------------------------------------------------ bootstrap


def test_wireup_from_slurm_env(monkeypatch):
    monkeypatch.setenv("SLURM_NTASKS", "128")
    monkeypatch.setenv("SLURM_PROCID", "7")
    monkeypatch.setenv("SLURM_STEP_NODELIST", "nid[001-032]")
    w = WireUp.from_env()
    assert w.num_processes == 128 and w.process_id == 7
    assert w.coordinator.startswith("nid")
    assert w.is_distributed


def test_init_benchmark_single_device():
    out = init_benchmark((1, 1), ("data", "model"), repeats=1)
    assert out["mesh_construct_s"] >= 0
    assert out["first_collective_s"] > 0


# ---------------------------------------------------------- diagnostics


def test_diagnostics_gate():
    d = Diagnostics()
    d.extend([{"severity": "info", "kind": "x", "detail": ""}], "t")
    assert d.gate()
    d.extend([{"severity": "error", "kind": "y", "detail": ""}], "t")
    assert not d.gate()
    assert d.worst == "error"
    assert "error" in d.render()


# ------------------------------------------- logits verdict, peaks, cache


def _rows(tokens, *, v=6, seed=0):
    """One logits row per token whose argmax is that token."""
    rng = np.random.default_rng(seed)
    rows = []
    for t in tokens:
        r = rng.standard_normal(v)
        r[t] = r.max() + 1.0
        rows.append(r)
    return rows


def test_logits_verdict_accepts_streams_parting_at_a_tie():
    from repro.core.verify import logits_verdict

    a_rows = _rows([1, 2, 3])
    b_rows = [r.copy() for r in a_rows]
    # the last row: tokens 3 and 4 within 1e-4 of each other, swapped
    a_rows[2][4] = a_rows[2][3] - 1e-4
    b_rows[2][4] = b_rows[2][3] + 1e-4
    a = {0: ([1, 2, 3], a_rows)}
    b = {0: ([1, 2, 4], b_rows)}
    v = logits_verdict(a, b, tol=1e-3)
    assert v.ok and v.kind == "logits" and "1 stream" in v.detail
    # the same parting with rows further apart than the limit fails
    b_rows[2] = b_rows[2] + 1.0
    assert not logits_verdict(a, b, tol=1e-3).ok


def test_logits_verdict_rejects_non_argmax_tokens_and_missing_requests():
    from repro.core.verify import logits_verdict

    rows = _rows([1, 2])
    a = {0: ([1, 2], rows)}
    # same rows, but run b emitted a token that is not its row's argmax
    v = logits_verdict(a, {0: ([1, 5], rows)}, tol=1e-3)
    assert not v.ok and "argmax" in v.detail
    assert not logits_verdict(a, {}, tol=1e-3).ok
    assert logits_verdict(a, {0: ([1, 2], [r + 1e-6 for r in rows])},
                          tol=1e-3).ok


def test_rel_l2_is_per_row_and_scale_free():
    from repro.core.verify import rel_l2

    want = np.array([[1.0, 0.0], [0.0, 100.0]])
    got = np.array([[1.0, 0.1], [0.0, 100.0]])
    assert rel_l2(got, want) == pytest.approx(0.1)
    assert rel_l2(10 * got, 10 * want) == pytest.approx(0.1)


def test_peaks_table_is_keyed_by_device_kind_and_refuses_unknowns():
    from repro.core.manifest import HostBinding
    from repro.core.peaks import chip_peaks

    v5e = chip_peaks("TPU v5 lite")
    assert (v5e.bf16_flops, v5e.hbm_bw) == (197e12, 819e9)
    assert "TPU v5e" in v5e.source
    with pytest.raises(KeyError, match="no published peaks"):
        chip_peaks("cpu")
    binding = HostBinding(device_kind="cpu", n_devices=1, mesh_shape=(1,),
                          mesh_axes=("data",))
    with pytest.raises(KeyError):
        binding.peaks
    # the binding carries no borrowed default peaks
    assert not hasattr(HostBinding, "peak_flops")


def test_compile_cache_env_wins_else_fixed_checkout_dir(monkeypatch):
    from repro.core import bootstrap

    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        jax.config.update("jax_compilation_cache_dir", None)
        assert bootstrap.setup_compile_cache() == "/elsewhere/cache"
        # JAX's own handling of the variable stands: nothing set in code
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = bootstrap.setup_compile_cache()
        repo = Path(__file__).resolve().parents[1]
        assert path == str(repo / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert ".jax_cache/" in (repo / ".gitignore").read_text()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
