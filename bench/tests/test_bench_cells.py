"""Whole runs of the tiny cells on the CPU (``tiny.py``: a dense one, and
an MoE one whose family and reference are files added to the root):
parts are found by name, a sound run is correct, and a run whose timed
path is broken underneath, or the fp8 control in the program's place,
is not.  The harness's look for a chip is the only part skipped
(``bench/run.py`` makes it; see ``test_run_refuses_without_a_tpu``)."""
import json
import os
import re
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import check, harness, program, spec
from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**31 + 7
SECONDS = 1.0


def run(root, workload, **kw):
    cell = spec.load(root, workload, False)
    return harness.run(cell, SEED, SECONDS, False, time.perf_counter(), **kw)


def test_parts_are_found_by_name(tmp_path):
    root = tiny.make_root(tmp_path, metrics=("setup_s", "tokens_seen"))
    (root / "bench" / "metrics" / "tokens_seen.py").write_text(
        "def read(w):\n    return float(sum(len(r.tokens) "
        "for r in w.recs.values()))\n")
    cell = spec.load(root, "tiny.backlog", False)
    assert cell.config["name"] == "tiny"
    assert cell.mix["arrivals"] == "backlog"
    assert set(cell.readers) == {"setup_s", "tokens_seen"}
    assert cell.reference.__name__.endswith("phi3")
    assert cell.family.__name__.endswith("dense")
    out = run(root, "tiny.backlog")
    assert out["metrics"]["tokens_seen"]["value"] > 0
    with pytest.raises(KeyError):
        spec.load(root, "tiny.missing", False)


def test_an_unknown_family_fails_with_the_path_it_looked_for(tmp_path):
    root = tiny.make_root(tmp_path)
    file = root / "bench" / "configs" / "tiny.json"
    file.write_text(json.dumps(dict(tiny.CONFIG, family="sparse")))
    want = root / "bench" / "families" / "sparse.py"
    with pytest.raises(FileNotFoundError, match=re.escape(str(want))):
        spec.load(root, "tiny.backlog", False)


def test_metrics_of_a_cell_follow_their_workloads_key(tmp_path):
    root = tiny.make_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append(dict(bench["workloads"][0], name="tiny.other"))
    bench["per_layer"] = [
        {"name": "step_ms", "unit": "ms", "workloads": ["tiny.other"]},
        {"name": "batch_occupancy", "unit": "%"}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    assert set(spec.load(root, "tiny.backlog", True).readers) == {
        "batch_occupancy"}
    assert set(spec.load(root, "tiny.other", True).readers) == {
        "step_ms", "batch_occupancy"}


@pytest.mark.parametrize("cell", list(tiny.CELLS))
def test_a_sound_run_is_correct(tmp_path, cell):
    served: list = []
    out = run(tiny.make_root(tmp_path), cell, keep=served)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert out["checks"]["compared_tokens"]["value"] >= check.MIN_COMPARED
    m = out["metrics"]
    assert m["output_tokens_per_s"]["value"] > 0
    assert m["setup_s"]["value"] > 0
    # every request served a token is compared: the in-flight lanes (the
    # first four, each prefilled with part of its answer) and the
    # window's own admissions
    assert {s.rid for s in served} >= {0, 1, 2, 3}
    assert len(served) > tiny.CELLS[cell]["serving"]["slots"]
    # every served token is compared, but where the reference leaves a
    # position undecided: the MoE reference at its router's near-ties
    tokens = sum(len(s.out) for s in served)
    undecided = tokens - out["checks"]["compared_tokens"]["value"]
    if tiny.CELLS[cell]["reference"] == "phi3":
        assert undecided == 0
    else:
        assert 0 <= undecided < tokens // 4


def _broken(monkeypatch, fault):
    """Build engines whose jitted step is broken underneath."""
    build = program.build_engine

    def build_broken(model, params, serving):
        eng = build(model, params, serving)
        step = eng._chunk_fn.fn
        calls = [0]

        def broken(*args):
            calls[0] += 1
            if fault == "half":
                # half of the lanes left out: their tokens never computed
                sampled, logits, cache = step(*args)
                half = sampled.shape[0] // 2
                return sampled.at[half:].set(0), logits, cache
            if fault == "state":
                # the step returns the pool it was given: no KV written
                keep = jax.tree.map(jnp.copy, args[1])
                sampled, logits, _ = step(*args)
                return sampled, logits, keep
            sampled, logits, cache = step(*args)
            if calls[0] % 3 == 0:      # a token altered where produced
                sampled = (sampled + 1) % model.cfg.vocab_size
            return sampled, logits, cache

        eng._chunk_fn.fn = broken
        return eng

    monkeypatch.setattr(program, "build_engine", build_broken)


@pytest.mark.parametrize("cell", list(tiny.CELLS))
@pytest.mark.parametrize("fault", ["state", "half", "token"])
def test_a_broken_step_is_not_correct(tmp_path, monkeypatch, fault, cell):
    _broken(monkeypatch, fault)
    out = run(tiny.make_root(tmp_path), cell)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", list(tiny.CELLS))
def test_the_fp8_control_is_not_correct(tmp_path, cell):
    # the MoE cell's control fails its share_over_gap, not its widest gap
    # (tiny.MOE_CONFIG's limits say why)
    root = tiny.make_root(tmp_path)
    served: list = []
    out = run(root, cell, keep=served)
    loaded = spec.load(root, cell, False)
    ctl = check.judge(loaded.reference, loaded.config, SEED, served,
                      quantize=check.fp8_weights)
    assert out["correct"] and not check.passed(ctl), (out["checks"], ctl)
    assert ctl["compared_tokens"] == {
        "value": out["checks"]["compared_tokens"]["value"],
        "limit": check.MIN_COMPARED, "at_most": False}


def _cli(cwd, *extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "phi3-mini.decode-batch", "--seed", "1", "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=120)


def test_run_refuses_without_a_tpu():
    res = _cli(ROOT)
    assert res.returncode != 0
    assert "TPU" in res.stderr and res.stdout.strip() == ""


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = _cli(tmp_path)
    assert res.returncode != 0 and res.stdout.strip() == ""
