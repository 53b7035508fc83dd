"""The afmoe family through whole runs of a tiny cell on the CPU
(``afmoe_tiny.py``): a sound run is correct; the fp8 control in the
program's place, and each step that leaves out one of the layer's
mechanisms (the window, sigmoid routing, the shared expert, the output
gate), are not.  Then the family's work counts."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import pytest

from bench import check, harness, spec
from bench.families import afmoe as family
from bench.tests import afmoe_tiny

SEED = 2**31 + 11
SECONDS = 1.0


def run(root, cell=None, **kw):
    cell = cell or spec.load(root, afmoe_tiny.CELL, False)
    return harness.run(cell, SEED, SECONDS, False, time.perf_counter(), **kw)


def test_a_sound_run_is_correct(tmp_path):
    served: list = []
    out = run(afmoe_tiny.make_root(tmp_path), keep=served)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["compared_tokens"]["value"] >= check.MIN_COMPARED
    # past the window: some served context is longer than it
    window = afmoe_tiny.CONFIG["sliding_window"]
    assert max(len(s.prompt) + len(s.out) for s in served) > 3 * window
    tokens = sum(len(s.out) for s in served)
    undecided = tokens - out["checks"]["compared_tokens"]["value"]
    assert 0 <= undecided < tokens // 3


def test_the_fp8_control_is_not_correct(tmp_path):
    root = afmoe_tiny.make_root(tmp_path)
    served: list = []
    out = run(root, keep=served)
    cell = spec.load(root, afmoe_tiny.CELL, False)
    ctl = check.judge(cell.reference, cell.config, SEED, served,
                      quantize=check.fp8_weights)
    assert out["correct"] and not check.passed(ctl), (out["checks"], ctl)


def _softmax_route(cfg, p, x):
    """Routing by the softmax of the router's logits, the bias unread."""
    probs = jax.nn.softmax(x.astype(jnp.float32) @ p["router"], axis=-1)
    top_w, top_i = jax.lax.top_k(probs, cfg.top_k)
    return top_i, top_w / jnp.sum(top_w, -1, keepdims=True) * cfg.route_scale


def _break(monkeypatch, cell, fault):
    """Make the cell's program leave out one mechanism."""
    from repro.models import moe

    model_config, draw = cell.family.model_config, cell.family.draw
    if fault == "window":
        monkeypatch.setattr(cell.family, "model_config", lambda c: (
            dataclasses.replace(model_config(c), sliding_window=1 << 20)))
    elif fault == "softmax":
        monkeypatch.setattr(moe, "route", _softmax_route)
    elif fault == "shared":
        monkeypatch.setattr(moe, "swiglu",
                            lambda p, x: jnp.zeros_like(x))
    elif fault == "gate":
        def no_gate(reference, cfg, want):
            make = draw(reference, cfg, want)

            def tree(key):
                t = make(key)
                for stack in ("dense_layers", "layers"):
                    del t[stack]["attn"]["wg"]
                return t
            return tree

        monkeypatch.setattr(cell.family, "model_config", lambda c: (
            dataclasses.replace(model_config(c), attn_gate=False)))
        monkeypatch.setattr(cell.family, "draw", no_gate)


@pytest.mark.parametrize("fault", ["window", "softmax", "shared", "gate"])
def test_a_step_without_a_mechanism_is_not_correct(tmp_path, monkeypatch,
                                                   fault):
    cell = spec.load(afmoe_tiny.make_root(tmp_path), afmoe_tiny.CELL, False)
    _break(monkeypatch, cell, fault)
    out = run(None, cell)
    assert not out["correct"], out["checks"]


CFG = afmoe_tiny.CONFIG


def test_a_windowed_layer_counts_the_window_alone():
    lanes = [(100, 1), (5, 8)]
    one_layer = dict(CFG, num_hidden_layers=1)
    full = dict(one_layer, layer_types=["full_attention"])
    h, hd, kv = 8, 16, 2
    # 100 + 1 keys whole, 16 windowed; a chunk of 8 at 5 sees 6..13 keys
    # both ways (every row's context is under the window)
    assert family.paged_attn_flops(full, lanes) == 4 * h * hd * (101 + 76)
    assert family.paged_attn_flops(one_layer, lanes) == 4 * h * hd * (16 + 76)
    # K/V rows read: 101 whole, 16 from the window's first row on; 13 for
    # the chunk either way; queries and outputs of the 9 fresh rows
    assert family.paged_attn_bytes(full, lanes) == 2 * hd * (
        2 * kv * (101 + 13) + 2 * h * 9)
    assert family.paged_attn_bytes(one_layer, lanes) == 2 * hd * (
        2 * kv * (16 + 13) + 2 * h * 9)
    # an idle lane counts nothing
    assert family.paged_attn_bytes(CFG, [(50, 0)]) == 0


def test_the_expert_counts_follow_the_share():
    d, f, fe = 64, 128, 32
    attn = 3 * d * 8 * 16 + 2 * d * 2 * 16
    # one held expert in 16 takes a row with chance 8 / 16: 4 held, 2 a row
    moe = d * 16 + 3 * d * fe + 2 * 3 * d * fe
    assert family.matmul_params(CFG) == 8 * attn + 2 * 3 * d * f + 6 * moe
    lanes = [(30, 1), (40, 1), (0, 0)]
    assert family.moe_ffn_flops(CFG, lanes) == 6 * 2 * 2 * 6 * d * fe
    hit = 4 * (1 - 0.5 ** 2)
    assert family.moe_ffn_bytes(CFG, lanes) == round(
        6 * 2 * (hit * 3 * d * fe + 2 * 2 * 2 * d))
    assert family.moe_ffn_flops(CFG, [(3, 0)]) == 0
    assert family.longest_context(CFG, CFG["serving"]) == 95


def test_the_program_pattern_is_the_files():
    mc = family.model_config(CFG)
    assert mc.layer_windows() == (16, 16, 16, 0) * 2
    assert (mc.held_experts, mc.expert_offset, mc.n_experts) == (4, 4, 16)
    bad = dict(CFG, layer_types=["full_attention"] * 8)
    with pytest.raises(ValueError, match="layer_types"):
        family.model_config(bad)


def test_moe_ffn_roofline_reads_the_marked_expert_loops():
    """The loops marked ``moe_experts`` are timed once each; the
    operations inside them, and unmarked loops, are not counted."""
    from types import SimpleNamespace as NS

    from bench.tests.tiny import BENCH

    reader = spec.load_module(BENCH / "metrics" / "moe_ffn_roofline.py")
    mark = 'frontend_attributes={scope="moe_experts"}'
    ops = {
        f"%while.61 = (s32[], f32[1024,2048]) while(%tuple.3), {mark}": 0.4,
        f"%fusion.9 = bf16[128,1024] fusion(%p), kind=kLoop, {mark}": 0.3,
        f"%while.62 = f32[1024,2048] get-tuple-element(%while.61), {mark}":
            0.1,
        "%while.57 = (s32[], bf16[32,32,2048]) while(%tuple.1)": 5.0,
    }
    steps = [NS(start=0.0, end=1.0, lanes=2, work=[(100, 1), (200, 1)])]
    w = NS(t0=0.0, t1=10.0, config=CFG, family=family,
           peak=NS(bf16_flops=1e12, hbm_bw=1e9),
           trace=NS(ops=ops, labels={}))
    w.window_steps = lambda: steps
    need = max(family.moe_ffn_flops(CFG, steps[0].work) / 1e12,
               family.moe_ffn_bytes(CFG, steps[0].work) / 1e9)
    assert reader.read(w) == pytest.approx(100 * need / 0.4)
    # a program without the mark, or a family without the counts
    w.trace = NS(ops={k: v for k, v in ops.items() if "61 =" not in k},
                 labels={})
    assert reader.read(w) is None
    w.family = NS()
    assert reader.read(w) is None
