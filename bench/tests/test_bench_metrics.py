"""Metric readers on hand-made window records."""
from types import SimpleNamespace as NS

import pytest

from bench import spec
from bench.families import dense
from bench.tests.tiny import BENCH


def reader(name):
    return spec.load_module(BENCH / "metrics" / f"{name}.py")


def test_itl_samples_span_a_quarter_second():
    itl = reader("itl_p90_ms")
    stamps = [0.1 * i for i in range(11)]          # one token a 100 ms
    assert itl.samples(stamps) == pytest.approx([0.1] * 3)
    # a stall of 0.5 s is one sample on its own
    assert itl.samples([0.0, 0.1, 0.6, 0.7, 0.8, 0.9]) == pytest.approx(
        [0.3, 0.1])
    assert itl.samples([0.0, 0.1]) == []


def _window(recs=(), steps=(), **kw):
    w = NS(t0=0.0, t1=10.0, recs={i: r for i, r in enumerate(recs)},
           length=10.0, slots=8, **kw)
    w.window_steps = lambda: [s for s in steps
                              if w.t0 <= s.start and s.end <= w.t1]
    return w


def test_occupancy_and_step_time_read_the_window_steps():
    steps = [NS(start=-1.0, end=-0.5, lanes=1),      # before the window
             NS(start=0.0, end=0.5, lanes=8),
             NS(start=0.5, end=1.5, lanes=4)]
    w = _window(steps=steps)
    assert reader("batch_occupancy").read(w) == pytest.approx(75.0)
    assert reader("step_ms").read(w) == pytest.approx(750.0)
    assert reader("batch_occupancy").read(_window()) is None


def test_tokens_per_second_and_idle_share():
    recs = [NS(tokens=[-1.0, 1.0, 2.0, 11.0]), NS(tokens=[5.0])]
    w = _window(recs, trace=NS(busy_s=7.5, window_s=10.0))
    assert reader("output_tokens_per_s").read(w) == pytest.approx(0.3)
    assert reader("device_idle_share").read(w) == pytest.approx(25.0)
    assert reader("device_idle_share").read(_window(trace=None)) is None


CFG = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
       "intermediate_size": 128, "vocab_size": 500, "num_hidden_layers": 2}
PEAK = NS(bf16_flops=1e12, hbm_bw=1e9)


def test_device_readers_divide_the_work_by_trace_time():
    steps = [NS(start=0.0, end=1.0, lanes=2, work=[(10, 1), (40, 8)])]
    trace = NS(window_s=2.0, time_of=lambda marks: 0.5)
    w = _window(steps=steps, config=CFG, family=dense, peak=PEAK,
                trace=trace)
    flops = reader("step_mfu").step_flops(dense, CFG, steps[0].work)
    assert reader("step_mfu").read(w) == pytest.approx(
        100 * flops / (2.0 * 1e12))
    need = max(dense.paged_attn_flops(CFG, steps[0].work) / 1e12,
               dense.paged_attn_bytes(CFG, steps[0].work) / 1e9)
    assert reader("paged_attn_roofline").read(w) == pytest.approx(
        100 * need / 0.5)
    # no kernel in the trace, or no traced step: no reading, never 0
    w.trace = NS(window_s=2.0, time_of=lambda marks: 0.0)
    assert reader("paged_attn_roofline").read(w) is None
    steps[0].work = None
    assert reader("step_mfu").read(w) is None
