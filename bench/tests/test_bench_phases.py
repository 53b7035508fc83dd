"""The program's spans and counters as the benchmark reads them: on a
hand-built trace, per-phase time, the host time of a step, the device
idle put down to the innermost program span, and the counters' shares;
on the tiny cell, each ``step`` event's ``work`` is the lane work the
harness reads from the engine around the step."""
from types import SimpleNamespace as NS

import jax
import pytest

import repro.serve as serve
from bench import harness, phases, program, spec, traffic
from bench import trace as T
from bench.tests import tiny
from repro.audit.trace import Tracer

E = T.Event


def _host():
    """Two steps inside the window, each under ``bench.step``, with the
    harness's spans between them."""
    host = [E("bench.window", 0.0, 30.0),
            E("bench.submit", 0.0, 1.0), E("bench.step", 1.0, 12.0),
            E("bench.collect", 12.0, 13.0), E("bench.submit", 13.0, 14.0),
            E("bench.step", 14.0, 25.0), E("bench.collect", 25.0, 30.0)]
    for lo in (1.0, 14.0):
        host += [E("serve.step", lo, lo + 11.0),
                 E("serve.schedule", lo, lo + 0.5),
                 E("serve.inputs", lo + 0.5, lo + 1.0),
                 E("serve.dispatch", lo + 1.0, lo + 2.0),
                 E("serve.wait", lo + 2.0, lo + 10.0),
                 E("serve.commit", lo + 10.0, lo + 11.0)]
    return host


# the device runs [2.8, 10.5] of the first step and [15.8, 24.5] of the
# second: idle [0, 2.8], [10.5, 15.8] and [24.5, 30], 13.6 s
DEVICE = {"/device:TPU:0": [E("op", 2.8, 10.5), E("op", 15.8, 24.5)]}


def test_phase_times_and_step_host_time():
    host = _host()
    spans = [e for e in host if e.name.startswith("serve.")]
    times = phases.phase_times(spans, 0.0, 30.0)
    assert times["serve.step"] == pytest.approx(22.0)
    assert times["serve.wait"] == pytest.approx(16.0)
    assert times["serve.dispatch"] == pytest.approx(2.0)
    # 11 s a step, 8 of them waiting
    assert phases.step_host_ms(spans, 0.0, 30.0) == pytest.approx(3000.0)
    # a step cut by the window does not count
    assert phases.step_host_ms(spans, 0.0, 20.0) == pytest.approx(3000.0)
    assert phases.step_host_ms([], 0.0, 30.0) is None
    assert phases.window_of(host) == (0.0, 30.0)
    assert phases.window_of(spans) is None


def test_idle_goes_to_the_innermost_program_span():
    host = _host()
    idle = phases.idle_phases(DEVICE, host)
    r = T.reduce(DEVICE, host)
    # the phases sum to the window's idle
    assert sum(idle.values()) == pytest.approx(r.window_s - r.busy_s)
    assert sum(idle.values()) == pytest.approx(13.6)
    # [0, 2.8]: submit 1 (a benchmark span), schedule .5, inputs .5,
    # dispatch .8 -> the program's dispatch, which overlaps it most
    # [10.5, 15.8]: wait .5, commit 1, collect 1, submit 1, schedule .5,
    # inputs .5, dispatch .8 -> commit
    # [24.5, 30]: commit .5, collect 5 -> commit (a program span first)
    assert idle == pytest.approx({"serve.dispatch": 2.8,
                                  "serve.commit": 5.3 + 5.5})
    # the benchmark's own reading is unchanged beside it
    assert sum(r.idle_by_span.values()) == pytest.approx(sum(idle.values()))


def test_idle_falls_back_to_benchmark_spans_then_other():
    bench_only = [e for e in _host() if e.name.startswith("bench.")]
    idle = phases.idle_phases(DEVICE, bench_only)
    assert idle == pytest.approx({"bench.step": 2.8 + 5.3,
                                  "bench.collect": 5.5})
    bare = [E("bench.window", 0.0, 30.0)]
    assert phases.idle_phases(DEVICE, bare) == pytest.approx({"other": 13.6})
    assert phases.idle_phases({}, _host()) == {}
    assert phases.idle_phases(DEVICE, []) == {}


def test_step_profile_places_a_slow_phase():
    host = _host()
    prof = phases.step_profile(DEVICE, host)
    assert len(prof) == 2
    one = prof[0]
    assert one["length"] == pytest.approx(11.0)
    assert list(one["phases"]) == list(phases.PHASES)
    assert one["phases"]["serve.wait"] == pytest.approx(8.0)
    # the device ran [2.8, 10.5]: .8 of the dispatch [2, 3] and .5 of the
    # wait [3, 11] idle, all of the commit
    assert one["idle"]["serve.dispatch"] == pytest.approx(0.8)
    assert one["idle"]["serve.wait"] == pytest.approx(0.5)
    assert one["idle"]["serve.commit"] == pytest.approx(1.0)
    # its longest device gap opens the step, before the first operation
    assert one["gap"] == pytest.approx({"at": 0.0, "length": 1.8,
                                        "before": None, "after": "op"})
    assert prof[1]["gap"]["length"] == pytest.approx(1.8)
    assert phases.step_profile({}, host) == []


def test_counter_shares_and_their_absence():
    events = [{"work": ((100, 1), (200, 1)), "rows": 16,
               "pages_written": 19, "pages_bound": 30},
              {"work": ((101, 1), (40, 8)), "rows": 16,
               "pages_written": 10, "pages_bound": 30}]
    assert phases.useful_row_share(events) == pytest.approx(100 * 11 / 32)
    assert phases.kv_pages_used_share(events) == pytest.approx(
        100 * 29 / 60)
    # a program whose step events carry no counters, or no events
    bare = [{"lanes": 2, "chunk_sizes": (1, 1)}]
    for read in (phases.useful_row_share, phases.kv_pages_used_share):
        assert read(bare) is None
        assert read([]) is None


def test_step_events_carry_the_lane_work_the_harness_reads(tmp_path,
                                                           monkeypatch):
    """Over admissions, prefill and finishes on the tiny cell, the
    program's own ``work`` for a step is ``Driver._work`` for it, and it
    has a lane for every lane that ran."""
    cell = spec.load(tiny.make_root(tmp_path), "tiny.backlog", True)
    cfg, serving = cell.config, cell.config["serving"]
    events = []
    tr = Tracer()
    tr.subscribe(lambda ev: ev.kind == "step" and events.append(ev.data))
    engine_cls = serve.PagedServeEngine
    monkeypatch.setattr(serve, "PagedServeEngine",
                        lambda *a, **kw: engine_cls(*a, tracer=tr, **kw))
    model = program.build_model(cell.family.model_config(cfg))
    params = program.make_params(model, cell.family, cell.reference, cfg,
                                 2**31 + 5)
    engine = program.build_engine(model, params, serving)
    assert engine.trace is tr
    items = traffic.generate(cell.mix, 2**31 + 5, cfg["vocab_size"],
                             serving["slots"])
    drv = harness.Driver(engine, items, cell.mix, serving["slots"],
                         serving["chunk"])
    drv.traced = True
    seen = NS(prefill=0, decode=0, finished=0, admitted=0)
    for _ in range(60):
        drv.top_up()
        mark = len(events)
        admitted = sum(r.admitted is not None for r in drv.recs.values())
        st = drv.step()
        [ev] = events[mark:]
        assert list(ev["work"]) == st.work
        assert len(ev["work"]) == st.lanes
        assert ev["rows"] == serving["slots"] * serving["chunk"]
        seen.prefill += ev["prefill_lanes"] > 0
        seen.decode += ev["decode_lanes"] > 0
        seen.admitted += sum(r.admitted is not None
                             for r in drv.recs.values()) > admitted
        seen.finished += st.lanes > len(engine.active)
    assert seen.prefill and seen.decode and seen.finished
    assert seen.admitted > 1
    jax.block_until_ready(engine.cache)
