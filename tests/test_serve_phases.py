"""PagedServeEngine.step on the profiler's clock: each tick is one
``serve.step`` holding its five phases, in order, nested and apart; the
phases are profiler annotations alone, so a real tracer's event stream
is what it was; and the ``step`` event's counters say what the step
program was given."""
import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.audit.trace import KNOWN_KINDS, NULL_TRACER, Tracer
from repro.configs import ALL_ARCHS, reduced
from repro.models import build
from repro.serve import PagedServeEngine, Request
from repro.serve.paging import pages_for

PHASES = ["serve.schedule", "serve.inputs", "serve.dispatch", "serve.wait",
          "serve.commit"]
SLOTS, CHUNK, BS = 2, 4, 8


@pytest.fixture(scope="module")
def served():
    cfg = reduced(ALL_ARCHS["deepseek-7b"])
    model = build(cfg)
    return cfg, model, model.init_params(jax.random.PRNGKey(0))


def _engine(model, params, tracer=None):
    return PagedServeEngine(model, params, slots=SLOTS, max_len=64,
                            block_size=BS, chunk=CHUNK, tracer=tracer)


def _requests(cfg, n=3):
    rng = np.random.default_rng(3)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size,
                                               size=6 + 5 * i).tolist(),
                    max_new=3 + i) for i in range(n)]


def _run(eng, reqs) -> int:
    for r in reqs:
        eng.submit(r)
    steps = 0
    while eng.has_work():
        eng.step()
        steps += 1
    return steps


def _spans(trace_dir) -> list[tuple[str, int, int, dict]]:
    [path] = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    data = ProfileData.from_file(path)
    return sorted(((e.name, e.start_ns, e.end_ns, dict(e.stats))
                   for p in data.planes if p.name.startswith("/host:")
                   for line in p.lines for e in line.events
                   if e.name.startswith("serve.")),
                  key=lambda s: (s[1], -s[2]))


def test_each_step_holds_its_five_phases_in_order(served, tmp_path):
    cfg, model, params = served
    eng = _engine(model, params)             # NULL_TRACER: annotations only
    assert eng.trace is NULL_TRACER
    _run(_engine(model, params), _requests(cfg))    # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        steps = _run(eng, _requests(cfg))
    finally:
        jax.profiler.stop_trace()
    spans = _spans(tmp_path)
    outer = [s for s in spans if s[0] == "serve.step"]
    assert len(outer) == steps > 3
    assert [s[3]["step_num"] for s in outer] == list(range(1, steps + 1))
    assert all(s[0] in KNOWN_KINDS for s in spans)
    for (_, lo, hi, _), nxt in zip(outer, outer[1:] + [None]):
        assert nxt is None or hi <= nxt[1]
        kids = [s for s in spans if s[0] != "serve.step" and lo <= s[1] < hi]
        assert [k[0] for k in kids] == PHASES
        assert all(lo <= k[1] <= k[2] <= hi for k in kids)
        assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))


def test_phases_leave_the_event_stream_as_it_was(served):
    cfg, model, params = served
    tr = Tracer()
    seen = []
    tr.subscribe(seen.append)
    steps = _run(_engine(model, params, tr), _requests(cfg))
    kinds = {e.kind for e in seen}
    assert not any(k.startswith("serve.") for k in kinds)
    assert not any(k.startswith("serve.") for k in tr.summary()["counts"])
    assert tr.count("step") == steps
    assert len(seen) == tr.emitted == len(tr.events())


def test_step_counters_are_what_the_program_was_given(served):
    cfg, model, params = served
    tr = Tracer()
    eng = _engine(model, params, tr)
    reqs = _requests(cfg)
    for r in reqs:
        eng.submit(r)
    while eng.has_work():
        held = {s: (st.consumed, len(st.shared) + len(st.private))
                for s, st in eng.active.items()}
        before = tr.count("step")
        eng.step()
        if tr.count("step") == before:
            continue
        ev = tr.last("step").data
        assert ev["rows"] == SLOTS * CHUNK
        assert len(ev["work"]) == ev["lanes"] == len(ev["chunk_sizes"])
        assert [n for _, n in ev["work"]] == list(ev["chunk_sizes"])
        # lanes held before the step start where they stood; the pages
        # counted are those of every lane that ran
        for pos, _ in ev["work"][:len(held)]:
            assert pos in {c for c, _ in held.values()}
        assert ev["pages_written"] <= ev["pages_bound"]
        if not any(e.kind == "admit" and e.data["tick"] == ev["tick"]
                   for e in tr.events()):
            assert ev["pages_bound"] == sum(p for _, p in held.values())
            assert ev["pages_written"] == sum(pages_for(c, BS)
                                              for c, _ in held.values())
    assert sum(n for e in tr.events("step") for _, n in e.data["work"]) == \
        sum(len(r.prompt) + len(r.out) - 1 for r in reqs)
