"""Cluster serving: prefix-affinity routing over N paged replicas.

Covers the pure routing machinery (summaries, match depth, policies)
without a model, then the ClusterEngine against real workload traces:
token-exactness vs the single engine under every policy, affinity
accounting, load-aware spill, summary staleness, cancel of unrouted
requests, the aggregated report, and the audit layer's
``pathway-routing`` detection of a misrouting cluster.
"""
import jax
import numpy as np
import pytest

from repro.audit import (AuditContext, DEFAULT_REGISTRY, Evidence,
                         ExpectedSignature, Rule, Tracer)
from repro.serve import (AffinityPolicy, BloomSummary, ClusterEngine,
                         ExactSummary, PagedServeEngine, RandomPolicy,
                         Request, RoundRobinPolicy, SamplingParams,
                         chain_hashes, compare_engines, generate,
                         make_policy, match_depth, smoke_specs,
                         token_matrix)

GEOM = dict(slots=2, max_len=48, block_size=8, chunk=4)
MAX_NEW = 4


@pytest.fixture(scope="module")
def served():
    from repro.configs import ALL_ARCHS, reduced
    from repro.models import build

    cfg = reduced(ALL_ARCHS["deepseek-7b"])
    model = build(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def chat_trace(served):
    cfg, _, _ = served
    return generate(smoke_specs(vocab_size=cfg.vocab_size, seed=0)[0])


def _requests(trace):
    reqs = trace.requests()
    for r in reqs:
        r.max_new = MAX_NEW
    return reqs


# ------------------------------------------------------------- summaries


def test_exact_summary_membership():
    s = ExactSummary()
    for h in (3, 99, 2**63):
        s.add(h)
    assert 3 in s and 99 in s and 2**63 in s
    assert 7 not in s
    assert len(s) == 3


def test_bloom_summary_no_false_negatives_and_low_fp():
    s = BloomSummary(bits=4096, k=3)
    rng = np.random.default_rng(0)
    member = [int(h) for h in rng.integers(0, 2**63, size=64)]
    for h in member:
        s.add(h)
    assert all(h in s for h in member)          # never a false negative
    probe = [int(h) for h in rng.integers(0, 2**63, size=2000)]
    fp = sum(1 for h in probe if h not in member and h in s)
    assert fp / len(probe) < 0.05               # ~64 keys in 4096 bits


def test_bloom_summary_validates_geometry():
    with pytest.raises(ValueError):
        BloomSummary(bits=0)
    with pytest.raises(ValueError):
        BloomSummary(k=9)


def test_match_depth_counts_leading_blocks_only():
    s = ExactSummary()
    tokens = list(range(32))
    hashes = chain_hashes(tokens, 8)
    for h in hashes[:2]:
        s.add(h)
    assert match_depth(s, hashes) == 2
    # a hole stops the walk even if deeper hashes are present
    s2 = ExactSummary()
    s2.add(hashes[0])
    s2.add(hashes[2])
    assert match_depth(s2, hashes) == 1
    assert match_depth(ExactSummary(), hashes) == 0


# -------------------------------------------------------------- policies


class _FakeReplica:
    def __init__(self, idx, load, slots=2):
        self.idx, self.load, self.slots = idx, load, slots


def test_make_policy_resolves_names_and_passthrough():
    assert isinstance(make_policy("affinity"), AffinityPolicy)
    assert isinstance(make_policy("round_robin"), RoundRobinPolicy)
    assert isinstance(make_policy("random", seed=3), RandomPolicy)
    pol = AffinityPolicy(spill_factor=3.0)
    assert make_policy(pol) is pol
    with pytest.raises(ValueError):
        make_policy("nearest")


def test_affinity_policy_prefers_deepest_match():
    pol = AffinityPolicy()
    reps = [_FakeReplica(0, 0), _FakeReplica(1, 0), _FakeReplica(2, 0)]
    idx, kind = pol.choose(None, [1, 3, 2], reps)
    assert (idx, kind) == (1, "affine")


def test_affinity_policy_cold_routes_to_least_loaded():
    pol = AffinityPolicy()
    reps = [_FakeReplica(0, 5), _FakeReplica(1, 1), _FakeReplica(2, 2)]
    idx, kind = pol.choose(None, [0, 0, 0], reps)
    assert (idx, kind) == (1, "cold")


def test_affinity_policy_spills_off_saturated_replica():
    pol = AffinityPolicy(spill_factor=2.0)
    # replica 0 holds the prefix but is saturated (load 4 >= 2.0 * 2)
    reps = [_FakeReplica(0, 4, slots=2), _FakeReplica(1, 0, slots=2)]
    idx, kind = pol.choose(None, [2, 0], reps)
    assert (idx, kind) == (1, "spill")
    # not saturated: affinity wins even against an idle sibling
    reps = [_FakeReplica(0, 3, slots=2), _FakeReplica(1, 0, slots=2)]
    idx, kind = pol.choose(None, [2, 0], reps)
    assert (idx, kind) == (0, "affine")


def test_round_robin_cycles():
    pol = RoundRobinPolicy()
    reps = [_FakeReplica(i, 0) for i in range(3)]
    picks = [pol.choose(None, [0, 0, 0], reps)[0] for _ in range(6)]
    assert picks == [0, 1, 2, 0, 1, 2]


def test_random_policy_is_seed_deterministic():
    reps = [_FakeReplica(i, 0) for i in range(4)]
    a = [RandomPolicy(seed=5).choose(None, [0] * 4, reps)[0]
         for _ in range(1)]
    picks1 = [make_policy("random", seed=5).choose(None, [0] * 4, reps)[0]
              for _ in range(8)]
    pol = make_policy("random", seed=5)
    picks2 = [pol.choose(None, [0] * 4, reps)[0] for _ in range(8)]
    pol3 = make_policy("random", seed=5)
    picks3 = [pol3.choose(None, [0] * 4, reps)[0] for _ in range(8)]
    assert picks2 == picks3
    assert a[0] == picks2[0]
    assert len(set(picks2)) > 1                 # actually scatters


# ------------------------------------------------------ engine behaviour


def test_cluster_validates_construction(served):
    _, model, params = served
    with pytest.raises(ValueError):
        ClusterEngine(model, params, replicas=0, **GEOM)
    with pytest.raises(ValueError):
        ClusterEngine(model, params, replicas=2, summary="lossy", **GEOM)
    with pytest.raises(ValueError):
        ClusterEngine(model, params, replicas=2, refresh_every=0, **GEOM)
    with pytest.raises(ValueError):
        ClusterEngine(model, params, replicas=2, routing="nearest", **GEOM)
    with pytest.raises(ValueError):
        ClusterEngine(model, params, replicas=2,
                      replica_tracers=[Tracer()], **GEOM)


def test_cluster_rejects_unplaceable_request_at_submit(served):
    _, model, params = served
    eng = ClusterEngine(model, params, replicas=2, **GEOM)
    with pytest.raises(ValueError):
        eng.submit(Request(rid=0, prompt=[1], max_new=200))


def test_cluster_token_exact_vs_single_engine_all_policies(
        served, chat_trace):
    """Counter-based sampling is placement-independent: every routing
    policy must reproduce the single paged engine's streams exactly."""
    _, model, params = served
    n = len(chat_trace.requests())
    single = PagedServeEngine(model, params, **GEOM)
    ref = token_matrix(single.run(_requests(chat_trace),
                                  arrivals=list(chat_trace.arrivals)),
                       n, MAX_NEW)
    for routing in ("affinity", "round_robin", "random"):
        eng = ClusterEngine(model, params, replicas=3, routing=routing,
                            **GEOM)
        got = token_matrix(eng.run(_requests(chat_trace),
                                   arrivals=list(chat_trace.arrivals)),
                           n, MAX_NEW)
        assert (got == ref).all(), routing


def test_compare_engines_cluster_mode_sampled(served, chat_trace):
    _, model, params = served
    sp = SamplingParams(temperature=0.8, top_k=16, seed=9)
    rep = compare_engines(model, params, lambda: _requests(chat_trace),
                          sampling=sp, cluster={"replicas": 3},
                          **{k: v for k, v in GEOM.items()})
    assert rep.ok, rep.verdicts


def test_affinity_beats_random_on_shared_prefix_trace(served, chat_trace):
    """The routing quality signal the audit layer gates on: affinity
    converts its opportunities; seeded random routing does not."""
    _, model, params = served

    def run(routing):
        eng = ClusterEngine(model, params, replicas=3, routing=routing,
                            routing_seed=11, **GEOM)
        eng.run(_requests(chat_trace), arrivals=list(chat_trace.arrivals))
        return eng.report()

    healthy, misrouted = run("affinity"), run("random")
    assert healthy["affine_opportunities"] > 0
    assert healthy["routed_affinity"] == 1.0
    assert misrouted["routed_affinity"] < healthy["routed_affinity"]
    assert misrouted["shared_hit_rate"] < healthy["shared_hit_rate"]


def test_route_events_and_summary_rebuilds(served, chat_trace):
    _, model, params = served
    tr = Tracer()
    eng = ClusterEngine(model, params, replicas=2, tracer=tr, **GEOM)
    eng.run(_requests(chat_trace), arrivals=list(chat_trace.arrivals))
    n = len(chat_trace.requests())
    routes = tr.events("route")
    assert len(routes) == n
    assert {e.data["replica"] for e in routes} <= {0, 1}
    assert all(e.data["decision"] in ("affine", "spill", "cold")
               for e in routes)
    # each chosen replica's own tracer carries its route decisions too
    per_replica = sum(t.count("route") for t in eng.replica_tracers)
    assert per_replica == n
    # summaries were rebuilt from the report feed as caches filled
    assert eng.report()["summary_rebuilds"] > 0


def test_bloom_summary_routing_matches_exact(served, chat_trace):
    """With this few chains the Bloom digest should make the same
    decisions as the exact set (false positives are rare)."""
    _, model, params = served

    def decisions(summary):
        tr = Tracer()
        eng = ClusterEngine(model, params, replicas=3, summary=summary,
                            tracer=tr, **GEOM)
        eng.run(_requests(chat_trace), arrivals=list(chat_trace.arrivals))
        return [(e.data["rid"], e.data["replica"]) for e in
                tr.events("route")]

    assert decisions("exact") == decisions("bloom")


def test_refresh_every_staleness_still_token_exact(served, chat_trace):
    """A stale summary view may misroute; it must never corrupt output."""
    _, model, params = served
    n = len(chat_trace.requests())
    single = PagedServeEngine(model, params, **GEOM)
    ref = token_matrix(single.run(_requests(chat_trace),
                                  arrivals=list(chat_trace.arrivals)),
                       n, MAX_NEW)
    eng = ClusterEngine(model, params, replicas=3, refresh_every=7, **GEOM)
    got = token_matrix(eng.run(_requests(chat_trace),
                               arrivals=list(chat_trace.arrivals)),
                       n, MAX_NEW)
    assert (got == ref).all()
    assert eng.report()["summary_rebuilds"] >= 1


def test_cancel_unrouted_request_before_arrival(served, chat_trace):
    _, model, params = served
    tr = Tracer()
    eng = ClusterEngine(model, params, replicas=2, tracer=tr, **GEOM)
    req = _requests(chat_trace)[0]
    h = eng.submit(req, arrival=100.0)      # far future: never routed
    assert eng.has_work()
    assert h.cancel() is True
    assert req.cancelled and not eng.has_work()
    assert h.cancel() is False              # idempotent
    assert eng.report()["cancelled"] == 1
    assert eng.report()["routed"] == 0
    ev = tr.last("cancel")
    assert ev.data["phase"] == "waiting" and ev.data["released_pages"] == 0


def test_cluster_report_aggregates_replicas(served, chat_trace):
    _, model, params = served
    eng = ClusterEngine(model, params, replicas=3, **GEOM)
    eng.run(_requests(chat_trace), arrivals=list(chat_trace.arrivals))
    rep = eng.report()
    n = len(chat_trace.requests())
    assert rep["engine"] == "cluster" and rep["replica_engine"] == "paged"
    assert rep["served"] == n and rep["routed"] == n
    per = rep["per_replica"]
    assert len(per) == 3
    for key in ("served", "tokens_out", "prefill_tokens", "cached_tokens",
                "decode_steps", "preemptions"):
        assert rep[key] == sum(p[key] for p in per), key
    assert rep["pages"] == sum(p["pages"] for p in per)
    assert rep["compiles"] == max(p["compiles"] for p in per) == 1
    total = rep["prefill_tokens"] + rep["cached_tokens"]
    assert rep["shared_hit_rate"] == pytest.approx(
        rep["cached_tokens"] / total, abs=1e-3)


# ------------------------------------------------------ audit integration


def test_default_registry_judges_cluster_as_paged(served, chat_trace):
    """The serve-dense-paged rule reads through the cluster to its
    replica engine: a healthy cluster passes, and the engine check does
    not misfire on ``engine="cluster"``."""
    cfg, model, params = served
    tr = Tracer()
    eng = ClusterEngine(model, params, replicas=2, tracer=tr, **GEOM)
    eng.run(_requests(chat_trace), arrivals=list(chat_trace.arrivals))
    ctx = AuditContext(workload="serve", family=cfg.family, arch=cfg.name,
                       shared_prefix=True)
    findings = DEFAULT_REGISTRY.evaluate(
        ctx, Evidence(tracer=tr, engine_report=eng.report()))
    assert findings == []


def test_pathway_routing_finding_fires_on_misrouting(served, chat_trace):
    cfg, model, params = served

    def report(routing):
        eng = ClusterEngine(model, params, replicas=3, routing=routing,
                            routing_seed=11, **GEOM)
        eng.run(_requests(chat_trace), arrivals=list(chat_trace.arrivals))
        return eng.report()

    healthy = report("affinity")
    rule = Rule(name="t-routing", workloads=("serve",),
                expect=ExpectedSignature(
                    min_routed_affinity=0.8 * healthy["routed_affinity"],
                    min_shared_hit_rate=0.85 * healthy["shared_hit_rate"]))
    ctx = AuditContext(workload="serve", family=cfg.family, arch=cfg.name,
                       shared_prefix=True)
    from repro.audit import ExpectationRegistry

    reg = ExpectationRegistry([rule])
    assert reg.evaluate(ctx, Evidence(engine_report=healthy)) == []
    kinds = [f["kind"] for f in
             reg.evaluate(ctx, Evidence(engine_report=report("random")))]
    assert kinds and set(kinds) == {"pathway-routing"}


def test_routed_affinity_vacuous_without_opportunities():
    """No affinity opportunity -> no routing finding (nothing to
    convert), even with a floor of 1.0."""
    rule = Rule(name="t", workloads=("serve",),
                expect=ExpectedSignature(min_routed_affinity=1.0))
    ctx = AuditContext(workload="serve", family="dense")
    from repro.audit import ExpectationRegistry

    rep = {"engine": "cluster", "routed_affinity": 0.0,
           "affine_opportunities": 0}
    assert ExpectationRegistry([rule]).evaluate(
        ctx, Evidence(engine_report=rep)) == []


def test_cluster_logits_agree_with_single_engine(served, chat_trace):
    """The logits form of the routing oracle (what the four-chip smoke
    runs): every served row of the cluster equals the single engine's."""
    _, model, params = served
    for sp in (None, SamplingParams(temperature=0.8, top_k=16, seed=5)):
        rep = compare_engines(model, params, lambda: _requests(chat_trace),
                              cluster={"replicas": 3}, sampling=sp,
                              logits_tol=1e-3, **GEOM)
        (v,) = [v for v in rep.verdicts if v.kind == "logits"]
        assert rep.ok and v.measured == 0.0, v.detail


def test_replicas_take_one_device_each_when_the_host_has_enough():
    """On a host with as many devices as replicas, replica i's weights
    and page pool live on device i; cluster logits still match one
    engine.  (Four placeholder CPU devices, in a child process.)"""
    import os
    import subprocess
    import sys
    import textwrap
    from pathlib import Path

    code = textwrap.dedent("""
        import jax, numpy as np
        from repro.configs import ALL_ARCHS, reduced
        from repro.models import build
        from repro.serve import ClusterEngine, Request, compare_engines
        cfg = reduced(ALL_ARCHS["deepseek-7b"])
        model = build(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        geom = dict(slots=2, max_len=48, block_size=8, chunk=4)
        eng = ClusterEngine(model, params, replicas=4, **geom)
        pools = [next(iter(r.view.k.devices())).id for r in eng.replicas]
        weights = [next(iter(jax.tree.leaves(r.params)[0].devices())).id
                   for r in eng.replicas]
        assert pools == weights == [0, 1, 2, 3], (pools, weights)
        rng = np.random.default_rng(0)
        pre = rng.integers(0, cfg.vocab_size, size=16).tolist()
        tails = [rng.integers(0, cfg.vocab_size, size=3 + i).tolist()
                 for i in range(6)]
        make = lambda: [Request(rid=i, prompt=pre + t, max_new=4)
                        for i, t in enumerate(tails)]
        rep = compare_engines(model, params, make, cluster={"replicas": 4},
                              logits_tol=1e-3, **geom)
        assert rep.ok, rep.summary()
        print("OK")
    """)
    repo = Path(__file__).resolve().parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(repo / "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=repo, env=env, timeout=600)
    assert out.returncode == 0 and out.stdout.strip().endswith("OK"), \
        out.stderr[-3000:]
