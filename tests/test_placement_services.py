"""Tests for the production placement services (flat, hierarchical, refit,
expert placement, shard placement)."""

import numpy as np
import pytest

from repro.core import (
    PlacementPlan, PlacementService, baseline_contiguous_placement,
    greedy_set_cover, mixture_batch_recipes, plan_expert_placement,
    plan_shard_placement, random_workload, synthetic_routing_trace,
)


@pytest.fixture(scope="module")
def queries():
    return random_workload(num_items=120, num_queries=250, density=6, seed=2).queries


def test_fit_and_select(queries):
    svc = PlacementService("lmbr", seed=0)
    plan = svc.fit(queries, 120, 8, 30)
    parts, accessed = plan.select(queries[0])
    got = sorted(int(v) for items in accessed for v in items)
    assert got == sorted(int(v) for v in queries[0])
    assert plan.span(queries[0]) == len(parts)


def test_json_roundtrip(queries):
    svc = PlacementService("ds", seed=0)
    plan = svc.fit(queries, 120, 8, 30)
    plan2 = PlacementPlan.from_json(plan.to_json())
    assert (plan2.member == plan.member).all()
    assert plan2.capacity == plan.capacity


def test_json_roundtrip_empty_partitions_and_weights():
    """Round-trip must survive partitions holding nothing and heterogeneous
    item weights (both exercised by TPC-H-style layouts)."""
    member = np.zeros((4, 6), dtype=bool)
    member[0, [0, 2]] = True
    member[2, [1, 3, 4, 5]] = True  # partitions 1 and 3 stay empty
    weights = np.array([0.5, 2.0, 1.25, 3.0, 0.25, 1.0])
    plan = PlacementPlan(member, 7.5, weights, "custom")
    plan2 = PlacementPlan.from_json(plan.to_json())
    assert (plan2.member == member).all()
    assert plan2.member.shape == member.shape  # empty rows preserved
    assert plan2.capacity == 7.5
    assert np.array_equal(plan2.node_weights, weights)
    assert plan2.algorithm == "custom"


def test_plan_spans_match_reference_loop(queries):
    """The batched PlacementPlan.span/spans/avg_span equals the per-query
    greedy_set_cover loop it replaced, element-wise."""
    svc = PlacementService("lmbr", seed=0)
    plan = svc.fit(queries, 120, 8, 30)
    ref = np.array([
        len(greedy_set_cover(np.asarray(q, dtype=np.int64), plan.member))
        for q in queries
    ])
    assert np.array_equal(plan.spans(queries), ref)
    assert plan.span(queries[7]) == int(ref[7])
    assert plan.avg_span(queries) == float(ref.mean())
    assert plan.avg_span([]) == 0.0


def test_hierarchical_spans_and_weighted_span(queries):
    """HierarchicalPlan.spans == hierarchical greedy cover (pods first, then
    hosts restricted to the chosen pods); weighted_span is the DCN/ICI mix."""
    svc = PlacementService("lmbr", seed=0)
    hp = svc.fit_hierarchical(queries, 120, num_pods=2, hosts_per_pod=4,
                              host_capacity=30)
    for q in queries[:40]:
        q = np.asarray(q, dtype=np.int64)
        ps, hs = hp.spans(q)
        pods = greedy_set_cover(q, hp.pod_plan.member)
        assert ps == len(pods)
        rows = [p * hp.hosts_per_pod + h for p in pods
                for h in range(hp.hosts_per_pod)]
        assert hs == len(greedy_set_cover(q, hp.host_member[rows]))
        assert hp.weighted_span(q) == 8.0 * (ps - 1) + (hs - 1)
        assert hp.weighted_span(q, pod_weight=2.5) == 2.5 * (ps - 1) + (hs - 1)
        # a query served inside one pod costs no DCN hops
        if ps == 1:
            assert hp.weighted_span(q) == hs - 1


def test_hierarchical_spans(queries):
    svc = PlacementService("lmbr", seed=0)
    hp = svc.fit_hierarchical(queries, 120, num_pods=2, hosts_per_pod=4,
                              host_capacity=30)
    pod_spans, host_spans = zip(*(hp.spans(q) for q in queries[:50]))
    assert max(pod_spans) <= 2
    assert all(h >= p for p, h in zip(pod_spans, host_spans))
    # pod-level co-location: most queries stay inside one pod
    assert np.mean(np.asarray(pod_spans) == 1) > 0.5


def test_refit_improves_drifted_workload():
    wl_old = random_workload(num_items=120, num_queries=200, density=6, seed=2)
    wl_new = random_workload(num_items=120, num_queries=100, density=6, seed=99)
    svc = PlacementService("hpa", seed=0)  # no replication yet -> room to refit
    plan = svc.fit(wl_old.queries, 120, 10, 30)
    before = plan.avg_span(wl_new.queries)
    plan2 = svc.refit(plan, wl_new.queries)
    after = plan2.avg_span(wl_new.queries)
    assert after <= before
    # refit only adds copies, never removes
    assert (plan2.member >= plan.member).all()


def test_expert_placement_reduces_span_and_a2a():
    trace = synthetic_routing_trace(num_experts=64, num_groups=300, top_k=8,
                                    seed=0)
    base = baseline_contiguous_placement(64, 8, slots_per_rank=12)
    plan = plan_expert_placement(trace, 64, 8, slots_per_rank=12,
                                 algorithm="lmbr", seed=0)
    assert plan.avg_span(trace) < base.avg_span(trace)
    assert plan.a2a_bytes(trace, 1024, 2048) < base.a2a_bytes(trace, 1024, 2048)
    # structural invariants for the device tables
    assert plan.member.sum(axis=1).max() <= 12
    assert plan.member.any(axis=0).all()  # every expert placed
    for r in range(8):
        slots = plan.slot_to_expert[r]
        live = slots[slots >= 0]
        assert len(set(live.tolist())) == len(live)  # no dup expert per rank
        for s, e in enumerate(slots):
            if e >= 0:
                assert plan.expert_slot_table[e, r] == s


def test_expert_placement_needs_enough_slots():
    with pytest.raises(ValueError):
        plan_expert_placement([np.array([0, 1])], 64, 4, slots_per_rank=8)


def test_shard_placement_failover():
    recipes = mixture_batch_recipes(100, 150, seed=1)
    plan = plan_shard_placement(recipes, 100, 12, capacity=30, algorithm="pra3")
    assert plan.survives_failures(1)
    assert plan.survives_failures(2)
    hosts, accessed = plan.hosts_for_batch(recipes[0])
    # failure of the primary host still covers the batch
    hosts2, _ = plan.cover_excluding(recipes[0], {hosts[0]})
    assert hosts[0] not in hosts2
    got = sorted(int(v) for it in _ for v in it)
    assert got == sorted(set(int(v) for v in recipes[0]))


def test_shard_placement_beats_random():
    recipes = mixture_batch_recipes(100, 200, seed=3)
    rnd = plan_shard_placement(recipes, 100, 12, capacity=30, algorithm="random3")
    pra = plan_shard_placement(recipes, 100, 12, capacity=30, algorithm="pra3")
    assert pra.avg_span(recipes) < rnd.avg_span(recipes)


def _repeating_trace(seed, n=300):
    """A trace that repeats 40 distinct access sets, some given with a
    duplicate item or in another item order."""
    base = random_workload(num_items=120, num_queries=40, density=6,
                           seed=seed).queries
    rng = np.random.default_rng(seed)
    out = []
    for i in rng.integers(0, len(base), size=n):
        q = list(base[int(i)])
        out.append(q[::-1] + q[:1] if rng.random() < 0.3 else q)
    return out


@pytest.mark.parametrize("algorithm", ["lmbr", "hpa", "random", "ihpa", "ds",
                                       "pra"])
def test_fit_merges_repeats_into_weighted_access_sets(algorithm):
    from repro.core import ALGORITHMS, WEIGHTED_EDGES, Hypergraph

    trace = _repeating_trace(4)
    hg = Hypergraph.from_access_sets(trace, 120)
    assert hg.num_edges < len(trace) and hg.edge_weights.sum() == len(trace)
    svc = PlacementService(algorithm, seed=0)
    a = svc.fit(trace, 120, 8, 30)
    assert a.stats["queries"] == len(trace)
    if algorithm not in WEIGHTED_EDGES:
        # repeats count as edges in IHPA's drop, DS's degrees and PRA's
        # hitting sets: these fit every query as its own edge
        pl = ALGORITHMS[algorithm](Hypergraph.from_edges(trace, 120), 8, 30,
                                   seed=0, nruns=2)
        assert np.array_equal(a.member, pl.member)
        assert a.stats["edges"] == len(trace)
        return
    merged = [hg.edge(e) for e in range(hg.num_edges)]
    b = svc.fit(merged, 120, 8, 30, query_weights=hg.edge_weights)
    assert np.array_equal(a.member, b.member)
    assert b.stats["queries"] == len(merged)
    assert a.stats["edges"] == b.stats["edges"] == hg.num_edges
    for k in ("moves", "gain_calls"):
        assert a.stats.get(k) == b.stats.get(k)


@pytest.mark.parametrize("algorithm", ["lmbr", "pra"])
def test_every_entry_point_fits_one_hypergraph(algorithm):
    """fit_sharded and fit_hierarchical build a query list's hypergraph as
    fit does: merged where the algorithm weighs edges, else per query."""
    from repro import flags, obs
    from repro.core import WEIGHTED_EDGES, Hypergraph

    trace = _repeating_trace(5)
    build = (Hypergraph.from_access_sets if algorithm in WEIGHTED_EDGES
             else Hypergraph.from_edges)
    hg = build(trace, 120)
    svc = PlacementService(algorithm, seed=0)
    a = svc.fit_sharded(trace, 8, 30, num_items=120, num_shards=2)
    b = svc.fit_sharded(hg, 8, 30, num_shards=2)
    assert np.array_equal(a.member, b.member)
    flags.FLAGS["obs_level"] = "trace"
    obs.reset()
    try:
        svc.fit_hierarchical(trace, 120, 2, 4, 30)
        events = [e["args"] for e in obs.tracer().events
                  if e["name"] == "fit.merge"]
    finally:
        flags.FLAGS["obs_level"] = "off"
        obs.reset()
    # the pod level's fit, then the host level's own build of the trace
    assert [(e["queries"], e["edges"]) for e in events[:2]] == [
        (len(trace), hg.num_edges)] * 2


def test_fit_without_repeats_is_the_unmerged_fit(queries):
    from repro.core import ALGORITHMS, Hypergraph

    assert len({tuple(sorted(q)) for q in queries}) == len(queries)
    plan = PlacementService("lmbr", seed=0).fit(queries, 120, 8, 30)
    pl = ALGORITHMS["lmbr"](Hypergraph.from_edges(queries, num_nodes=120),
                            8, 30, seed=0, nruns=2)
    assert np.array_equal(plan.member, pl.member)
    assert plan.stats["edges"] == plan.stats["queries"] == len(queries)
    for k in ("moves", "gain_calls", "peel_pairs", "peel_pins_max",
              "peel_items", "peel_classes"):
        assert plan.stats[k] == pl.stats[k]


def test_fit_merge_span_and_refit_merge():
    from repro import flags, obs

    trace = _repeating_trace(6)
    flags.FLAGS["obs_level"] = "trace"
    obs.reset()
    try:
        svc = PlacementService("hpa", seed=0)
        plan = svc.fit(trace, 120, 10, 30)
        plan2 = svc.refit(plan, trace + trace[:7])
        events = [e for e in obs.tracer().events if e["name"] == "fit.merge"]
    finally:
        flags.FLAGS["obs_level"] = "off"
        obs.reset()
    edges = plan.stats["edges"]
    assert [(e["args"]["queries"], e["args"]["edges"]) for e in events] == [
        (len(trace), edges), (len(trace) + 7, edges)]
    # each merge is inside its request span
    assert all(e["args"]["fit"] is not None for e in events)
    assert plan2.stats["queries"] == len(trace) + 7
    assert plan2.stats["edges"] == edges
    assert (plan2.member >= plan.member).all()
