"""Tests for the cluster-scale pipeline (repro.scale): streaming trace
ingestion, workload sharding, parallel per-shard fits + merge + boundary
repair, and the scale flag surface."""

import numpy as np
import pytest

from repro import flags
from repro.core import (
    ALGORITHMS,
    Hypergraph,
    PlacementService,
    canonicalize_csr,
    random_workload,
    spans_for_workload,
    web_scale_chunks,
    web_scale_workload,
)
from repro.scale import (
    StreamingHypergraphBuilder,
    connected_components,
    fit_sharded_placement,
    shard_workload,
)


def _random_queries(rng, num_items, n, with_dups=True):
    out = []
    for _ in range(n):
        k = int(rng.integers(1, 9))
        q = rng.integers(0, num_items, size=k)
        if not with_dups:
            q = np.unique(q)
        out.append(q)
    return out


# ------------------------------------------------------------------ stream
def test_canonicalize_csr_matches_per_edge_unique():
    rng = np.random.default_rng(0)
    queries = _random_queries(rng, 40, 300)
    ptr = np.zeros(len(queries) + 1, dtype=np.int64)
    ptr[1:] = np.cumsum([len(q) for q in queries])
    nodes = np.concatenate(queries)
    cptr, cnodes = canonicalize_csr(ptr, nodes)
    for i, q in enumerate(queries):
        assert np.array_equal(cnodes[cptr[i]: cptr[i + 1]], np.unique(q))


def test_streaming_builder_equals_dict_builder():
    """Chunked streaming build == Hypergraph.from_edges bit-for-bit:
    edge order, per-edge pin dedup + sort, weights, dtypes."""
    rng = np.random.default_rng(1)
    queries = _random_queries(rng, 80, 700)
    weights = rng.uniform(0.5, 3.0, size=len(queries))
    ref = Hypergraph.from_edges(queries, num_nodes=80, edge_weights=weights)
    builder = StreamingHypergraphBuilder(80)
    for lo in range(0, len(queries), 123):  # uneven chunks
        builder.add_queries(queries[lo: lo + 123], weights[lo: lo + 123])
    got = builder.build()
    assert got.equals(ref)
    assert got.edge_ptr.dtype == ref.edge_ptr.dtype
    assert got.edge_nodes.dtype == ref.edge_nodes.dtype


def test_streaming_builder_csr_chunks_and_rebuild():
    """add_csr ingests raw CSR chunks (duplicate pins allowed); build() is
    non-destructive, so appending more chunks extends the trace."""
    rng = np.random.default_rng(2)
    q1 = _random_queries(rng, 30, 100)
    q2 = _random_queries(rng, 30, 50)
    builder = StreamingHypergraphBuilder(30)
    ptr = np.zeros(len(q1) + 1, dtype=np.int64)
    ptr[1:] = np.cumsum([len(q) for q in q1])
    builder.add_csr(ptr, np.concatenate(q1))
    assert builder.build().equals(Hypergraph.from_edges(q1, num_nodes=30))
    builder.add_queries(q2)
    assert builder.build().equals(
        Hypergraph.from_edges(q1 + q2, num_nodes=30)
    )
    assert builder.num_chunks == 2


def test_streaming_builder_merges_duplicates_like_dict():
    """merge_duplicates=True == the dict reference: unique canonical edges
    in first-seen order, weights summed in arrival order."""
    rng = np.random.default_rng(3)
    base = _random_queries(rng, 12, 60)  # small universe -> many duplicates
    weights = rng.uniform(0.1, 2.0, size=len(base))
    builder = StreamingHypergraphBuilder(12, merge_duplicates=True)
    for lo in range(0, len(base), 17):
        builder.add_queries(base[lo: lo + 17], weights[lo: lo + 17])
    got = builder.build()
    seen: dict[tuple, float] = {}
    order: list[tuple] = []
    for q, w in zip(base, weights):
        key = tuple(np.unique(np.asarray(q, dtype=np.int64)))
        if key in seen:
            seen[key] += float(w)
        else:
            seen[key] = float(w)
            order.append(key)
    assert got.num_edges == len(order)
    for i, key in enumerate(order):
        assert tuple(got.edge(i)) == key
        assert got.edge_weights[i] == seen[key]


def test_streaming_builder_rejects_bad_chunks():
    builder = StreamingHypergraphBuilder(10)
    with pytest.raises(ValueError):
        builder.add_queries([[0, 10]])  # pin out of range
    with pytest.raises(ValueError):
        builder.add_queries([[0, -1]])
    with pytest.raises(ValueError):
        builder.add_queries([[0, 1]], edge_weights=[1.0, 2.0])


def test_web_scale_workload_small_params():
    # chunk size shapes the RNG stream, so rebuilds must chunk identically
    wl = web_scale_workload(num_items=500, num_queries=2000, num_clusters=16,
                            seed=0, chunk=512)
    hg = wl.hypergraph
    assert hg.num_nodes == 500 and hg.num_edges == 2000
    assert hg.edge_nodes.min() >= 0 and hg.edge_nodes.max() < 500
    sizes = hg.edge_sizes()
    assert sizes.min() >= 1 and sizes.max() <= 8
    # generator chunks == built hypergraph through the builder
    b = StreamingHypergraphBuilder(500)
    for ptr, pins in web_scale_chunks(num_items=500, num_queries=2000,
                                      num_clusters=16, seed=0, chunk=512):
        b.add_csr(ptr, pins)
    assert b.build().equals(hg)


# ----------------------------------------------------------------- sharder
def test_connected_components_matches_bruteforce():
    rng = np.random.default_rng(4)
    queries = _random_queries(rng, 60, 25)
    hg = Hypergraph.from_edges(queries, num_nodes=60)
    labels = connected_components(hg)
    # brute-force union-find
    parent = list(range(60))

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for e in range(hg.num_edges):
        pins = hg.edge(e)
        for u in pins[1:]:
            ra, rb = find(int(pins[0])), find(int(u))
            if ra != rb:
                parent[max(ra, rb)] = min(ra, rb)
    want = np.array([find(v) for v in range(60)])
    assert np.array_equal(labels, want)


def test_shard_workload_accounting():
    wl = random_workload(num_items=300, num_queries=1000, density=5, seed=5)
    hg = wl.hypergraph
    plan = shard_workload(hg, num_partitions=12, capacity=60, num_shards=4)
    assert plan.num_shards == 4
    # every item homed exactly once; shard item lists match the map
    assert plan.item_shard.shape == (300,)
    for s, spec in enumerate(plan.shards):
        assert np.array_equal(spec.items, np.flatnonzero(plan.item_shard == s))
        assert spec.sub_hg.num_nodes == len(spec.items)
        # every sub-edge has >= 1 pin, fragments were trimmed to >= 2
        if spec.sub_hg.num_edges:
            assert spec.sub_hg.edge_sizes().min() >= 1
    # partition budget: exact split, each shard feasible
    n_parts = np.diff(plan.part_offset)
    assert n_parts.sum() == 12
    for spec, n in zip(plan.shards, n_parts):
        assert spec.weight <= n * 60 + 1e-9
    # boundary edges are exactly those whose pins span > 1 shard
    pin_shards = plan.item_shard[hg.edge_nodes]
    want_boundary = [
        e for e in range(hg.num_edges)
        if len(set(pin_shards[hg.edge_ptr[e]: hg.edge_ptr[e + 1]])) > 1
    ]
    assert np.array_equal(plan.boundary_edges, want_boundary)
    lam = np.array([
        len(set(pin_shards[hg.edge_ptr[e]: hg.edge_ptr[e + 1]]))
        for e in want_boundary
    ])
    assert np.array_equal(plan.boundary_lambda, lam)
    assert plan.boundary_cost == pytest.approx(
        float((hg.edge_weights[plan.boundary_edges] * (lam - 1)).sum())
    )


def test_shard_workload_separates_components():
    """Two co-access islands + one bridge query: the islands land on
    different shards and only the bridge is a boundary edge."""
    qs = [[0, 1], [1, 2], [0, 2], [3, 4], [4, 5], [3, 5], [2, 3]]
    hg = Hypergraph.from_edges(qs, num_nodes=6)
    # one component (the bridge connects them): force a 2-shard cut
    plan = shard_workload(hg, num_partitions=2, capacity=4, num_shards=2)
    assert plan.num_shards == 2
    assert len(plan.boundary_edges) >= 1
    # without the bridge, components separate perfectly: no boundary
    hg2 = Hypergraph.from_edges(qs[:-1], num_nodes=6)
    plan2 = shard_workload(hg2, num_partitions=2, capacity=4, num_shards=2)
    assert plan2.num_components == 2
    assert len(plan2.boundary_edges) == 0
    assert plan2.boundary_cost == 0.0


def test_shard_workload_infeasible_budget_raises():
    wl = random_workload(num_items=100, num_queries=200, density=5, seed=0)
    with pytest.raises(ValueError):
        shard_workload(wl.hypergraph, num_partitions=2, capacity=10,
                       num_shards=2)


# ------------------------------------------------------------ parallel fit
@pytest.fixture(scope="module")
def clustered_wl():
    return web_scale_workload(num_items=800, num_queries=4000,
                              num_clusters=16, cross_frac=0.05, seed=7)


def test_fit_sharded_serial_equals_pool(clustered_wl):
    """Worker count never changes the fitted placement: the pooled run is
    bit-identical to the deterministic serial fallback."""
    hg = clustered_wl.hypergraph
    serial = fit_sharded_placement(hg, 16, 110, num_shards=4, workers=1,
                                   seed=0, max_moves=40)
    pooled = fit_sharded_placement(hg, 16, 110, num_shards=4, workers=3,
                                   seed=0, max_moves=40)
    assert (serial.member == pooled.member).all()
    assert serial.stats["used_pool"] is False
    serial.placement.validate()


def test_fit_sharded_pool_refused_on_tpu(clustered_wl, monkeypatch):
    """A TPU serves one process: on a TPU backend a process pool of shard
    fits raises instead of starting children on a held chip, and the
    serial path still runs."""
    from repro.scale import parallel_fit

    monkeypatch.setattr(parallel_fit.jax, "default_backend", lambda: "tpu")
    hg = clustered_wl.hypergraph
    with pytest.raises(RuntimeError, match="scale_workers=3"):
        fit_sharded_placement(hg, 16, 110, num_shards=4, workers=3,
                              seed=0, max_moves=40)
    serial = fit_sharded_placement(hg, 16, 110, num_shards=4, workers=1,
                                   seed=0, max_moves=40)
    assert serial.stats["used_pool"] is False


def test_fit_sharded_service_entry_point(clustered_wl):
    hg = clustered_wl.hypergraph
    svc = PlacementService("lmbr", seed=0)
    plan = svc.fit_sharded(hg, num_partitions=16, capacity=110, num_shards=4,
                           workers=1, max_moves=40)
    assert plan.algorithm == "lmbr+sharded"
    assert plan.member.shape == (16, 800)
    assert plan.stats["shards"] == 4
    assert plan.stats["boundary_edges"] >= 0
    # spans are computable for the whole trace (placement covers all items)
    spans = spans_for_workload(hg, plan.as_placement())
    assert len(spans) == hg.num_edges and (spans >= 1).all()
    # flags drive the defaults the same way the kwargs do
    flags.set_variant("shards4+scalew1+brepair64")
    try:
        via_flags = svc.fit_sharded(hg, num_partitions=16, capacity=110,
                                    max_moves=40, boundary_repair=None)
    finally:
        flags.reset()
    assert via_flags.stats["shards"] == 4


def test_fit_sharded_quality_near_monolithic(clustered_wl):
    """On a clustered mid-size workload the sharded fit's avg span stays
    close to the monolithic fit (the bench gates 1.05 on its mid tier; the
    test tier is smaller, so allow a looser 1.15)."""
    hg = clustered_wl.hypergraph
    mono = ALGORITHMS["lmbr"](hg, 16, 110, seed=0, max_moves=160)
    sharded = fit_sharded_placement(hg, 16, 110, num_shards=4, workers=1,
                                    seed=0, max_moves=80)
    mono_span = float(spans_for_workload(hg, mono).mean())
    shard_span = float(spans_for_workload(hg, sharded.placement).mean())
    assert shard_span <= 1.15 * mono_span, (shard_span, mono_span)


def test_boundary_repair_capacity_safety_near_full():
    """Adversarial near-full layout: partitions have almost no free space,
    so the boundary repair pass must place little-to-nothing and NEVER
    violate capacity."""
    wl = web_scale_workload(num_items=600, num_queries=3000, num_clusters=8,
                            cross_frac=0.2, seed=11)
    hg = wl.hypergraph
    # shard weights here are [166, 166, 166, 102]; at capacity 84 three of
    # the four shards have 2 units of free space across 2 partitions each —
    # the repair pass has cross-shard pressure (1400+ boundary edges) but
    # almost nowhere to put copies
    res = fit_sharded_placement(hg, 8, 84, num_shards=4, workers=1, seed=0,
                                max_moves=40, boundary_repair=200)
    res.placement.validate()  # would raise on any over-capacity row
    assert (res.placement.partition_weights() <= 84 + 1e-9).all()
    # and disabling the pass is allowed
    res0 = fit_sharded_placement(hg, 8, 84, num_shards=4, workers=1, seed=0,
                                 max_moves=40, boundary_repair=0)
    assert res0.stats["repair_moves"] == 0


# ------------------------------------------------------------------- flags
def test_scale_flag_variants():
    flags.set_variant("shards16+scalew4+brepair128")
    try:
        assert flags.FLAGS["scale_shards"] == 16
        assert flags.FLAGS["scale_workers"] == 4
        assert flags.FLAGS["scale_boundary_repair"] == 128
    finally:
        flags.reset()
    for bad in ("scalew0", "brepair-1"):
        with pytest.raises(ValueError):
            flags.set_variant(bad)
    flags.reset()
