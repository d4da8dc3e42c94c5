"""Equivalence tests: the batched bitset span engine and the incremental
SpanMaintainer must agree BIT-FOR-BIT with the per-edge reference greedy
cover (same spans, same chosen partitions in the same order, same replica
attribution, same unplaced-item error)."""

import numpy as np
import pytest

from _pbt import given, settings, st

from repro import flags
from repro.core.hypergraph import Hypergraph
from repro.core.setcover import (
    Placement,
    SpanMaintainer,
    batched_cover_csr,
    batched_spans_csr,
    cover_for_query,
    greedy_set_cover,
)


def random_instance(rng, *, weighted=False, phantoms=False, cover_all=True):
    """A random membership matrix + workload, optionally with weighted and
    phantom (weight-0) items."""
    num_items = int(rng.integers(3, 120))
    n_parts = int(rng.integers(1, 9))
    member = rng.random((n_parts, num_items)) < rng.uniform(0.1, 0.7)
    if cover_all:
        member[rng.integers(0, n_parts), :] |= ~member.any(axis=0)
    weights = (
        rng.uniform(0.5, 5.0, num_items) if weighted
        else np.ones(num_items)
    )
    if phantoms:
        weights[rng.random(num_items) < 0.2] = 0.0
    edges = [
        rng.choice(num_items, size=int(rng.integers(1, min(num_items, 90) + 1)),
                   replace=False)
        for _ in range(int(rng.integers(1, 40)))
    ]
    hg = Hypergraph.from_edges(edges, num_nodes=num_items)
    return hg, member, weights


def assert_batched_matches_reference(hg, member):
    cov = batched_cover_csr(hg.edge_ptr, hg.edge_nodes, member,
                            with_pin_parts=True)
    for e in range(hg.num_edges):
        q = hg.edge(e)
        chosen, accessed = cover_for_query(q, member)
        assert list(cov.chosen(e)) == chosen
        assert cov.spans[e] == len(greedy_set_cover(q, member))
        pp = cov.pin_parts[hg.edge_ptr[e]: hg.edge_ptr[e + 1]]
        for p, items in zip(chosen, accessed):
            np.testing.assert_array_equal(q[pp == p], items)


@given(st.integers(0, 10_000))
@settings(max_examples=40, deadline=None)
def test_batched_cover_equals_reference(seed):
    rng = np.random.default_rng(seed)
    hg, member, _ = random_instance(rng)
    assert_batched_matches_reference(hg, member)


@given(st.integers(0, 10_000))
@settings(max_examples=25, deadline=None)
def test_batched_cover_weighted_and_phantom_items(seed):
    """Item weights (incl. phantom weight-0 items) never change covers —
    only capacity accounting — but the instances exercise the same paths the
    placement algorithms hit."""
    rng = np.random.default_rng(seed)
    hg, member, weights = random_instance(rng, weighted=True, phantoms=True)
    pl = Placement(member, capacity=1e9, node_weights=weights)
    ref = np.asarray([
        len(greedy_set_cover(hg.edge(e), pl.member))
        for e in range(hg.num_edges)
    ])
    np.testing.assert_array_equal(
        batched_spans_csr(hg.edge_ptr, hg.edge_nodes, pl.member), ref
    )


def test_multiword_queries():
    """Queries above 64 pins use multi-word bitsets."""
    V = 400
    member = np.zeros((5, V), dtype=bool)
    member[0] = True
    member[1, ::2] = True
    member[2, ::7] = True
    edges = [range(0, 200), range(37, 391), [3], range(V)]
    hg = Hypergraph.from_edges(edges, num_nodes=V)
    assert_batched_matches_reference(hg, member)


def test_unplaced_item_raises_like_reference():
    member = np.zeros((2, 4), dtype=bool)
    member[0, [0, 1]] = True
    hg = Hypergraph.from_edges([[0, 1], [1, 2, 3]], num_nodes=4)
    with pytest.raises(ValueError):
        greedy_set_cover(hg.edge(1), member)
    with pytest.raises(ValueError):
        batched_spans_csr(hg.edge_ptr, hg.edge_nodes, member)


def test_empty_and_trivial_queries():
    member = np.ones((3, 2), dtype=bool)
    ptr = np.array([0, 0, 1, 2])  # one empty query
    nodes = np.array([0, 1])
    cov = batched_cover_csr(ptr, nodes, member, with_pin_parts=True)
    np.testing.assert_array_equal(cov.spans, [0, 1, 1])
    assert list(cov.chosen(0)) == []
    assert list(cov.chosen(1)) == [0]  # tie -> lowest partition id


@given(st.integers(0, 10_000))
@settings(max_examples=20, deadline=None)
def test_span_maintainer_tracks_mutations(seed):
    """Incremental spans after notify_items == full batched recompute ==
    per-edge reference, across a random mutation sequence."""
    rng = np.random.default_rng(seed)
    hg, member, _ = random_instance(rng)
    pl = Placement(member.copy(), capacity=1e9,
                   node_weights=np.ones(hg.num_nodes))
    sm = SpanMaintainer(hg, pl)
    for _ in range(8):
        items = rng.choice(hg.num_nodes,
                           size=min(hg.num_nodes, int(rng.integers(1, 6))),
                           replace=False)
        pl.member[int(rng.integers(0, pl.num_partitions)), items] = True
        sm.notify_items(items)
        want = batched_spans_csr(hg.edge_ptr, hg.edge_nodes, pl.member)
        np.testing.assert_array_equal(sm.spans(), want)
        np.testing.assert_array_equal(
            sm.residual_edges(1), np.flatnonzero(want > 1)
        )


def test_jax_backend_matches_numpy():
    jax = pytest.importorskip("jax")  # noqa: F841
    rng = np.random.default_rng(7)
    hg, member, _ = random_instance(rng)
    ref = batched_spans_csr(hg.edge_ptr, hg.edge_nodes, member)
    flags.FLAGS["span_backend"] = "jax"
    try:
        got = batched_spans_csr(hg.edge_ptr, hg.edge_nodes, member)
    finally:
        flags.reset()
    np.testing.assert_array_equal(got, ref)


def test_span_backend_variant_flag():
    flags.set_variant("spanjax")
    assert flags.FLAGS["span_backend"] == "jax"
    flags.set_variant("spanpallas+spanth12345")
    assert flags.FLAGS["span_backend"] == "pallas"
    assert flags.FLAGS["span_dispatch_threshold"] == 12345
    flags.reset()
    # auto = per-bucket dispatch (numpy below the threshold, accelerated
    # above); every backend is bit-identical so the default is purely perf
    assert flags.FLAGS["span_backend"] == "auto"
    assert flags.FLAGS["span_dispatch_threshold"] == 48_000


def test_auto_dispatch_threshold_boundaries():
    """auto mode is exact at both extremes of the threshold: everything on
    numpy (huge threshold) and everything accelerated (threshold 0)."""
    rng = np.random.default_rng(3)
    hg, member, _ = random_instance(rng)
    flags.FLAGS["span_backend"] = "numpy"
    try:
        ref = batched_spans_csr(hg.edge_ptr, hg.edge_nodes, member)
    finally:
        flags.reset()
    for thresh in (0, 1 << 60):
        flags.FLAGS.update(span_backend="auto",
                           span_dispatch_threshold=thresh)
        try:
            got = batched_spans_csr(hg.edge_ptr, hg.edge_nodes, member)
        finally:
            flags.reset()
        np.testing.assert_array_equal(got, ref)


def test_maintainer_cover_mode_matches_reference():
    """SpanMaintainer(with_covers=True): covers after refresh_edges equal
    per-edge cover_for_query across membership mutations."""
    rng = np.random.default_rng(17)
    hg, member, _ = random_instance(rng)
    pl = Placement(member.copy(), capacity=1e9,
                   node_weights=np.ones(hg.num_nodes))
    sm = SpanMaintainer(hg, pl, with_covers=True)
    for _ in range(5):
        items = rng.choice(hg.num_nodes, size=int(rng.integers(1, 5)),
                           replace=False)
        pl.member[int(rng.integers(0, pl.num_partitions)), items] = True
        sm.refresh_edges(np.arange(hg.num_edges))
        for e in range(hg.num_edges):
            chosen, accessed = cover_for_query(hg.edge(e), pl.member)
            cov = sm.cover(e)
            assert list(cov) == chosen  # same partitions, selection order
            for p, its in zip(chosen, accessed):
                np.testing.assert_array_equal(cov[p], its)
        np.testing.assert_array_equal(
            sm.spans(),
            batched_spans_csr(hg.edge_ptr, hg.edge_nodes, pl.member),
        )
