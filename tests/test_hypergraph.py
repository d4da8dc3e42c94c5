"""Unit + property tests for the hypergraph substrate."""

import collections

import numpy as np
import pytest

from _pbt import given, settings, st

from repro.core.hypergraph import Hypergraph, build_incidence


def small_hg():
    return Hypergraph.from_edges(
        [[0, 1, 2], [2, 3], [3, 4, 5], [0, 5], [1, 2, 3]], num_nodes=6
    )


def test_basic_shapes():
    hg = small_hg()
    assert hg.num_nodes == 6
    assert hg.num_edges == 5
    assert hg.num_pins == 13
    assert hg.avg_items_per_query() == pytest.approx(13 / 5)
    np.testing.assert_array_equal(hg.edge(0), [0, 1, 2])
    np.testing.assert_array_equal(hg.edge_sizes(), [3, 2, 3, 2, 3])


def test_from_edges_dedupes_pins():
    hg = Hypergraph.from_edges([[1, 1, 2]])
    np.testing.assert_array_equal(hg.edge(0), [1, 2])


def test_incidence_inverse():
    hg = small_hg()
    node_ptr, node_edges = hg.incidence()
    # node 2 appears in edges 0, 1, 4
    np.testing.assert_array_equal(sorted(hg.node_edges_of(2)), [0, 1, 4])
    np.testing.assert_array_equal(sorted(hg.node_edges_of(4)), [2])


def test_degrees_weighted():
    hg = Hypergraph.from_edges(
        [[0, 1], [1, 2]], edge_weights=np.array([2.0, 3.0])
    )
    np.testing.assert_allclose(hg.degrees(), [2.0, 5.0, 3.0])


def test_subhypergraph_edges_preserves_node_ids():
    hg = small_hg()
    sub = hg.subhypergraph_edges(np.array([1, 3]))
    assert sub.num_edges == 2
    np.testing.assert_array_equal(sub.edge(0), [2, 3])
    np.testing.assert_array_equal(sub.edge(1), [0, 5])
    assert sub.num_nodes == 6  # labels preserved


def test_relabel_compacts():
    hg = small_hg().subhypergraph_edges(np.array([1]))
    g, old_ids = hg.relabel()
    assert g.num_nodes == 2
    np.testing.assert_array_equal(old_ids, [2, 3])
    np.testing.assert_array_equal(old_ids[g.edge(0)], [2, 3])


def test_peel_densest():
    # clique on 0-3 (dense) plus pendant edges to 4,5,6
    edges = [[0, 1], [0, 2], [0, 3], [1, 2], [1, 3], [2, 3], [3, 4], [4, 5], [5, 6]]
    hg = Hypergraph.from_edges(edges, num_nodes=7)
    dense = set(hg.k_densest_nodes(4))
    assert dense == {0, 1, 2, 3}


def test_prune_to_size_keeps_contained_edges():
    edges = [[0, 1], [0, 2], [1, 2], [2, 3], [3, 4]]
    hg = Hypergraph.from_edges(edges, num_nodes=5)
    pruned = hg.prune_to_size(3)
    survivors = set(pruned.active_nodes())
    # every surviving edge is fully inside the surviving node set
    for e in range(pruned.num_edges):
        assert set(int(v) for v in pruned.edge(e)) <= survivors


def test_mutable_roundtrip():
    hg = small_hg()
    m = hg.copy_mutable()
    new = m.add_node_copy(2)
    assert new == 6
    assert m.node_weights[new] == hg.node_weights[2]
    assert m.replace_in_edge(0, 2, new)
    frozen = m.freeze()
    assert frozen.num_nodes == 7
    np.testing.assert_array_equal(frozen.edge(0), [0, 1, 6])


# --------------------------------------------------------------- properties
edge_strategy = st.lists(
    st.lists(st.integers(0, 19), min_size=1, max_size=6),
    min_size=1, max_size=30,
)


@given(edge_strategy)
@settings(max_examples=50, deadline=None)
def test_incidence_is_inverse_property(edges):
    hg = Hypergraph.from_edges(edges, num_nodes=20)
    node_ptr, node_edges = build_incidence(hg.edge_ptr, hg.edge_nodes, 20)
    # pin count conserved
    assert node_ptr[-1] == hg.num_pins
    for v in range(20):
        for e in node_edges[node_ptr[v]:node_ptr[v + 1]]:
            assert v in set(hg.edge(int(e)))


@given(edge_strategy, st.floats(1.0, 15.0))
@settings(max_examples=50, deadline=None)
def test_peel_respects_weight_budget(edges, budget):
    hg = Hypergraph.from_edges(edges, num_nodes=20)
    nodes = hg.k_densest_nodes(budget)
    assert hg.node_weights[nodes].sum() <= budget + 1e-9


def _counter_merge(queries, weights=None):
    """Plain reference: a Counter of canonical item sets, which keeps
    first-occurrence order, and the weights summed in trace order."""
    keys = [tuple(sorted(set(int(v) for v in q))) for q in queries]
    w = [1.0] * len(keys) if weights is None else [float(x) for x in weights]
    order = list(collections.Counter(keys))
    total = dict.fromkeys(order, 0.0)
    for k, x in zip(keys, w):
        total[k] += x
    return order, [total[k] for k in order]


@pytest.mark.parametrize("weighted", [False, True])
def test_from_access_sets_matches_counter_reference(weighted):
    rng = np.random.default_rng(5)
    base = [rng.choice(30, size=rng.integers(1, 9), replace=False)
            for _ in range(12)]
    queries = []
    for _ in range(200):
        q = base[int(rng.integers(len(base)))]
        # the same set with duplicate items, in another order, as a list
        q = np.concatenate([q, q[: int(rng.integers(0, len(q) + 1))]])
        rng.shuffle(q)
        queries.append(q.tolist() if rng.random() < 0.5 else q)
    weights = rng.random(len(queries)) * 3 if weighted else None
    hg = Hypergraph.from_access_sets(queries, 30, query_weights=weights)
    order, want_w = _counter_merge(queries, weights)
    assert [tuple(hg.edge(e).tolist()) for e in range(hg.num_edges)] == order
    assert hg.edge_weights.tolist() == want_w
    assert hg.num_edges == len(base)
    assert np.array_equal(hg.node_weights, np.ones(30))


def test_from_access_sets_without_repeats_is_from_edges():
    rng = np.random.default_rng(8)
    # query i alone holds item i, with up to 10 of items 50..59
    queries = [rng.permutation(np.append(
        rng.choice(np.arange(50, 60), size=int(k), replace=False), i))
        for i, k in enumerate(rng.integers(0, 11, size=50))]
    nw = rng.random(60) + 0.5
    qw = rng.random(50)
    for qs, kw in ((queries, {}), ([np.sort(q) for q in queries], {}),
                   (queries, {"query_weights": qw})):
        merged = Hypergraph.from_access_sets(qs, 60, node_weights=nw, **kw)
        plain = Hypergraph.from_edges(qs, num_nodes=60, node_weights=nw,
                                      edge_weights=kw.get("query_weights"))
        assert merged.equals(plain)
    assert Hypergraph.from_access_sets([], 3).equals(
        Hypergraph.from_edges([], num_nodes=3))
