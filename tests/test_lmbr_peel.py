"""LMBR move-engine exactness: vectorized batched peel vs pure-Python oracle.

The contract under test (tentpole of PR 3): `_lmbr_gain_batch` /
`_lmbr_max_gain_vectorized` reproduce `_lmbr_max_gain_reference`
BIT-IDENTICALLY — same gain floats, same item subsets, same tie-breaks
(ascending edge id in the projection scan, lowest item id on density ties) —
on weighted instances, free pins, and zero-capacity destinations; and the
epoch-keyed gain cache never changes any result.
"""

import numpy as np
import pytest

from repro import flags
from repro.core import hpa_partition, lmbr, random_workload
from repro.core.algorithms import (
    _assign_to_placement,
    _eval_traj,
    _lmbr_gain_batch,
    _lmbr_max_gain_reference,
    _lmbr_max_gain_vectorized,
    _lmbr_peel_flat,
    _lmbr_peel_scalar,
    _lmbr_project,
    _LMBRState,
)
from repro.core.hypergraph import Hypergraph


def _random_state(rng, *, weighted_nodes=False, weighted_edges=False,
                  num_items=60, num_edges=150, num_parts=8, capacity=40.0):
    """A random placement state: random hyperedges over `num_items` items,
    every item on >= 1 random partition plus random extra replicas."""
    edges = []
    for _ in range(num_edges):
        size = int(rng.integers(2, 8))
        edges.append(rng.choice(num_items, size=size, replace=False))
    node_w = (
        rng.uniform(0.5, 4.0, size=num_items) if weighted_nodes else None
    )
    edge_w = (
        rng.uniform(0.1, 3.0, size=num_edges) if weighted_edges else None
    )
    hg = Hypergraph.from_edges(edges, num_nodes=num_items,
                               node_weights=node_w, edge_weights=edge_w)
    assign = rng.integers(0, num_parts, size=num_items)
    pl = _assign_to_placement(hg, assign, num_parts, capacity)
    # random extra replicas (creates free pins: items already on dest)
    extra = rng.random((num_parts, num_items)) < 0.08
    pl.member |= extra
    return hg, _LMBRState(hg, pl)


def _assert_pair_equal(ref, vec, ctx):
    g_ref, it_ref = ref
    g_vec, it_vec = vec
    assert g_ref == g_vec, f"{ctx}: gain {g_ref} != {g_vec}"
    if it_ref is None:
        assert it_vec is None, ctx
    else:
        np.testing.assert_array_equal(it_ref, it_vec, err_msg=str(ctx))


@pytest.mark.parametrize("weighted_nodes,weighted_edges", [
    (False, False), (True, False), (False, True), (True, True),
])
def test_peel_matches_oracle_randomized(weighted_nodes, weighted_edges):
    """Property-style: on randomized (optionally weighted) instances, every
    (src, dest) pair peels to the oracle's exact (gain, items)."""
    rng = np.random.default_rng(11 + 2 * weighted_nodes + weighted_edges)
    for trial in range(4):
        hg, state = _random_state(
            rng, weighted_nodes=weighted_nodes, weighted_edges=weighted_edges,
        )
        n = state.pl.num_partitions
        pairs = [(s, d) for s in range(n) for d in range(n) if s != d]
        batch = _lmbr_gain_batch(state, pairs)
        for key in pairs:
            ref = _lmbr_max_gain_reference(state, *key)
            _assert_pair_equal(ref, batch[key], (trial, key))


@pytest.mark.parametrize("batch", ["one", "all"])
@pytest.mark.parametrize("weighted_nodes,weighted_edges", [
    (False, False), (True, False), (False, True), (True, True),
])
def test_scalar_peel_matches_lockstep_bytes(weighted_nodes, weighted_edges,
                                            batch):
    """The scalar heap peel's trajectories, selected under any free space,
    give the lockstep peel's exact (gain, items), pair by pair and over all
    n(n-1) pairs at once; its pool and fingerprint are the projection's."""
    rng = np.random.default_rng(11 + 2 * weighted_nodes + weighted_edges)
    for trial in range(4):
        hg, state = _random_state(
            rng, weighted_nodes=weighted_nodes, weighted_edges=weighted_edges,
        )
        n = state.pl.num_partitions
        pairs = [(s, d) for s in range(n) for d in range(n) if s != d]
        _, proj = _lmbr_project(state, pairs)
        assert proj
        batches = [[p] for p in proj] if batch == "one" else [proj]
        for b in batches:
            scalar = _lmbr_peel_scalar(state, b)
            assert scalar.keys() == {p[0] for p in b}
            for p in b:
                pool, fp, _ = scalar[p[0]]
                np.testing.assert_array_equal(pool, p[3])
                for x, y in zip(fp, (p[2], p[3], p[4], p[5], p[6])):
                    np.testing.assert_array_equal(x, y)
            for c in ("own", 0.5, 0.25, 1e18):
                bc = [p if c == "own" else
                      (p[0], p[1] * c if c < 1 else c, *p[2:]) for p in b]
                flat = _lmbr_peel_flat(state, bc)
                for p in bc:
                    pool, _, traj = scalar[p[0]]
                    _assert_pair_equal(flat[p[0]],
                                       _eval_traj(pool, traj, p[1]),
                                       (trial, batch, c, p[0]))


def test_host_peel_paths_give_the_same_fit():
    """A float-weighted fit is the same plan, with the same gain calls and
    moves, whether its peels run as the scalar trajectory peel behind the
    item cache (the default), as the lockstep peel behind the partition
    epoch cache, or as the pure-Python oracle."""
    wl = random_workload(num_items=120, num_queries=260, density=5, seed=2)
    rng = np.random.default_rng(4)
    hg = wl.hypergraph
    hg = Hypergraph(hg.edge_ptr, hg.edge_nodes,
                    rng.uniform(0.5, 1.5, hg.num_nodes),
                    rng.uniform(0.1, 3.0, hg.num_edges))
    fits = {}
    for spec in ("baseline", "lmbrepochpartition", "peelreference"):
        flags.set_variant(spec)
        try:
            fits[spec] = lmbr(hg, 9, 25, seed=0)
        finally:
            flags.reset()
    sc = fits["baseline"]
    assert not sc.stats["int_exact"]
    assert sc.stats["peel_pairs"] > 0
    for spec in ("lmbrepochpartition", "peelreference"):
        other = fits[spec]
        np.testing.assert_array_equal(sc.member, other.member, err_msg=spec)
        for k in ("gain_calls", "moves"):
            assert sc.stats[k] == other.stats[k], (spec, k)


def test_peel_zero_capacity_dest():
    """A destination with no free space never receives a candidate set."""
    rng = np.random.default_rng(3)
    hg, state = _random_state(rng, capacity=40.0)
    # drown partition 0 in replicas until it exceeds capacity
    state.pl.member[0, :] = True
    state._loads[0] = state.pl.partition_weight(0)
    assert state.free_space(0) <= 0
    for src in range(1, state.pl.num_partitions):
        _assert_pair_equal(
            _lmbr_max_gain_reference(state, src, 0),
            _lmbr_max_gain_vectorized(state, src, 0),
            ("zero-cap", src),
        )
        assert _lmbr_max_gain_vectorized(state, src, 0) == (0.0, None)


def test_peel_free_pins_are_never_candidates():
    """Items already resident on dest are free (cost 0): they never appear
    in the returned candidate subset, matching the oracle."""
    rng = np.random.default_rng(5)
    hg, state = _random_state(rng)
    n = state.pl.num_partitions
    checked = 0
    for src in range(n):
        for dest in range(n):
            if src == dest:
                continue
            ref = _lmbr_max_gain_reference(state, src, dest)
            vec = _lmbr_max_gain_vectorized(state, src, dest)
            _assert_pair_equal(ref, vec, (src, dest))
            if vec[1] is not None:
                assert not state.pl.member[dest, vec[1]].any()
                checked += 1
    assert checked > 0  # the instance must exercise the non-trivial path


def test_peel_after_moves_and_recompute():
    """Equivalence holds across a sequence of apply_move + recompute_edges
    (the exact mutation pattern of the LMBR move loop)."""
    rng = np.random.default_rng(7)
    hg, state = _random_state(rng)
    n = state.pl.num_partitions
    for step in range(4):
        # apply a random legal move and refresh the touched edges
        dest = int(rng.integers(n))
        items = rng.choice(hg.num_nodes, size=2, replace=False)
        items = items[~state.pl.member[dest, items]]
        if len(items) == 0:
            continue
        state.apply_move(dest, items)
        node_ptr, node_edges = hg.incidence()
        touched = np.unique(np.concatenate(
            [node_edges[node_ptr[v]: node_ptr[v + 1]] for v in items]
        ))
        state.recompute_edges(touched)
        pairs = [(s, d) for s in range(n) for d in range(n) if s != d]
        batch = _lmbr_gain_batch(state, pairs)
        for key in pairs:
            _assert_pair_equal(
                _lmbr_max_gain_reference(state, *key), batch[key],
                (step, key),
            )


def test_gain_cache_is_exactness_neutral():
    """max_gain_many with the epoch cache returns the same results as direct
    (uncached) evaluation across a mutation sequence, and actually hits."""
    rng = np.random.default_rng(9)
    hg, state = _random_state(rng)
    n = state.pl.num_partitions
    pairs = [(s, d) for s in range(n) for d in range(n) if s != d]
    flags.reset()
    first = state.max_gain_many(pairs)
    again = state.max_gain_many(pairs)  # all epochs unchanged -> all hits
    assert state.stats["gain_cache_hits"] >= len(pairs)
    for key in pairs:
        _assert_pair_equal(first[key], again[key], key)
        _assert_pair_equal(
            _lmbr_max_gain_reference(state, *key), again[key], key
        )
    # a move must invalidate exactly through the epochs: results stay
    # correct (vs oracle) after mutation, whether served cached or fresh
    dest = 0
    items = np.flatnonzero(~state.pl.member[dest])[:2]
    state.apply_move(dest, items)
    node_ptr, node_edges = hg.incidence()
    touched = np.unique(np.concatenate(
        [node_edges[node_ptr[v]: node_ptr[v + 1]] for v in items]
    ))
    state.recompute_edges(touched)
    post = state.max_gain_many(pairs)
    for key in pairs:
        _assert_pair_equal(
            _lmbr_max_gain_reference(state, *key), post[key], key
        )


def test_full_lmbr_bit_identical_across_engines():
    """End-to-end: reference peel (cache off) and vectorized peel (cache on
    and off) produce the same placement, bit for bit."""
    wl = random_workload(num_items=120, num_queries=260, density=5, seed=2)
    hg = wl.hypergraph
    flags.set_variant("peelreference+lmbrcache0")
    try:
        ref = lmbr(hg, 9, 25, seed=0)
    finally:
        flags.reset()
    flags.set_variant("lmbrcache0")
    try:
        nocache = lmbr(hg, 9, 25, seed=0)
    finally:
        flags.reset()
    vec = lmbr(hg, 9, 25, seed=0)
    np.testing.assert_array_equal(ref.member, vec.member)
    np.testing.assert_array_equal(ref.member, nocache.member)
    assert vec.stats["moves"] == ref.stats["moves"]
    assert vec.stats["peel"] == "vector" and ref.stats["peel"] == "reference"


def test_lmbr_warm_start_unchanged():
    """The move engine preserves the warm-start (`initial`) contract."""
    wl = random_workload(num_items=80, num_queries=150, density=5, seed=6)
    hg = wl.hypergraph
    assign = hpa_partition(hg, 8, 20, seed=0, nruns=2)
    pl0 = _assign_to_placement(hg, assign, 8, 20)
    out = lmbr(hg, 8, 20, seed=0, initial=pl0)
    # warm start only adds copies: the initial layout survives
    assert (out.member[pl0.member]).all()


def test_variant_validation_errors():
    """set_variant rejects unknown backends/components instead of silently
    accepting them."""
    try:
        for bad in ("peelbogus", "spanbogus", "routerbalX", "driftwx",
                    "nonsense"):
            with pytest.raises(ValueError):
                flags.set_variant(bad)
    finally:
        flags.reset()


@pytest.mark.parametrize("spec", ["peelauto", "peelth64"])
def test_retired_peel_variants_rejected(spec):
    """The size-dispatched hybrid peel and its threshold are gone: their
    variant strings fail loudly instead of being ignored."""
    try:
        with pytest.raises(ValueError):
            flags.set_variant(spec)
        assert flags.FLAGS["lmbr_peel"] == "vector"
    finally:
        flags.reset()


def test_variant_roundtrip_online_knobs():
    try:
        flags.set_variant("peelreference+routerbal1+routermb512"
                          "+driftw256+driftth1.5")
        assert flags.FLAGS["lmbr_peel"] == "reference"
        assert flags.FLAGS["router_balance"] is True
        assert flags.FLAGS["router_microbatch"] == 512
        assert flags.FLAGS["drift_window"] == 256
        assert flags.FLAGS["drift_threshold"] == 1.5
    finally:
        flags.reset()
