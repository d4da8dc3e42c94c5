"""LMBR move-engine exactness: vectorized batched peel vs pure-Python oracle.

The contract under test (tentpole of PR 3): `_lmbr_gain_batch` /
`_lmbr_max_gain_vectorized` reproduce `_lmbr_max_gain_reference`
BIT-IDENTICALLY — same gain floats, same item subsets, same tie-breaks
(ascending edge id in the projection scan, lowest item id on density ties) —
on weighted instances, free pins, and zero-capacity destinations; and the
epoch-keyed gain cache never changes any result.
"""

import heapq

import numpy as np
import pytest

from repro import flags
from repro.core import algorithms, hpa_partition, lmbr, random_workload
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


def _twin_edges(rng, num_items, num_edges, num_parts):
    """Twin-heavy hyperedges, like a column store's month blocks: items in
    blocks of 2-6 scattered ids, each block on one partition and read only
    whole, so a block's items share one edge set (and are twins in every
    projection that keeps them all); mixed with random edges over all items,
    which split some blocks.  Edge weights come from a few non-integer
    values, their one-ulp neighbours and a tiny one, so degrees of
    different classes tie exactly or nearly."""
    perm = rng.permutation(num_items)
    blocks, lo = [], 0
    while lo < num_items:
        size = int(rng.integers(2, 7))
        blocks.append(np.sort(perm[lo: lo + size]))
        lo += size
    assign = np.empty(num_items, dtype=np.int64)
    for b in blocks:
        assign[b] = rng.integers(num_parts)
    edges = []
    for _ in range(num_edges):
        if rng.random() < 0.8:
            pick = rng.choice(len(blocks), size=int(rng.integers(1, 4)),
                              replace=False)
            edges.append(np.concatenate([blocks[i] for i in pick]))
        else:
            edges.append(rng.choice(num_items, size=int(rng.integers(2, 6)),
                                    replace=False))
    base = np.array([3e-10, 0.3, 0.7, 1.1, 2.35])
    w = rng.choice(base, size=num_edges)
    w = np.where(rng.random(num_edges) < 0.3,
                 np.nextafter(w, rng.choice([0.0, 10.0], size=num_edges)), w)
    return edges, w, assign


def _random_state(rng, *, weighted_nodes=False, weighted_edges=False,
                  twins=False, num_items=60, num_edges=150, num_parts=8,
                  capacity=40.0):
    """A random placement state: random hyperedges over `num_items` items,
    every item on >= 1 random partition plus random extra replicas.  With
    ``twins`` the edges, their weights and the first copies come from
    ``_twin_edges``, and node weights are always drawn."""
    assign = None
    if twins:
        edges, edge_w, assign = _twin_edges(rng, num_items, num_edges,
                                            num_parts)
        node_w = rng.uniform(0.5, 4.0, size=num_items)
    else:
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
    if assign is None:
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


# instance flavours: the four weightings of plain random edges, and the
# twin-heavy instance (non-integer node and edge weights)
FLAVOURS = [
    pytest.param(n, e, False, id=f"{n}-{e}")
    for n, e in [(False, False), (True, False), (False, True), (True, True)]
] + [pytest.param(True, True, True, id="twins")]


def _flavour_rng(weighted_nodes, weighted_edges, twins):
    return np.random.default_rng(
        11 + 2 * weighted_nodes + weighted_edges + 100 * twins)


@pytest.mark.parametrize("weighted_nodes,weighted_edges,twins", FLAVOURS)
def test_peel_matches_oracle_randomized(weighted_nodes, weighted_edges,
                                        twins):
    """Property-style: on randomized (optionally weighted or twin-heavy)
    instances, every (src, dest) pair peels to the oracle's exact (gain,
    items)."""
    rng = _flavour_rng(weighted_nodes, weighted_edges, twins)
    for trial in range(4):
        hg, state = _random_state(
            rng, weighted_nodes=weighted_nodes, weighted_edges=weighted_edges,
            twins=twins,
        )
        n = state.pl.num_partitions
        pairs = [(s, d) for s in range(n) for d in range(n) if s != d]
        batch = _lmbr_gain_batch(state, pairs)
        for key in pairs:
            ref = _lmbr_max_gain_reference(state, *key)
            _assert_pair_equal(ref, batch[key], (trial, key))


@pytest.mark.parametrize("batch", ["one", "all"])
@pytest.mark.parametrize("weighted_nodes,weighted_edges,twins", FLAVOURS)
def test_scalar_peel_matches_lockstep_bytes(weighted_nodes, weighted_edges,
                                            twins, batch):
    """The scalar heap peel's trajectories, selected under any free space,
    give the lockstep peel's exact (gain, items), pair by pair and over all
    n(n-1) pairs at once; its pool and fingerprint are the projection's."""
    rng = _flavour_rng(weighted_nodes, weighted_edges, twins)
    for trial in range(4):
        hg, state = _random_state(
            rng, weighted_nodes=weighted_nodes, weighted_edges=weighted_edges,
            twins=twins,
        )
        n = state.pl.num_partitions
        pairs = [(s, d) for s in range(n) for d in range(n) if s != d]
        _, proj = _lmbr_project(state, pairs)
        assert proj
        batches = [[p] for p in proj] if batch == "one" else [proj]
        for b in batches:
            scalar, _ = _lmbr_peel_scalar(state, b)
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


def _item_peel(state, proj):
    """The per-item heap peel: one heap entry (degree, column) per pool
    item, the same float operations as the twin-class peel.  Returns
    (out, items) in ``_lmbr_peel_scalar``'s form, each item its own class."""
    node_w = state.hg.node_weights
    out, n_items = {}, 0
    for key, _, we_a, uniq, loc_a, cedge_a, pin_cnt, totw in proj:
        we, nw = we_a.tolist(), node_w[uniq].tolist()
        n = len(nw)
        n_items += n
        deg = [0.0] * n
        inc = [[] for _ in range(n)]
        pins = [[] for _ in we]
        for c, e in zip(loc_a.tolist(), cedge_a.tolist()):
            deg[c] += we[e]
            inc[c].append(e)
            pins[e].append(c)
        benefit = we[0]
        for w in we[1:]:
            benefit += w
        heap = [(d, c) for c, d in enumerate(deg)]
        heapq.heapify(heap)
        alive, edge_alive = [True] * n, [True] * len(we)
        rj, rtot, rben = [], [], []
        while benefit > 1e-12 and n:
            rtot.append(totw)
            rben.append(benefit)
            d, j = heapq.heappop(heap)
            while not (alive[j] and d == deg[j]):
                d, j = heapq.heappop(heap)
            rj.append(j)
            alive[j] = False
            n -= 1
            totw -= nw[j]
            for e in inc[j]:
                if edge_alive[e]:
                    edge_alive[e] = False
                    benefit -= we[e]
                    for u in pins[e]:
                        if alive[u]:
                            deg[u] -= we[e]
                            heapq.heappush(heap, (deg[u], u))
        traj = (np.array(rj), np.array(rtot), np.array(rben)) if rj else None
        out[key] = (uniq, (we_a, uniq, loc_a, cedge_a, pin_cnt), traj)
    return out, n_items


def test_twin_peel_trajectories_are_the_item_peels():
    """On the twin-heavy instance every trajectory of the twin-class peel,
    peeled order included, is byte for byte the per-item peel's."""
    rng = _flavour_rng(True, True, True)
    for trial in range(4):
        hg, state = _random_state(rng, weighted_nodes=True,
                                  weighted_edges=True, twins=True)
        n = state.pl.num_partitions
        _, proj = _lmbr_project(
            state, [(s, d) for s in range(n) for d in range(n) if s != d])
        got, classes = _lmbr_peel_scalar(state, proj)
        want, items = _item_peel(state, proj)
        assert classes < items
        for p in proj:
            a, b = got[p[0]][2], want[p[0]][2]
            assert (a is None) == (b is None), (trial, p[0])
            for x, y in zip(a or (), b or ()):
                assert x.tobytes() == y.tobytes(), (trial, p[0])


@pytest.mark.parametrize("twins", [False, True])
def test_peel_counts_twin_classes(twins, monkeypatch):
    """``peel_classes`` counts the heap entries the scalar peel keeps: one
    per item where no two pool items share an edge set (every edge holds
    two items, so a kept edge has one costly pin), fewer on the twin-heavy
    instance.  Either way the fit is the reference peel's plan with its
    gain calls and moves, and has the per-item peel's cache hits (the
    reference's result-only cache entries hit only while no move
    intervened, so its hit count differs by design)."""
    rng = np.random.default_rng(23)
    if twins:
        hg = _random_state(rng, weighted_nodes=True, twins=True)[0]
    else:
        edges = [rng.choice(60, size=2, replace=False) for _ in range(150)]
        hg = Hypergraph.from_edges(edges, num_nodes=60,
                                   node_weights=rng.uniform(0.5, 4.0, 60),
                                   edge_weights=rng.uniform(0.1, 3.0, 150))
    fits = {}
    for spec in ("baseline", "peelreference"):
        flags.set_variant(spec)
        try:
            fits[spec] = lmbr(hg, 8, 40.0, seed=0)
        finally:
            flags.reset()
    monkeypatch.setattr(algorithms, "_lmbr_peel_scalar", _item_peel)
    fits["item"] = lmbr(hg, 8, 40.0, seed=0)
    st = fits["baseline"].stats
    assert st["peel_pairs"] > 0 and st["moves"] > 0
    assert st["peel_items"] == fits["item"].stats["peel_items"]
    if twins:
        assert st["peel_classes"] < st["peel_items"]
    else:
        assert st["peel_classes"] == st["peel_items"]
    for other, keys in (("peelreference", ("gain_calls", "moves")),
                        ("item", ("gain_calls", "moves", "gain_cache_hits",
                                  "gain_fp_hits"))):
        np.testing.assert_array_equal(fits["baseline"].member,
                                      fits[other].member, err_msg=other)
        for k in keys:
            assert st[k] == fits[other].stats[k], (other, k)


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
