"""Backend equivalence for the lockstep_peel kernel package and the
device-resident LMBR dispatch.

Contract: every peel backend — the f64 numpy oracle, the jitted f32 jnp
lockstep, the Pallas kernel (compiled on TPU, interpreted elsewhere) —
emits BIT-IDENTICAL trajectories on the integer-valued-weight domain the
LMBR dispatcher enforces, and the full fit under
``lmbr_peel="device"|"interpret"`` reproduces the vector engine's placement
exactly (same members, same covers, same float-tie handling).  Off TPU,
``"pallas"`` refuses to run instead of quietly interpreting."""

import jax
import numpy as np
import pytest

from repro import flags
from repro.core import lmbr, random_workload
from repro.core.workloads import ispd_like_workload
from repro.kernels.lockstep_peel.ops import lockstep_peel
from repro.kernels.lockstep_peel.ref import lockstep_peel_ref

ON_TPU = jax.default_backend() == "tpu"


# ------------------------------------------------------------- unit level
def _rand_instance(rng, G, K, U):
    """Random integer-weight peel batch; padding rules of the dispatcher:
    incidence / weights zero beyond each pair's ``nvalid`` prefix."""
    inc = np.zeros((G, K, U), dtype=np.float64)
    nvalid = rng.integers(1, U + 1, size=G).astype(np.int64)
    for g in range(G):
        u = int(nvalid[g])
        for k in range(K):
            pins = np.unique(rng.integers(0, u, size=int(rng.integers(1, 5))))
            inc[g, k, pins] = 1.0
    we = rng.integers(1, 9, size=(G, K)).astype(np.float64)
    nodew = np.zeros((G, U), dtype=np.float64)
    for g in range(G):
        nodew[g, : nvalid[g]] = rng.integers(1, 5, size=int(nvalid[g]))
    return inc, we, nodew, nvalid


# odd shapes straddle the kernel's (8, 128) tile pad; U=1 and K=1 are the
# degenerate single-slot cells
SHAPES = [(1, 1, 1), (3, 4, 7), (7, 13, 21), (5, 9, 130), (2, 17, 3)]


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("force", ["numpy", "jax", "interpret", "pallas"])
def test_backends_match_oracle(shape, force):
    G, K, U = shape
    rng = np.random.default_rng(G * 1000 + K * 10 + U)
    inc, we, nodew, nvalid = _rand_instance(rng, G, K, U)
    want = lockstep_peel_ref(inc, we, nodew, nvalid)
    if force == "pallas" and not ON_TPU:
        with pytest.raises(RuntimeError, match="compiled TPU kernel"):
            lockstep_peel(inc, we, nodew, nvalid, force=force)
        return
    got = lockstep_peel(inc, we, nodew, nvalid, force=force)
    for w, g, name in zip(want, got, ("peel", "rtot", "rben")):
        assert g.shape == w.shape, (force, name)
        np.testing.assert_array_equal(g, w, err_msg=f"{force}:{name}")


def test_trajectory_semantics_reference():
    """One hand-checked cell: two edges sharing an item.  Initial degrees
    are (2, 5, 3); peeling item 0 kills edge 0, leaving items 1 and 2 tied
    at degree 3 — the tie goes to the LOWEST slot (item 1), whose peel
    kills edge 1 and ends the pair.  Head-of-round (pool weight, benefit)
    snapshots land in the trajectory rows."""
    inc = np.zeros((1, 2, 3))
    inc[0, 0, [0, 1]] = 1.0   # edge 0 over items {0, 1}, weight 2
    inc[0, 1, [1, 2]] = 1.0   # edge 1 over items {1, 2}, weight 3
    we = np.array([[2.0, 3.0]])
    nodew = np.array([[1.0, 1.0, 1.0]])
    peel, rtot, rben = lockstep_peel_ref(inc, we, nodew, np.array([3]))
    np.testing.assert_array_equal(peel[0], [0, 1, -1])
    np.testing.assert_array_equal(rtot[0], [3.0, 2.0, 0.0])
    np.testing.assert_array_equal(rben[0], [5.0, 3.0, 0.0])


# ------------------------------------------------------------- fit level
def _fit_members(wl, n, cap, max_moves, setups):
    members = {}
    for name, setup in setups.items():
        flags.FLAGS.update(setup)
        try:
            pl = lmbr(wl.hypergraph, n, cap, seed=0, max_moves=max_moves)
        finally:
            flags.reset()
        if setup["lmbr_peel"] in ("device", "pallas", "interpret"):
            # a pinned dense peel ran, and the exactness gate skipped none
            assert pl.stats["dense_peel_calls"] > 0, name
            assert pl.stats["dense_peel_skips"] == 0, name
        members[name] = (pl.member.copy(), pl.stats["peel"])
    return members


@pytest.mark.parametrize("tier", ["fig6", "fig9", "lmbr-stress"])
def test_full_fit_backend_bit_identity(tier):
    """Placements (hence covers and every float tie-break) are identical
    across vector / device / Pallas-kernel peel backends and both cache
    granularities on quick versions of the benchmark tiers."""
    if tier == "fig6":
        wl, n, cap, moves = random_workload(60, 200, seed=3), 6, 22.0, 24
    elif tier == "fig9":
        wl, n, cap, moves = ispd_like_workload(160, 200, seed=1), 6, 40.0, 24
    else:
        wl, n, cap, moves = random_workload(
            90, 260, min_query=3, max_query=9, seed=5), 8, 18.0, 24
    setups = {
        "vector": dict(lmbr_peel="vector"),
        "device": dict(lmbr_peel="device"),
        "partition-epochs": dict(lmbr_peel="vector", lmbr_epochs="partition"),
    }
    if tier == "fig6":  # interpret-mode Pallas is slow; one tier covers it
        setups["pallas"] = dict(lmbr_peel="pallas" if ON_TPU else "interpret")
    members = _fit_members(wl, n, cap, moves, setups)
    want, _ = members["vector"]
    for name, (got, _) in members.items():
        np.testing.assert_array_equal(got, want, err_msg=name)


def test_device_peel_falls_back_on_float_weights():
    """Non-integer weights are outside the f32-exact domain: the dispatcher
    must keep the flat engine and count every skipped batch (stats record
    the requested backend, results stay bit-identical to vector)."""
    rng = np.random.default_rng(9)
    wl = random_workload(50, 150, seed=2)
    hg = wl.hypergraph
    hg.node_weights = rng.uniform(0.5, 2.0, size=hg.num_nodes)
    flags.FLAGS["lmbr_peel"] = "device"
    try:
        dev = lmbr(hg, 5, hg.total_node_weight() / 3, seed=0, max_moves=16)
    finally:
        flags.reset()
    vec = lmbr(hg, 5, hg.total_node_weight() / 3, seed=0, max_moves=16)
    np.testing.assert_array_equal(dev.member, vec.member)
    assert dev.stats["int_exact"] is False
    assert dev.stats["dense_peel_calls"] == 0
    assert dev.stats["dense_peel_skips"] > 0


# ------------------------------------------------------------ flag surface
@pytest.mark.parametrize("spec,key,val", [
    ("spanroundnumpy", "span_round_backend", "numpy"),
    ("spanrounddevice", "span_round_backend", "device"),
    ("spanroundauto", "span_round_backend", "auto"),
    ("spanroundth12345", "span_round_threshold", 12345),
    ("peeldevice", "lmbr_peel", "device"),
    ("peelpallas", "lmbr_peel", "pallas"),
    ("peelinterpret", "lmbr_peel", "interpret"),
    ("spaninterpret", "span_backend", "interpret"),
    ("lmbrepochitem", "lmbr_epochs", "item"),
    ("lmbrepochpartition", "lmbr_epochs", "partition"),
])
def test_variant_spellings(spec, key, val):
    try:
        flags.set_variant(spec)
        assert flags.FLAGS[key] == val
    finally:
        flags.reset()


@pytest.mark.parametrize("spec", [
    "spanroundcuda", "peelfancy", "lmbrepochquery",
])
def test_variant_rejects_unknown_values(spec):
    with pytest.raises(ValueError):
        flags.set_variant(spec)
    flags.reset()
