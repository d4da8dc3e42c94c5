"""Backend-equivalence tests for the span_gain kernel package.

The gain matrix is integer popcount math, so every backend — numpy oracle,
jitted jnp, Pallas kernel in interpret mode — must agree EXACTLY, including
over the padding seams (query-batch block pad, partition-axis 128 pad,
uint64 -> uint32 lane split).  ``"pallas"`` is the compiled kernel: on a
TPU it must match too, anywhere else it must refuse to run rather than
quietly interpret."""

import jax
import numpy as np
import pytest

from repro import flags
from repro.kernels.span_gain.ops import span_gains
from repro.kernels.span_gain.ref import span_gain_ref

ON_TPU = jax.default_backend() == "tpu"

# (A, N, W): odd batch sizes straddle the block pad, A > 256 spans two
# blocks, N > 128 straddles the lane pad, W > 1 exercises the multi-word
# reduce
SHAPES = [(1, 1, 1), (3, 5, 2), (17, 35, 2), (40, 7, 6), (64, 130, 1),
          (9, 129, 3), (300, 40, 11)]


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("force", ["numpy", "jax", "interpret", "pallas"])
def test_backends_match_oracle(shape, force):
    A, N, W = shape
    rng = np.random.default_rng(A * 1000 + N * 10 + W)
    codes = rng.integers(0, 2**63, size=(A, N, W), dtype=np.uint64)
    rem = rng.integers(0, 2**63, size=(A, W), dtype=np.uint64)
    # exercise the full uint64 range incl. the sign bit of the int64 view
    codes[0, 0, 0] = np.uint64(0xFFFFFFFFFFFFFFFF)
    rem[0, 0] = np.uint64(0xFFFFFFFFFFFFFFFF)
    want = span_gain_ref(codes, rem)
    if force == "pallas" and not ON_TPU:
        with pytest.raises(RuntimeError, match="compiled TPU kernel"):
            span_gains(codes, rem, force=force)
        return
    got = span_gains(codes, rem, force=force)
    assert got.dtype == np.int64
    np.testing.assert_array_equal(got, want)


def test_gain_rounds_compile_one_shape_per_word_width(monkeypatch):
    """Every accelerated call runs in (BLOCK_ROWS, N, W2) blocks, whatever
    the round's active count: a shrinking active set compiles nothing
    new."""
    from repro.kernels.span_gain import ops

    seen = []

    def record(c32, r32):
        seen.append((c32.shape, r32.shape))
        return np.asarray(ops.span_gain_jnp(c32, r32))

    monkeypatch.setattr(ops, "_JNP_GAINS", record)
    rng = np.random.default_rng(3)
    for a in (1, 100, 256, 257, 700):
        codes = rng.integers(0, 2**63, size=(a, 40, 3), dtype=np.uint64)
        rem = rng.integers(0, 2**63, size=(a, 3), dtype=np.uint64)
        got = span_gains(codes, rem, force="jax")
        np.testing.assert_array_equal(got, span_gain_ref(codes, rem))
    assert set(seen) == {((ops.BLOCK_ROWS, 40, 6), (ops.BLOCK_ROWS, 6))}
    assert len(seen) == 1 + 1 + 1 + 2 + 3


def test_kernel_interpret_matches_jnp_ref():
    """The Pallas kernel itself (not just the dispatcher) against the jnp
    reference it shares lanes with, at an already-padded shape."""
    from repro.kernels.span_gain.kernel import span_gain
    from repro.kernels.span_gain.ref import span_gain_jnp

    rng = np.random.default_rng(7)
    A, W2, N = 16, 4, 256
    c32 = rng.integers(0, 2**32, size=(A, N, W2), dtype=np.uint64).astype(
        np.uint32
    )
    r32 = rng.integers(0, 2**32, size=(A, W2), dtype=np.uint64).astype(
        np.uint32
    )
    want = np.asarray(span_gain_jnp(c32, r32))
    got = np.asarray(
        span_gain(
            np.ascontiguousarray(c32.transpose(0, 2, 1)), r32, interpret=True
        )
    )
    np.testing.assert_array_equal(got, want)


def test_zero_rem_zero_gain():
    codes = np.full((4, 3, 2), 0xFFFFFFFFFFFFFFFF, dtype=np.uint64)
    rem = np.zeros((4, 2), dtype=np.uint64)
    for force in ("numpy", "jax", "interpret"):
        assert (span_gains(codes, rem, force=force) == 0).all()


def test_engine_per_bucket_dispatch_is_exact():
    """batched_cover_csr under forced thresholds: all-numpy, all-accelerated
    (threshold 0) and the pinned kernel must produce identical covers, and
    the per-backend gain-round counters must show the pinned backend ran."""
    from repro.core.hypergraph import Hypergraph
    from repro.core.setcover import ENGINE_COUNTERS, batched_cover_csr

    rng = np.random.default_rng(11)
    num_items, n_parts = 90, 6
    member = rng.random((n_parts, num_items)) < 0.35
    member[0] |= ~member.any(axis=0)
    edges = [
        rng.choice(num_items, size=int(rng.integers(1, 80)), replace=False)
        for _ in range(25)
    ]
    hg = Hypergraph.from_edges(edges, num_nodes=num_items)

    def run():
        cov = batched_cover_csr(hg.edge_ptr, hg.edge_nodes, member,
                                with_pin_parts=True)
        return cov.spans, cov.cover_ptr, cov.cover_parts, cov.pin_parts

    flags.FLAGS["span_backend"] = "numpy"
    try:
        want = run()
    finally:
        flags.reset()
    kernel = "pallas" if ON_TPU else "interpret"
    for setup, ran in (
        (dict(span_backend="auto", span_dispatch_threshold=0),
         "pallas" if ON_TPU else "jax"),
        (dict(span_backend="jax"), "jax"),
        (dict(span_backend=kernel), kernel),
    ):
        flags.FLAGS.update(setup)
        before = dict(ENGINE_COUNTERS)
        try:
            got = run()
        finally:
            flags.reset()
        key = f"{ran}_gain_rounds"
        assert ENGINE_COUNTERS[key] > before[key], setup
        assert ENGINE_COUNTERS["numpy_gain_rounds"] == \
            before["numpy_gain_rounds"], setup
        for w, g in zip(want, got):
            np.testing.assert_array_equal(g, w, err_msg=str(setup))


def test_whole_round_device_loop_is_exact():
    """The jitted whole-round cover loop (``span_round_backend="device"``)
    must reproduce the per-round host loop bit-exactly — same covers, same
    pin_parts, same spans — and the auto threshold must route big buckets
    to it (counter check) without changing results."""
    from repro.core.hypergraph import Hypergraph
    from repro.core.setcover import ENGINE_COUNTERS, batched_cover_csr

    rng = np.random.default_rng(23)
    num_items, n_parts = 140, 9
    member = rng.random((n_parts, num_items)) < 0.3
    member[0] |= ~member.any(axis=0)
    edges = [
        rng.choice(num_items, size=int(rng.integers(2, 90)), replace=False)
        for _ in range(40)
    ]
    hg = Hypergraph.from_edges(edges, num_nodes=num_items)

    def run():
        cov = batched_cover_csr(hg.edge_ptr, hg.edge_nodes, member,
                                with_pin_parts=True)
        return cov.spans, cov.cover_ptr, cov.cover_parts, cov.pin_parts

    flags.FLAGS["span_round_backend"] = "numpy"
    try:
        want = run()
    finally:
        flags.reset()
    for setup in (
        dict(span_round_backend="device"),
        dict(span_round_backend="auto", span_round_threshold=0),
    ):
        flags.FLAGS.update(setup)
        before = ENGINE_COUNTERS["device_buckets"]
        try:
            got = run()
        finally:
            flags.reset()
        assert ENGINE_COUNTERS["device_buckets"] > before, setup
        for w, g in zip(want, got):
            np.testing.assert_array_equal(g, w, err_msg=str(setup))
