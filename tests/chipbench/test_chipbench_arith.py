"""The benchmark's own arithmetic: roofline bytes, peaks, the generator
and the reference."""

import numpy as np
import pytest

from chipbench import peaks, roofline
from chipbench.gen import lists_to_csr, reference, tpch_snowflake

TPCH = dict(num_items=300, min_query=3, max_query=11, levels=3, degree=5,
            size_lo_gb=2.5e-05, size_hi_gb=28.0, fact_lo_gb=1.0,
            dim_hi_gb=0.5, fill=0.97, size_order_seed=0, capacity=100.0,
            target_min_partitions=20)


@pytest.mark.parametrize("shape,want", [
    # 4 * (512*2*256 codes + 512*2 rem + 512*256 gains)
    ((512, 2, 256), 4 * (262144 + 1024 + 131072)),
    # 4 * (4096*2*128 codes + 4096*2 rem + 4096*128 gains)
    ((4096, 2, 128), 4 * (1048576 + 8192 + 524288)),
])
def test_span_gain_bytes(shape, want):
    assert roofline.span_gain_bytes(*shape) == want


def test_span_gain_shape_from_hlo():
    # an op name as a v5e trace records it
    hlo = ("%span_gain.1 = s32[4096,128]{1,0:T(8,128)} custom-call(u32[4096"
           ",2,128]{2,1,0:T(2,128)} %codes32.1, u32[4096,2]{1,0:T(8,128)S(1)}"
           " %copy), custom_call_target=\"tpu_custom_call\"")
    k = roofline.KERNELS["span_gain"]
    assert k.matches(hlo) and k.shapes(hlo) == (4096, 2, 128)
    assert not k.matches("%copy = u32[4096,2]{1,0} copy(u32[4096,2] %rem)")


def test_peaks_lookup():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks("TPU v99")


@pytest.mark.parametrize("stream", [1, 2**40 + 3])
def test_generators_deterministic_by_seed(stream):
    gen, cfg = tpch_snowflake, TPCH
    a, b, c, d = (lists_to_csr(gen.query_lists(cfg, seed, s, 500))
                  for seed, s in ((2**31 + 11, stream), (2**31 + 11, stream),
                                  (2**31 + 12, stream),
                                  (2**31 + 11, stream + 1)))
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[1], c[1])
    assert not np.array_equal(a[1], d[1])
    assert np.array_equal(gen.node_weights(cfg, 5), gen.node_weights(cfg, 5))
    ptr, nodes = a
    for i in range(len(ptr) - 1):
        q = nodes[ptr[i]: ptr[i + 1]]
        assert len(q) >= 1 and np.array_equal(q, np.unique(q))
    assert nodes.min() >= 0 and nodes.max() < cfg["num_items"]


def test_tpch_weights_fill_the_minimum_partitions():
    w = tpch_snowflake.node_weights(TPCH, 3)
    assert w.sum() == pytest.approx(0.97 * 20 * 100.0)
    assert (w > 0).all()


@pytest.mark.parametrize("num_items", [300, 2000])
def test_tpch_sizes_one_set_in_the_deployments_order(num_items):
    cfg = dict(TPCH, num_items=num_items)
    a = tpch_snowflake.node_weights(cfg, 2**31 + 5)
    assert np.array_equal(a, tpch_snowflake.node_weights(cfg, 2**31 + 6))
    b = tpch_snowflake.node_weights(dict(cfg, size_order_seed=1), 5)
    assert not np.array_equal(a, b)
    assert np.array_equal(np.sort(a), np.sort(b))
    # the range the paper states, to within the last scale's fraction of 1%
    assert a.min() == pytest.approx(25e-6, rel=0.01)
    assert a.max() == pytest.approx(28.0, rel=0.01)


def test_reference_cover_and_tie_break():
    # items 0..3; partition 0 holds {0,1}, 1 holds {2,3}, 2 holds {0,1}
    member = np.zeros((3, 4), dtype=bool)
    member[0, [0, 1]] = member[1, [2, 3]] = member[2, [0, 1]] = True
    q = np.array([0, 1, 2])
    # partitions 0 and 2 tie on the first round: the lowest id wins
    assert reference.greedy_cover(q, member.T) == [0, 1]
    member[:, 3] = False
    with pytest.raises(ValueError):
        reference.greedy_cover(np.array([3]), member.T)


def test_reference_placement_guarantees():
    member = np.array([[1, 1, 0], [0, 1, 0]], dtype=bool)
    w = np.array([2.0, 1.5, 0.5])
    assert reference.over_capacity(member, w, 3.0) == 1
    assert reference.over_capacity(member, w, 3.5) == 0
    assert reference.unplaced(member, w) == 1
    assert reference.unplaced(member, np.array([2.0, 1.5, 0.0])) == 0
