"""Compile rehearsals: the main path's device programs, compiled for a
described (not attached) TPU v5e.

Interpret mode runs a kernel body on the CPU but never sees the chip
compiler's rules (block tiling, VMEM budget, what Mosaic can lower), so
each kernel of the fit -> serve path is compiled here at the shapes the
engine emits: the span-gain kernel at the serving microbatch width, the
cover round loop at the largest bucket `chip_smoke.py`'s 75k-query fit over
256 partitions uploads, and the lockstep-peel kernel at a small and the
largest (K, U) class the LMBR dispatcher densifies; and the span-gain
kernel and round loop at the shapes of ``tpchcol40-fit``'s wide buckets
over 40 partitions.  Each compile must contain the
Pallas custom call (kernels) and fit one chip's 16 GiB of HBM.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and pytest-xdist imports this file
in every worker.  Keep these tests in this one file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device cannot be read back from the
    # persistent cache without the chip, so keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _arg(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _check(compiled, *, kernel: bool):
    if kernel:
        assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, mem
    return mem


@pytest.mark.parametrize("w2", [2, 8])
def test_span_gain_compiles(one_chip, w2):
    """The serving microbatch (384 queries, pow2-padded to 512) over 256
    partitions, single-word (W2=2) and four-word (W2=8) queries."""
    from repro.kernels.span_gain.kernel import span_gain

    a, n = 512, 256
    compiled = span_gain.lower(
        _arg(one_chip, (a, w2, n), jnp.uint32),
        _arg(one_chip, (a, w2), jnp.uint32),
    ).compile()
    _check(compiled, kernel=True)


@pytest.mark.parametrize("w2", [2, 22, 24])
def test_span_gain_compiles_tpch_columnar(one_chip, w2):
    """``tpchcol40-fit``'s gain rounds over 40 partitions (the lane axis
    padded to 128), each a block of ``BLOCK_ROWS`` queries: its single-word
    buckets, its widest (Q9's 673 items, 11 words) and one word past it."""
    from repro.kernels.span_gain.kernel import span_gain
    from repro.kernels.span_gain.ops import BLOCK_ROWS

    compiled = span_gain.lower(
        _arg(one_chip, (BLOCK_ROWS, w2, 128), jnp.uint32),
        _arg(one_chip, (BLOCK_ROWS, w2), jnp.uint32),
    ).compile()
    _check(compiled, kernel=True)


def test_cover_round_loop_compiles_tpch_columnar(one_chip):
    """``tpchcol40-fit``'s one bucket past ``span_round_threshold``: some
    730 eight-word queries (Q8, Q18, Q19, Q21) over 40 partitions, padded
    to 1024, every greedy round on the device."""
    from repro.core.setcover import _round_loop_fn

    B, N, W2, Rmax = 1024, 40, 16, 40
    compiled = _round_loop_fn(B, N, W2, Rmax).lower(
        _arg(one_chip, (W2, B, N), jnp.uint32),
        _arg(one_chip, (W2, B), jnp.uint32),
    ).compile()
    _check(compiled, kernel=False)


def test_cover_round_loop_compiles(one_chip):
    """The initial cover of a 75k-query fit: one single-word bucket padded
    to 131072 queries x 256 partitions, word-major (W2, B, N) layout.  The
    packed codes are 256 MiB; the loop's temporaries must stay below that
    (a per-row gather once made them 64x the codes at B=1024)."""
    from repro.core.setcover import _round_loop_fn

    B, N, W2, Rmax = 131072, 256, 2, 64
    fn = _round_loop_fn(B, N, W2, Rmax)
    compiled = fn.lower(
        _arg(one_chip, (W2, B, N), jnp.uint32),
        _arg(one_chip, (W2, B), jnp.uint32),
    ).compile()
    mem = _check(compiled, kernel=False)
    assert mem.temp_size_in_bytes < W2 * B * N * 4, mem


@pytest.mark.parametrize("shape", [(1 << 15, 8, 128), (1, 2048, 2048)],
                         ids=["small-class", "largest-tile"])
def test_lockstep_peel_compiles(one_chip, shape):
    """The smallest dense class (u2, k2 = 4 pad to one (8, 128) tile) at
    the widest pair batch one call takes (its slot counts must fit SMEM),
    and the largest tile the dispatcher densifies (u2 * k2 = 2^22 f32,
    16 MiB) that must fit the kernel's VMEM budget."""
    from repro.kernels.lockstep_peel.kernel import lockstep_peel
    from repro.kernels.lockstep_peel.ops import _KERNEL_MAX_PAIRS

    assert shape[0] <= _KERNEL_MAX_PAIRS

    g, k, u = shape
    compiled = lockstep_peel.lower(
        _arg(one_chip, (g, k, u), jnp.float32),
        _arg(one_chip, (g, k, 1), jnp.float32),
        _arg(one_chip, (g, 1, u), jnp.float32),
        _arg(one_chip, (g,), jnp.int32),
    ).compile()
    _check(compiled, kernel=True)
