"""Pallas TPU kernel for the span-engine gain matrix (replica selection).

One greedy set-cover round needs, for every still-active query e and every
partition p, the popcount of ``codes[e, p, :] & rem[e, :]`` — how many still
uncovered pins of e partition p stores.  That masked popcount-reduce is the
span engine's only O(A*N*W) operation, and as a plain jitted op it writes
the (A, N, W) masked intermediate back to HBM before reducing.  Fusing
mask + popcount + word-reduce into one VMEM-tiled kernel streams ``codes``
through VMEM exactly once and emits only the (A, N) gain tile.

Layout: the engine's uint64 words arrive pre-split into uint32 lanes and
transposed to (A, W2, N), so the partition axis — the long one — lies on the
128-wide lane dimension and the word axis W2 (= 2*ceil(|q|/64), typically
2-8) rides the sublanes and reduces in-register.

Grid: (A / block_a, N / block_n).  Tiles are independent (a pure map), so
both grid axes are parallel.  Integer kernel: results are bit-exact against
the numpy oracle, which the backend-equivalence tests enforce.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _span_gain_kernel(codes_ref, rem_ref, out_ref):
    c = codes_ref[...]                    # (BA, W2, BN) uint32
    r = rem_ref[...]                      # (BA, W2) uint32
    masked = jnp.bitwise_and(c, r[:, :, None])
    out_ref[...] = lax.population_count(masked).astype(jnp.int32).sum(axis=1)


@functools.partial(jax.jit, static_argnames=("block_a", "block_n", "interpret"))
def span_gain(
    codes32: jax.Array,   # (A, W2, N) uint32 — word-major packed membership
    rem32: jax.Array,     # (A, W2) uint32 — still-uncovered pin masks
    *,
    block_a: int = 8,
    block_n: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Gain matrix (A, N) int32.  A must divide block_a, N block_n (callers
    zero-pad; zero words contribute zero gain, so padding is inert)."""
    a, w2, n = codes32.shape
    if a % block_a or n % block_n:
        raise ValueError("A / N must be multiples of the block sizes")
    return pl.pallas_call(
        _span_gain_kernel,
        grid=(a // block_a, n // block_n),
        in_specs=[
            pl.BlockSpec((block_a, w2, block_n), lambda ia, jn: (ia, 0, jn)),
            pl.BlockSpec((block_a, w2), lambda ia, jn: (ia, 0)),
        ],
        out_specs=pl.BlockSpec((block_a, block_n), lambda ia, jn: (ia, jn)),
        out_shape=jax.ShapeDtypeStruct((a, n), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(codes32, rem32)
