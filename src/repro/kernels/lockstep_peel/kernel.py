"""Pallas TPU kernel for the dense lockstep LMBR peel.

One grid cell = one (src, dest) candidate pair.  The pair's (K, U)
incidence tile, edge weights and item weights live in VMEM for the WHOLE
peel — a `fori_loop` runs every round in-register, so the peel costs one
upload of the dense batch and one download of the trajectories instead of
a host round-trip per peeled item.

Per round (matching `ref.lockstep_peel_ref` bit-for-bit on the
integer-valued-weight domain the dispatcher guarantees):

  * a min-reduce over the (1, U) degree row, then a min-reduce of the slot
    ids holding that minimum, picks the peeled slot (+inf padding and
    first-minimum semantics give the oracle's lowest-item-id tie-break)
  * a one-hot masked lane reduce over the incidence tile flags edges losing
    a pin (edge death), a masked sublane reduce subtracts the dying edge
    weights from the degrees of their remaining items
  * head-of-round (pool weight, alive benefit) snapshots write into the
    trajectory rows via an iota==r select, so every store is static-shape

Every reduction is an elementwise product and a sum on the vector unit,
never a matmul: integer-valued f32 sums below 2^24 are exact in any order,
while the matrix unit may round f32 operands to bf16.

Trajectories only — the free-space-dependent (gain, items) selection is
host-side f64, shared with the gain-cache re-evaluation path.

Layout: U rides the 128-wide lane dimension, K the sublanes (f32 tiles are
(8, 128)-aligned; the ops dispatcher pads).  Edge weights arrive as a
(G, K, 1) column and item weights and trajectories as (G, 1, U) rows, so
every block's last two dims are tile-aligned or whole; the per-pair valid
slot count rides scalar prefetch (SMEM).  The grid axis is a pure map over
pairs, so it is parallel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# scoped-VMEM ceiling for the largest (K, U) cells (u2 * k2 = 2^22 f32 is a
# 16 MiB tile, double-buffered plus two same-size temporaries); v5e has
# 128 MiB of VMEM per core
_VMEM_LIMIT = 100 * 1024 * 1024


def _peel_kernel(nvalid_ref, inc_ref, we_ref, nodew_ref,
                 peel_ref, rtot_ref, rben_ref):
    inc = inc_ref[0]                      # (K, U) f32
    we = we_ref[0]                        # (K, 1) f32
    nodew = nodew_ref[0]                  # (1, U) f32
    nv = nvalid_ref[pl.program_id(0)]     # scalar int32 (SMEM)
    U = inc.shape[1]
    iota_u = lax.broadcasted_iota(jnp.int32, (1, U), 1)
    valid = iota_u < nv
    cand0 = jnp.where(valid, jnp.sum(we * inc, axis=0, keepdims=True),
                      jnp.inf)
    carry0 = (
        cand0,
        jnp.ones(we.shape, dtype=jnp.float32),      # alive-edge mask (K, 1)
        jnp.sum(we, axis=0, keepdims=True),         # benefit (1, 1)
        jnp.sum(nodew, axis=1, keepdims=True),      # pool weight (1, 1)
        jnp.full((1, 1), nv, dtype=jnp.int32),      # items left (1, 1)
        jnp.full((1, U), -1, dtype=jnp.int32),
        jnp.zeros((1, U), dtype=jnp.float32),
        jnp.zeros((1, U), dtype=jnp.float32),
    )

    def body(r, carry):
        cand, ealive, ben, totw, nal, peel, rtot, rben = carry
        act = (ben > 0.5) & (nal > 0)                         # (1, 1)
        here = (iota_u == r) & act
        rtot = jnp.where(here, totw, rtot)
        rben = jnp.where(here, ben, rben)
        cmin = jnp.min(cand, axis=1, keepdims=True)
        j = jnp.min(jnp.where(cand == cmin, iota_u, U), axis=1,
                    keepdims=True)                            # (1, 1)
        onehot = (iota_u == j) & act
        ohf = onehot.astype(jnp.float32)
        # (K, U) * (1, U) reduced over lanes -> (K, 1): edges hit by the peel
        hit = jnp.sum(inc * ohf, axis=1, keepdims=True)
        dying = jnp.where((ealive > 0.5) & (hit > 0.5), 1.0, 0.0)
        dw = we * dying
        ben = ben - jnp.sum(dw, axis=0, keepdims=True)
        cand = jnp.where(
            onehot, jnp.inf, cand - jnp.sum(dw * inc, axis=0, keepdims=True)
        )
        totw = totw - jnp.sum(nodew * ohf, axis=1, keepdims=True)
        nal = nal - act.astype(jnp.int32)
        peel = jnp.where(here, j, peel)
        return (cand, ealive * (1.0 - dying), ben, totw, nal, peel, rtot,
                rben)

    carry = lax.fori_loop(0, U, body, carry0)
    peel_ref[0] = carry[5]
    rtot_ref[0] = carry[6]
    rben_ref[0] = carry[7]


@functools.partial(jax.jit, static_argnames=("interpret",))
def lockstep_peel(
    inc32: jax.Array,     # (G, K, U) f32 incidence, zero-padded
    we32: jax.Array,      # (G, K, 1) f32 edge weights, zero-padded
    nodew32: jax.Array,   # (G, 1, U) f32 item weights, zero-padded
    nvalid: jax.Array,    # (G,) int32 valid item slots per pair
    *,
    interpret: bool = False,
):
    """Peel trajectories: peel (G, 1, U) int32, rtot/rben (G, 1, U) f32.
    K must be a multiple of 8 and U of 128 (the ops dispatcher pads;
    padding is inert — zero incidence, zero weights, +inf degrees)."""
    g, k, u = inc32.shape
    if k % 8 or u % 128:
        raise ValueError("K / U must be multiples of the (8, 128) f32 tile")
    row = pl.BlockSpec((1, 1, u), lambda i, nv: (i, 0, 0))
    return pl.pallas_call(
        _peel_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(g,),
            in_specs=[
                pl.BlockSpec((1, k, u), lambda i, nv: (i, 0, 0)),
                pl.BlockSpec((1, k, 1), lambda i, nv: (i, 0, 0)),
                row,
            ],
            out_specs=[row, row, row],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((g, 1, u), jnp.int32),
            jax.ShapeDtypeStruct((g, 1, u), jnp.float32),
            jax.ShapeDtypeStruct((g, 1, u), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(nvalid, inc32, we32, nodew32)
