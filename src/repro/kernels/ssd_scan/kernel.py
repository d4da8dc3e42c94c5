"""Pallas TPU kernel for the Mamba2 SSD chunk scan.

TPU adaptation of the SSD algorithm: the GPU implementation leans on warp
shuffles and shared-memory scans; on TPU we express each chunk as dense
(L x L) / (L x N) matmuls (MXU work) and carry the (P x N) inter-chunk state
in VMEM scratch across the sequential chunk grid axis — the memory hierarchy
analogue of the paper's Listing-1 decomposition.

Grid: (batch, heads, num_chunks[sequential]).  Per step the kernel consumes
x (L, P), dt (L,), B (L, N), C (L, N) VMEM tiles and emits y (L, P).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ssd_kernel(
    a_ref,                       # (1,) per-head decay A (negative)
    x_ref, dt_ref, b_ref, c_ref,  # VMEM tiles
    y_ref,
    h_scr,                        # (P, N) carried state
    *, chunk: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, 0, 0].astype(jnp.float32)    # (L, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)  # (L,)
    bm = b_ref[0, 0, 0].astype(jnp.float32)   # (L, N)
    cm = c_ref[0, 0, 0].astype(jnp.float32)   # (L, N)
    a = a_ref[0]                              # scalar (negative)

    adt = a * dt                              # (L,)
    cum = jnp.cumsum(adt)                     # (L,)
    L = chunk
    # intra-chunk: gate[t, u] = exp(cum_t - cum_u) for u <= t
    decay = cum[:, None] - cum[None, :]
    mask = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1) <= \
        jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    gate = jnp.where(mask, jnp.exp(decay), 0.0)
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L, L)
    att = cb * gate * dt[None, :]             # (L, L)
    y_intra = jax.lax.dot_general(att, x, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    # inter-chunk: y_inter[t] = exp(cum_t) * C_t . h   (h: (P, N))
    ch = jax.lax.dot_general(cm, h_scr[...], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L, P)
    y_ref[0, 0, 0] = (y_intra + jnp.exp(cum)[:, None] * ch).astype(y_ref.dtype)
    # state update: h' = exp(cum_L) h + sum_u exp(cum_L - cum_u) dt_u x_u B_u^T
    tail = jnp.exp(cum[-1] - cum) * dt        # (L,)
    dx = x * tail[:, None]                    # (L, P)
    h_scr[...] = jnp.exp(cum[-1]) * h_scr[...] + jax.lax.dot_general(
        dx, bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                          # (P, N)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x: jax.Array,      # (B, S, H, P)
    dt: jax.Array,     # (B, S, H) fp32 (softplus'd)
    a: jax.Array,      # (H,) fp32 negative decay
    bmat: jax.Array,   # (B, S, N)
    cmat: jax.Array,   # (B, S, N)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, s, h, p = x.shape
    n = bmat.shape[-1]
    if s % chunk:
        raise ValueError("sequence length must be a multiple of chunk")
    nc = s // chunk
    # (B, H, nc, L, ...) layouts so the chunk axis is a clean grid dim
    xT = x.transpose(0, 2, 1, 3).reshape(b, h, nc, chunk, p)
    dtT = dt.transpose(0, 2, 1).reshape(b, h, nc, chunk)
    bT = jnp.broadcast_to(bmat[:, None], (b, h, s, n)).reshape(b, h, nc, chunk, n)
    cT = jnp.broadcast_to(cmat[:, None], (b, h, s, n)).reshape(b, h, nc, chunk, n)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1,), lambda ib, ih, ic: (ih,)),
            pl.BlockSpec((1, 1, 1, chunk, p), lambda ib, ih, ic: (ib, ih, ic, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, 1, chunk, n), lambda ib, ih, ic: (ib, ih, ic, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, n), lambda ib, ih, ic: (ib, ih, ic, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, chunk, p),
                               lambda ib, ih, ic: (ib, ih, ic, 0, 0)),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
    )
    y = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, nc, chunk, p), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, xT, dtT, bT, cT)
    return y.reshape(b, h, s, p).transpose(0, 2, 1, 3)
