"""Pallas TPU flash-decode kernel: one query token against a long KV cache.

Decode attention is HBM-bandwidth-bound (it streams the whole KV cache per
token), so the kernel's job is to keep the MXU busy on (block_kv, D) tiles
while the online softmax runs in VMEM scratch.  The kv axis is the sequential
grid dimension; invalid cache slots (position < 0, e.g. unfilled ring-buffer
lanes) and out-of-window slots are masked via the positions array, which is
streamed alongside K/V.

Layout: q (B, H, D); k/v (B, K, T, D); kv_pos (B, T) int32; out (B, H, D).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _decode_kernel(
    qpos_ref,                      # scalar prefetch: (B,) current positions
    q_ref, k_ref, v_ref, pos_ref,  # VMEM blocks
    o_ref,
    m_scr, l_scr, acc_scr,
    *, scale: float, window: int | None, block_kv: int, num_kv_blocks: int,
    group: int,
):
    ib = pl.program_id(0)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                 # (G, D) grouped heads
    k = k_ref[0, 0].astype(jnp.float32)                 # (bkv, D)
    v = v_ref[0, 0].astype(jnp.float32)
    kpos = pos_ref[0]                                   # (bkv,) int32
    cur = qpos_ref[ib]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                           # (G, bkv)
    valid = (kpos >= 0) & (kpos <= cur)
    if window is not None:
        valid &= kpos > cur - window
    s = jnp.where(valid[None, :], s, NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True)),
                        -1e4)
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new

    @pl.when(ik == num_kv_blocks - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(
            o_ref.dtype
        )


@functools.partial(
    jax.jit, static_argnames=("window", "block_kv", "interpret")
)
def decode_attention(
    q: jax.Array,        # (B, H, D) one token per sequence
    k: jax.Array,        # (B, K, T, D)
    v: jax.Array,        # (B, K, T, D)
    kv_pos: jax.Array,   # (B, T) int32, -1 = invalid slot
    q_pos: jax.Array,    # (B,) int32 current decode positions
    *,
    window: int | None = None,
    block_kv: int = 256,
    interpret: bool = False,
) -> jax.Array:
    b, h, d = q.shape
    kheads, t = k.shape[1], k.shape[2]
    g = h // kheads
    if t % block_kv:
        raise ValueError("cache length must be a multiple of block_kv")
    nk = t // block_kv
    # group query heads by kv head: (B, K, G, D)
    qg = q.reshape(b, kheads, g, d)

    kernel = functools.partial(
        _decode_kernel, scale=d ** -0.5, window=window, block_kv=block_kv,
        num_kv_blocks=nk, group=g,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kheads, nk),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda ib, ih, ik, qpos: (ib, ih, 0, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda ib, ih, ik, qpos: (ib, ih, ik, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda ib, ih, ik, qpos: (ib, ih, ik, 0)),
            pl.BlockSpec((1, block_kv), lambda ib, ih, ik, qpos: (ib, ik)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda ib, ih, ik, qpos: (ib, ih, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kheads, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q_pos, qg.reshape(b, kheads, g, d), k, v, kv_pos)
    return out.reshape(b, h, d)
