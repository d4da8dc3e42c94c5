"""Pallas TPU flash-attention (prefill) kernel.

Grid: (batch, q_heads, num_q_blocks, num_kv_blocks); the kv-block axis is
sequential ("arbitrary") so the online-softmax running max / sum / accumulator
live in VMEM scratch across kv iterations.  BlockSpecs tile Q/K/V into
(block_q, head_dim) / (block_kv, head_dim) VMEM windows — MXU-aligned when
block sizes are multiples of 128.  GQA is handled in the K/V index_map
(kv head = q head // group size), so no KV replication in HBM.

Causal and sliding-window masking is done by position arithmetic on program
ids; fully-masked kv blocks are skipped with pl.when (no FLOPs, no loads
consumed downstream).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref,            # VMEM blocks
    o_ref,                          # output block
    m_scr, l_scr, acc_scr,          # scratch: (bq,1), (bq,1), (bq,d)
    *, scale: float, causal: bool, window: int | None,
    block_q: int, block_kv: int, num_kv_blocks: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q
    k_start = ik * block_kv
    # block-level skip: no query in this q block attends into this kv block
    run = ik >= 0  # traced True
    if causal:
        run &= k_start <= q_start + block_q - 1
    if window is not None:
        run &= k_start + block_kv - 1 > q_start - window

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)            # (bkv, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                       # (bq, bkv)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        mask = jnp.ones((block_q, block_kv), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]                             # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        m_new = jnp.maximum(m_new, -1e4)                # masked-block guard
        p = jnp.exp(s - m_new)                          # (bq, bkv)
        corr = jnp.exp(m_prev - m_new)                  # (bq, 1)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    @pl.when(ik == num_kv_blocks - 1)
    def _finalize():
        o_ref[0, 0] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_kv", "interpret"),
)
def flash_attention(
    q: jax.Array,        # (B, H, S, D)
    k: jax.Array,        # (B, K, T, D)
    v: jax.Array,        # (B, K, T, D)
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, h, s, d = q.shape
    kheads, t = k.shape[1], k.shape[2]
    g = h // kheads
    scale = d ** -0.5
    nq = -(-s // block_q)
    nk = -(-t // block_kv)
    if s % block_q or t % block_kv:
        raise ValueError("seq lengths must be multiples of the block sizes")

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_kv=block_kv, num_kv_blocks=nk,
    )
    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, block_kv, d), lambda ib, ih, iq, ik: (ib, ih // g, ik, 0)),
            pl.BlockSpec((1, 1, block_kv, d), lambda ib, ih, iq, ik: (ib, ih // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
