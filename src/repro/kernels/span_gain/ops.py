"""Backend dispatcher for the span-gain matrix.

Unlike the attention/SSD packages this wrapper is numpy-in / numpy-out: the
span engine is a numpy control loop (greedy rounds, argmax tie-breaks) that
treats the gain matrix as one batched op per round.  All backends are
bit-exact integer math, so the choice is purely a performance decision:

  * "numpy"     — ``np.bitwise_count`` oracle, zero dispatch overhead; wins
                  on small buckets where crossing into jax costs more than
                  the popcount itself.
  * "jax"       — jitted jnp popcount-reduce (XLA fuses the mask).
  * "pallas"    — the Pallas kernel, compiled; raises off TPU.
  * "interpret" — the Pallas kernel in interpreter mode (CPU tests).

The query-batch axis runs in fixed blocks of ``BLOCK_ROWS`` queries, the
last block zero-padded: greedy rounds shrink the active set every
iteration, so any size-dependent shape would compile a new program
whenever a trace's rounds land on a batch size the warm-up did not.  With
fixed blocks the programs are one per (W2, N): a trace that has the
warm-up's word widths compiles nothing.  Padded rows are all-zero and
sliced off.
"""

from __future__ import annotations

import jax
import numpy as np

from .ref import span_gain_jnp, span_gain_ref

_JNP_GAINS = jax.jit(span_gain_jnp)

BLOCK_ROWS = 256


def _block_pad(codes: np.ndarray, rem: np.ndarray):
    """Zero-pad the query axis to a whole number of ``BLOCK_ROWS`` blocks
    (at least one)."""
    a = codes.shape[0]
    pad = max(1, -(-a // BLOCK_ROWS)) * BLOCK_ROWS
    codes = np.concatenate(
        [codes, np.zeros((pad - a,) + codes.shape[1:], dtype=codes.dtype)])
    rem = np.concatenate(
        [rem, np.zeros((pad - a, rem.shape[1]), dtype=rem.dtype)])
    return codes, rem


def _by_block(fn, c32: np.ndarray, r32: np.ndarray) -> np.ndarray:
    """``fn`` over each ``BLOCK_ROWS`` block of the padded query axis: every
    block is dispatched before any result is read back, so a round waits
    on the device once, not once a block."""
    outs = [fn(c32[i: i + BLOCK_ROWS], r32[i: i + BLOCK_ROWS])
            for i in range(0, c32.shape[0], BLOCK_ROWS)]
    return np.concatenate(jax.device_get(outs))


def span_gains(
    codes: np.ndarray,   # (A, N, W) uint64 packed membership submatrices
    rem: np.ndarray,     # (A, W) uint64 still-uncovered masks
    *,
    force: str | None = None,
) -> np.ndarray:
    """Gain matrix (A, N) int64 for one greedy cover round."""
    if force == "numpy":
        return span_gain_ref(codes, rem)
    on_tpu = jax.default_backend() == "tpu"
    impl = force or ("pallas" if on_tpu else "jax")
    if impl == "pallas" and not on_tpu:
        raise RuntimeError(
            "span_gains: 'pallas' runs the compiled TPU kernel, but JAX's "
            f"backend is {jax.default_backend()!r}; ask for 'interpret' to "
            "run the kernel body in the interpreter"
        )
    if impl not in ("jax", "pallas", "interpret"):
        raise ValueError(f"unknown span_gains backend {impl!r}")
    a = codes.shape[0]
    codes, rem = _block_pad(codes, rem)
    r32 = np.ascontiguousarray(rem).view(np.uint32)         # (A2, W2)
    c32 = np.ascontiguousarray(codes).view(np.uint32)       # (A2, N, W2)
    if impl == "jax":
        return _by_block(_JNP_GAINS, c32, r32)[:a].astype(np.int64)

    from .kernel import span_gain as _kernel

    block_a, block_n = 8, 128
    n = codes.shape[1]
    n2 = -(-n // block_n) * block_n
    # partition axis onto the 128-wide lane dim
    c32 = np.ascontiguousarray(c32.transpose(0, 2, 1))      # (A2, W2, N)
    if n2 != n:
        c32 = np.concatenate(
            [c32, np.zeros(c32.shape[:2] + (n2 - n,), dtype=c32.dtype)], axis=2
        )
    out = _by_block(
        lambda c, r: _kernel(c, r, block_a=block_a, block_n=block_n,
                             interpret=(impl == "interpret")), c32, r32)
    return out[:a, :n].astype(np.int64)
