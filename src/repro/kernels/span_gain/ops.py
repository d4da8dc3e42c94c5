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

The query-batch axis is padded to the next power of two before any jax
call: greedy rounds shrink the active set every iteration and one XLA
program per distinct batch size would dominate wall-clock.  Padded rows are
all-zero and sliced off.
"""

from __future__ import annotations

import jax
import numpy as np

from .ref import span_gain_jnp, span_gain_ref

_JNP_GAINS = jax.jit(span_gain_jnp)


def _pow2_pad(codes: np.ndarray, rem: np.ndarray, multiple: int = 1):
    a = codes.shape[0]
    pad = max(multiple, 1 << (a - 1).bit_length()) if a else multiple
    if pad != a:
        codes = np.concatenate(
            [codes, np.zeros((pad - a,) + codes.shape[1:], dtype=codes.dtype)]
        )
        rem = np.concatenate(
            [rem, np.zeros((pad - a, rem.shape[1]), dtype=rem.dtype)]
        )
    return codes, rem


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
    if impl == "jax":
        codes, rem = _pow2_pad(codes, rem)
        c32 = np.ascontiguousarray(codes).view(np.uint32)   # (A2, N, W2)
        r32 = np.ascontiguousarray(rem).view(np.uint32)     # (A2, W2)
        out = np.asarray(_JNP_GAINS(c32, r32))
        return out[:a].astype(np.int64)

    from .kernel import span_gain as _kernel

    block_a, block_n = 8, 128
    codes, rem = _pow2_pad(codes, rem, multiple=block_a)
    n = codes.shape[1]
    n2 = -(-n // block_n) * block_n
    # uint64 -> uint32 lanes, partition axis onto the 128-wide lane dim
    c32 = np.ascontiguousarray(codes).view(np.uint32)       # (A2, N, W2)
    c32 = np.ascontiguousarray(c32.transpose(0, 2, 1))      # (A2, W2, N)
    if n2 != n:
        c32 = np.concatenate(
            [c32, np.zeros(c32.shape[:2] + (n2 - n,), dtype=c32.dtype)], axis=2
        )
    r32 = np.ascontiguousarray(rem).view(np.uint32)         # (A2, W2)
    out = np.asarray(
        _kernel(c32, r32, block_a=block_a, block_n=block_n,
                interpret=(impl == "interpret"))
    )
    return out[:a, :n].astype(np.int64)
