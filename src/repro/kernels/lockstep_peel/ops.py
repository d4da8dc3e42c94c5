"""Backend dispatcher for the dense lockstep LMBR peel.

numpy-in / numpy-out, mirroring ``span_gain.ops``: the LMBR move loop is a
numpy control loop and treats one peel batch as a single op.  Backends:

  * "numpy"     — float64 dense oracle (``ref.lockstep_peel_ref``).
  * "jax"       — jitted f32 jnp lockstep (``ref.lockstep_peel_jnp``).
  * "pallas"    — the Pallas kernel, compiled; raises off TPU.
  * "interpret" — the Pallas kernel in interpreter mode (CPU tests).

All backends emit the same free-space-independent trajectories
(peel order, head-of-round pool weight and benefit); on the
integer-valued-weight domain the LMBR dispatcher enforces, the f32 device
arithmetic is exact and the trajectories are bit-identical to the f64
oracle after the widening cast.

Shape discipline: callers bucket batches into pow2 (U, K) classes and this
dispatcher pads the pair axis G to a power of two, so jit recompilation is
bounded; the kernel path additionally pads K to the f32 sublane multiple
(8) and U to the lane width (128).  Padding is inert — zero
incidence/weights never create degree, +inf degrees never win argmin,
rounds never exceed the unpadded nvalid, and a padded pair (nvalid 0) never
runs a round.
"""

from __future__ import annotations

import jax
import numpy as np

from .ref import lockstep_peel_jnp, lockstep_peel_ref

_JNP_PEEL = jax.jit(lockstep_peel_jnp)
_KERNEL_MAX_PAIRS = 1 << 15  # pairs per kernel call (scalar-prefetch SMEM)


def _pad_axis(a: np.ndarray, axis: int, to: int) -> np.ndarray:
    if a.shape[axis] == to:
        return a
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, to - a.shape[axis])
    return np.pad(a, pad)


def lockstep_peel(
    inc: np.ndarray,      # (G, K, U) 0/1 incidence, zero-padded
    we: np.ndarray,       # (G, K) edge weights, zero-padded
    nodew: np.ndarray,    # (G, U) item weights, zero-padded
    nvalid: np.ndarray,   # (G,) valid item slots per pair
    *,
    force: str | None = None,
):
    """Peel trajectories (peel (G, U) int64, rtot/rben (G, U) float64)."""
    if force == "numpy":
        return lockstep_peel_ref(inc, we, nodew, nvalid)
    on_tpu = jax.default_backend() == "tpu"
    impl = force or ("pallas" if on_tpu else "jax")
    if impl == "pallas" and not on_tpu:
        raise RuntimeError(
            "lockstep_peel: 'pallas' runs the compiled TPU kernel, but JAX's "
            f"backend is {jax.default_backend()!r}; ask for 'interpret' to "
            "run the kernel body in the interpreter"
        )
    if impl not in ("jax", "pallas", "interpret"):
        raise ValueError(f"unknown lockstep_peel backend {impl!r}")
    G, K, U = inc.shape
    g2 = 1 << max(0, (G - 1).bit_length())
    inc32 = _pad_axis(np.asarray(inc, dtype=np.float32), 0, g2)
    we32 = _pad_axis(np.asarray(we, dtype=np.float32), 0, g2)
    nodew32 = _pad_axis(np.asarray(nodew, dtype=np.float32), 0, g2)
    nv32 = _pad_axis(np.asarray(nvalid, dtype=np.int32), 0, g2)
    if impl == "jax":
        peel, rtot, rben = _JNP_PEEL(inc32, we32, nodew32, nv32)
        return (
            np.asarray(peel)[:G].astype(np.int64),
            np.asarray(rtot)[:G].astype(np.float64),
            np.asarray(rben)[:G].astype(np.float64),
        )

    from .kernel import lockstep_peel as _kernel

    k2 = -(-max(K, 1) // 8) * 8
    u2 = -(-max(U, 1) // 128) * 128
    inc32 = _pad_axis(_pad_axis(inc32, 1, k2), 2, u2)
    we32 = _pad_axis(we32, 1, k2)[:, :, None]
    nodew32 = _pad_axis(nodew32, 1, u2)[:, None, :]
    # the per-pair slot counts ride scalar prefetch, and SMEM (1 MiB on a
    # v5e) cannot hold 2^18 of them: wider batches go in equal pow2 slices
    step = min(g2, _KERNEL_MAX_PAIRS)
    outs = [
        _kernel(inc32[lo: lo + step], we32[lo: lo + step],
                nodew32[lo: lo + step], nv32[lo: lo + step],
                interpret=(impl == "interpret"))
        for lo in range(0, g2, step)
    ]
    peel, rtot, rben = (np.concatenate([np.asarray(o[i]) for o in outs])
                        for i in range(3))
    # rounds never exceed nvalid <= U, so the U pad columns are all -1/0
    return (
        peel[:G, 0, :U].astype(np.int64),
        rtot[:G, 0, :U].astype(np.float64),
        rben[:G, 0, :U].astype(np.float64),
    )
