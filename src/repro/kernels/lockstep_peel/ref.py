"""Reference implementations of the dense lockstep LMBR peel.

One peel "cell" is Algorithm 5's densest-subset loop for a single
(src, dest) candidate pair, densified: the pair's K kept shared edges and U
candidate items become a (K, U) incidence matrix, per-round degree updates
become two small matmuls (edge death detection and degree subtraction), and
the lowest-degree pick is a row argmin.  G pairs run in lockstep as a
(G, K, U) batch.

The dense backends emit the free-space-independent peel TRAJECTORY — the
slot peeled each round plus the pool weight / alive-edge benefit at each
round head — NOT the final (gain, items) answer.  Selecting the best round
under the destination's free space (argmax of benefit/weight over fitting
rounds, earliest round on ties) happens on the host in float64, shared with
the gain-cache re-evaluation path, so every backend produces bit-identical
placements.

Exactness domain: callers dispatch here only for integer-valued edge and
node weights with totals below 2**24 (asserted upstream).  Then every
accumulated quantity — degrees, benefits, pool weights — is an integer
representable exactly in float32, sums are exact under ANY association
order, and the f32 device trajectory equals the f64 host trajectory
bit-for-bit after the (exact) widening cast.

Round semantics (mirrors ``algorithms._lmbr_peel_scalar`` / the pure-Python
oracle): a pair is active while its alive-edge benefit is positive and
items remain; each active round records (totw, benefit) at the round head,
peels the lowest-degree item (ties -> lowest slot id = lowest item id,
because slots are sorted by item id), retires edges that lose a pin, and
subtracts their weights from the degrees of their still-alive items.
"""

from __future__ import annotations

import numpy as np


def lockstep_peel_ref(
    inc: np.ndarray,      # (G, K, U) 0/1 incidence, zero-padded
    we: np.ndarray,       # (G, K) edge weights, zero-padded
    nodew: np.ndarray,    # (G, U) item weights, zero-padded
    nvalid: np.ndarray,   # (G,) valid item slots (prefix 0..nvalid-1)
):
    """Float64 numpy oracle.  Returns (peel, rtot, rben):

    peel (G, U) int64 — slot peeled at round r, -1 once the pair finished
    rtot (G, U) f64   — pool weight at the head of each recorded round
    rben (G, U) f64   — alive-edge benefit at the head of each round
    """
    inc = np.asarray(inc, dtype=np.float64)
    we = np.asarray(we, dtype=np.float64)
    nodew = np.asarray(nodew, dtype=np.float64)
    nvalid = np.asarray(nvalid, dtype=np.int64)
    G, K, U = inc.shape
    peel = np.full((G, U), -1, dtype=np.int64)
    rtot = np.zeros((G, U), dtype=np.float64)
    rben = np.zeros((G, U), dtype=np.float64)
    valid = np.arange(U, dtype=np.int64)[None, :] < nvalid[:, None]
    cand = np.einsum("gku,gk->gu", inc, we)
    cand = np.where(valid, cand, np.inf)
    ealive = np.ones((G, K), dtype=bool)
    ben = we.sum(axis=1)
    totw = nodew.sum(axis=1)          # padding weights are zero
    nal = nvalid.copy()
    for r in range(U):
        act = (ben > 0.5) & (nal > 0)
        if not act.any():
            break
        rows = np.flatnonzero(act)
        rtot[rows, r] = totw[rows]
        rben[rows, r] = ben[rows]
        j = np.argmin(cand[rows], axis=1)     # ties -> lowest slot id
        peel[rows, r] = j
        cand[rows, j] = np.inf
        totw[rows] -= nodew[rows, j]
        nal[rows] -= 1
        hit = inc[rows, :, j] > 0.5           # (A, K)
        dying = ealive[rows] & hit
        dw = we[rows] * dying
        ben[rows] -= dw.sum(axis=1)
        # dead/invalid slots sit at +inf; inf - finite stays inf
        cand[rows] -= np.einsum("aku,ak->au", inc[rows], dw)
        ealive[rows] &= ~dying
    return peel, rtot, rben


def lockstep_peel_jnp(inc, we, nodew, nvalid):
    """jnp float32 lockstep peel (jit-compiled by the ops dispatcher).

    Same trajectory contract as ``lockstep_peel_ref``; the early-exit
    ``lax.while_loop`` keeps device round count equal to the longest pair's
    peel instead of the static U bound.  The contractions ask for HIGHEST
    precision: at the default a TPU rounds f32 matmul operands to bf16,
    which is inexact for integer weights above 256.
    """
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    G, K, U = inc.shape
    iota_u = jnp.arange(U, dtype=jnp.int32)[None, :]
    valid = iota_u < nvalid[:, None]
    cand0 = jnp.where(
        valid, jnp.einsum("gku,gk->gu", inc, we, precision=hi), jnp.inf
    )
    state0 = (
        jnp.int32(0),
        cand0,
        jnp.ones((G, K), dtype=bool),
        we.sum(axis=1),
        nodew.sum(axis=1),
        nvalid.astype(jnp.int32),
        jnp.full((G, U), -1, dtype=jnp.int32),
        jnp.zeros((G, U), dtype=jnp.float32),
        jnp.zeros((G, U), dtype=jnp.float32),
    )

    def active(ben, nal):
        return (ben > 0.5) & (nal > 0)

    def cond(st):
        r, _, _, ben, _, nal, _, _, _ = st
        return (r < U) & jnp.any(active(ben, nal))

    def body(st):
        r, cand, ealive, ben, totw, nal, peel, rtot, rben = st
        act = active(ben, nal)
        rtot = rtot.at[:, r].set(jnp.where(act, totw, 0.0))
        rben = rben.at[:, r].set(jnp.where(act, ben, 0.0))
        j = jnp.argmin(cand, axis=1).astype(jnp.int32)
        onehot = (iota_u == j[:, None]) & act[:, None]
        ohf = onehot.astype(inc.dtype)
        hit = jnp.einsum("gku,gu->gk", inc, ohf, precision=hi) > 0.5
        dying = ealive & hit
        dw = we * dying.astype(we.dtype)
        ben = ben - dw.sum(axis=1)
        cand = jnp.where(
            onehot, jnp.inf,
            cand - jnp.einsum("gku,gk->gu", inc, dw, precision=hi),
        )
        totw = totw - (nodew * ohf).sum(axis=1)
        nal = nal - act.astype(jnp.int32)
        peel = peel.at[:, r].set(jnp.where(act, j, jnp.int32(-1)))
        return (r + 1, cand, ealive & ~dying, ben, totw, nal, peel, rtot,
                rben)

    st = lax.while_loop(cond, body, state0)
    return st[6], st[7], st[8]
