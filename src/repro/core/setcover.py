"""Replica selection via greedy set cover (paper §3, §4.1).

With replication, computing a query's span is the minimum set-cover problem
(NP-hard); the greedy algorithm gives the best-known log|Q| approximation and
doubles as the *replica selection* policy: the chosen partitions tell each
query which copy of each item to read.

`Placement` is the layout object shared by every algorithm: a boolean
membership matrix (partitions x items) plus per-partition weight accounting.

Span engine
-----------
Two evaluation paths produce bit-identical covers:

* the per-query reference (`greedy_set_cover` / `cover_for_query`): a Python
  loop over greedy rounds, kept as the executable specification;
* the batched bitset engine (`batched_cover_csr` / `batched_spans_csr`):
  queries are bucketed by word count W = ceil(|q|/64) and each query's
  membership submatrix is packed into uint64 words — ``codes[e, p, w]`` holds
  bit j iff partition p stores the query's (64*w + j)-th pin.  One greedy
  round for *every* still-uncovered query in the bucket is then a single
  popcount of ``codes & remaining`` followed by a row-wise argmax, instead
  of one Python loop per query.  The popcount backend is chosen PER BUCKET
  ROUND by ``_gain_matrix``: numpy ``bitwise_count`` below
  ``repro.flags.FLAGS["span_dispatch_threshold"]`` words, the accelerated
  path (Pallas span_gain kernel on TPU, jitted jnp elsewhere) above it;
  ``FLAGS["span_backend"]`` pins one backend globally instead.

Tie-break contract: every engine picks the LOWEST partition id among
partitions with maximal intersection gain (``np.argmax`` semantics).  The
batched engine is exact — same chosen partitions, same selection order, same
replica attribution, same ValueError on unplaced items — which the
equivalence tests in ``tests/test_span_engine.py`` enforce.

`SpanMaintainer` layers an incremental cache on top: per-edge covers are
recomputed only for edges incident to items whose membership changed
(dirty-set invalidation), which turns the inner loops of IHPA / DS / LMBR
from O(E) full sweeps into O(touched) batched refreshes.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import flags as _flags
from .. import obs as _obs
from ..kernels.span_gain.ops import span_gains
from .cluster import normalize_capacity

__all__ = [
    "Placement",
    "queries_to_csr",
    "greedy_set_cover",
    "cover_for_query",
    "query_span",
    "spans_for_workload",
    "WorkloadCover",
    "batched_cover_csr",
    "batched_spans_csr",
    "SpanMaintainer",
]

_WORD = 64


def queries_to_csr(queries) -> "tuple[np.ndarray, np.ndarray]":
    """CSR (ptr, nodes) of a list of queries (each an int sequence).  Pure
    packing — callers wanting set semantics deduplicate first (Hypergraph
    CSR edges and the online router's inputs already are)."""
    lists = [np.asarray(q, dtype=np.int64) for q in queries]
    ptr = np.zeros(len(lists) + 1, dtype=np.int64)
    ptr[1:] = np.cumsum([len(q) for q in lists])
    nodes = (
        np.concatenate(lists) if lists else np.zeros(0, dtype=np.int64)
    )
    return ptr, nodes


@dataclasses.dataclass
class Placement:
    """Layout of items onto partitions. member[p, v] == True iff a copy of
    item v is stored on partition p.

    ``stats`` is an optional fitting-diagnostics dict attached by the
    producing algorithm (e.g. LMBR's move-engine counters); it never
    influences placement semantics.

    ``capacity`` is either the classic scalar (every partition holds the
    same weight) or an (N,) per-partition vector for heterogeneous
    clusters (repro.core.cluster.NodeProfile).  Uniform vectors should be
    collapsed to the scalar via ``normalize_capacity`` before construction
    — `empty` does so — which keeps homogeneous profiles bit-identical to
    the scalar model."""

    member: np.ndarray  # (N, V) bool
    capacity: "float | np.ndarray"  # scalar, or (N,) per-partition vector
    node_weights: np.ndarray  # (V,)
    stats: dict | None = None

    @staticmethod
    def empty(num_partitions: int, num_items: int, capacity,
              node_weights: np.ndarray | None = None) -> "Placement":
        if node_weights is None:
            node_weights = np.ones(num_items, dtype=np.float64)
        return Placement(
            np.zeros((num_partitions, num_items), dtype=bool),
            normalize_capacity(capacity),
            np.asarray(node_weights, dtype=np.float64),
        )

    # ------------------------------------------------------------- accessors
    @property
    def num_partitions(self) -> int:
        return self.member.shape[0]

    @property
    def num_items(self) -> int:
        return self.member.shape[1]

    def partition_items(self, p: int) -> np.ndarray:
        return np.flatnonzero(self.member[p])

    def partition_weight(self, p: int) -> float:
        return float(self.node_weights[self.member[p]].sum())

    def partition_weights(self) -> np.ndarray:
        return self.member @ self.node_weights

    def cap_of(self, p: int) -> float:
        """Capacity of partition p (scalar capacities apply to every row)."""
        cap = self.capacity
        if isinstance(cap, np.ndarray) and cap.ndim:
            return float(cap[p])
        return float(cap)

    @property
    def capacity_vec(self) -> np.ndarray:
        """(N,) per-partition capacity (scalar capacity broadcast)."""
        cap = self.capacity
        if isinstance(cap, np.ndarray) and cap.ndim:
            return cap
        return np.full(self.num_partitions, float(cap))

    def free_space(self, p: int) -> float:
        return self.cap_of(p) - self.partition_weight(p)

    def replication_factor(self) -> float:
        placed = self.member.sum(axis=0)
        placed = placed[placed > 0]
        return float(placed.mean()) if len(placed) else 0.0

    def copies_of(self, v: int) -> np.ndarray:
        return np.flatnonzero(self.member[:, v])

    # ------------------------------------------------------------- mutation
    def add(self, p: int, items) -> None:
        self.member[p, np.asarray(items, dtype=np.int64)] = True

    def add_partition(self, capacity: float | None = None) -> int:
        self.member = np.vstack(
            [self.member, np.zeros((1, self.num_items), dtype=bool)]
        )
        cap = self.capacity
        if isinstance(cap, np.ndarray) and cap.ndim:
            new_cap = float(np.min(cap)) if capacity is None else float(capacity)
            self.capacity = np.append(cap, new_cap)
        elif capacity is not None and float(capacity) != float(cap):
            self.capacity = np.append(
                np.full(self.num_partitions - 1, float(cap)), float(capacity)
            )
        return self.num_partitions - 1

    def validate(self, tol: float = 1e-9) -> None:
        w = self.partition_weights()
        if (w > self.capacity + tol).any():
            cap = self.capacity_vec
            bad = int(np.argmax(w - cap))
            raise ValueError(
                f"partition {bad} over capacity: {w[bad]:.1f} > {cap[bad]}"
            )
        placed = self.member.any(axis=0)
        # items that appear in no partition are only legal if they are phantom
        # (weight 0) items
        missing = np.flatnonzero(~placed & (self.node_weights > 0))
        if len(missing):
            raise ValueError(f"{len(missing)} items unplaced, e.g. {missing[:5]}")


def greedy_set_cover(query: np.ndarray, member: np.ndarray) -> list[int]:
    """getSpanningPartitions: minimal-ish set of partitions covering `query`.

    Iteratively picks the partition with the largest intersection with the
    still-uncovered items (ties -> lowest partition id, deterministic).
    """
    query = np.asarray(query, dtype=np.int64)
    remaining = np.ones(len(query), dtype=bool)
    sub = member[:, query]  # (N, |q|)
    chosen: list[int] = []
    while remaining.any():
        gains = (sub & remaining[None, :]).sum(axis=1)
        p = int(np.argmax(gains))
        if gains[p] == 0:
            raise ValueError(
                f"query items {query[remaining][:5]} not stored on any partition"
            )
        chosen.append(p)
        remaining &= ~sub[p]
    return chosen


def cover_for_query(query: np.ndarray, member: np.ndarray):
    """Like greedy_set_cover but also returns, per chosen partition, the item
    ids the query reads from it (getAccessedItems for every member of the
    cover).  Items are attributed to the first chosen partition that holds
    them — i.e. the actual replica-selection decision.  Same tie-break as
    greedy_set_cover (maximal gain, ties -> lowest partition id), so the
    chosen list is identical to it; raises ValueError on unplaced items."""
    query = np.asarray(query, dtype=np.int64)
    remaining = np.ones(len(query), dtype=bool)
    sub = member[:, query]
    chosen: list[int] = []
    accessed: list[np.ndarray] = []
    while remaining.any():
        gains = (sub & remaining[None, :]).sum(axis=1)
        p = int(np.argmax(gains))
        if gains[p] == 0:
            raise ValueError("query contains an unplaced item")
        newly = sub[p] & remaining
        chosen.append(p)
        accessed.append(query[newly])
        remaining &= ~newly
    return chosen, accessed


def query_span(query: np.ndarray, member: np.ndarray) -> int:
    """getQuerySpan: size of the greedy cover (exact same selection as
    `greedy_set_cover`, ties -> lowest partition id)."""
    return len(greedy_set_cover(query, member))


# ===================================================================== engine
# Engine-level dispatch counters (observability, not control flow): how many
# word-count buckets resolved on which cover loop, how many greedy rounds
# each side ran, and how many host-loop gain rounds each gain backend
# computed.  `lmbr`/`PlacementService` snapshot deltas into Placement.stats;
# benchmarks read them to report transfer counts, and a run that pins a
# device path reads them to prove the path ran.
ENGINE_COUNTERS = {
    "device_buckets": 0,
    "host_buckets": 0,
    "device_rounds": 0,
    "host_rounds": 0,
    "numpy_gain_rounds": 0,
    "jax_gain_rounds": 0,
    "pallas_gain_rounds": 0,
    "interpret_gain_rounds": 0,
}


def _gains_numpy(codes: np.ndarray, rem: np.ndarray) -> np.ndarray:
    """Popcount gains: codes (A, N, W) uint64, rem (A, W) -> (A, N) int64."""
    return np.bitwise_count(codes & rem[:, None, :]).sum(axis=2, dtype=np.int64)


def _accel_backend() -> str:
    """The accelerated gain backend of this process: the compiled Pallas
    kernel on TPU, the jitted jnp popcount on any other JAX backend."""
    return "pallas" if jax.default_backend() == "tpu" else "jax"


def _resolve_gain_backend(words: int) -> str:
    """Per-round gain backend: ``span_backend`` if pinned, else numpy below
    ``span_dispatch_threshold`` gain-matrix words and the accelerated
    backend above it.  Counts the pick in ``ENGINE_COUNTERS``."""
    backend = _flags.FLAGS.get("span_backend", "auto")
    if backend == "auto":
        thresh = int(_flags.FLAGS.get("span_dispatch_threshold", 48_000))
        backend = "numpy" if words < thresh else _accel_backend()
    ENGINE_COUNTERS[f"{backend}_gain_rounds"] += 1
    return backend


def _gain_matrix_w1(codes1: np.ndarray, rem1: np.ndarray) -> np.ndarray:
    """Single-word variant of `_gain_matrix`: codes1 (A, N) uint64, rem1
    (A,) -> (A, N) gains.  Same per-round dispatch rule; the numpy path
    skips the word-axis reduction (gain values are identical, only the
    dtype differs — argmax/zero tests are unaffected)."""
    backend = _resolve_gain_backend(codes1.size)
    if backend == "numpy":
        return np.bitwise_count(codes1 & rem1[:, None])
    return span_gains(codes1[:, :, None], rem1[:, None], force=backend)


def _gain_matrix(codes: np.ndarray, rem: np.ndarray) -> np.ndarray:
    """Per-bucket backend dispatch for one greedy round.

    Every backend is bit-exact (integer popcount), so this is purely a
    performance decision: each call covers one (bucket, round) with
    codes.size = A * N * W words of gain work.  Small rounds stay on numpy
    (crossing into jax costs more than the popcount); rounds past the
    calibrated span_dispatch_threshold run on the accelerated backend — the
    Pallas span_gain kernel on TPU, the jitted jnp popcount elsewhere.
    """
    backend = _resolve_gain_backend(codes.size)
    if backend == "numpy":
        return _gains_numpy(codes, rem)
    return span_gains(codes, rem, force=backend)


def engine_counters() -> dict:
    """Snapshot of the cover-engine dispatch counters."""
    return dict(ENGINE_COUNTERS)


# ---------------------------------------------- device-resident round loop
_ROUND_LOOPS: dict[tuple[int, int, int, int], object] = {}


def _round_loop_fn(B: int, N: int, W2: int, Rmax: int):
    """Compile (and cache) the jitted whole-round cover loop for one padded
    bucket shape.

    The loop fuses mask+popcount+argmax+scatter for EVERY greedy round of
    the bucket inside one `lax.while_loop`, so cover state (remaining-bit
    words, chosen matrix) stays device-resident: one upload of the packed
    codes, one download of the chosen matrix, zero per-round transfers.

    Layout: codes arrive word-major, (W2, B, N) uint32, so the (B, N)
    minor dims tile the TPU's (8, 128) vreg and the word reduce is
    elementwise across planes.  Compiled for a v5e at B=65536, N=256,
    W2=2, the loop's temporaries take 0.5 MiB this way against 32 MiB with
    the 2-wide word axis minor.

    Exactness contract (mirrors the host loop bit-for-bit): gains are
    integer popcounts summed over uint32 lanes, `argmax` takes the first
    maximum (ties -> lowest partition id), and a query whose max gain hits
    zero while bits remain raises in the host path — here it sets a `bad`
    flag and terminates the row, and the caller re-runs the bucket on host
    to raise the identical ValueError.
    """
    key = (B, N, W2, Rmax)
    fn = _ROUND_LOOPS.get(key)
    if fn is not None:
        return fn

    def loop(codes, rem):  # codes (W2, B, N) uint32, rem (W2, B) uint32
        ch0 = jnp.full((Rmax, B), -1, dtype=jnp.int32)
        bad0 = jnp.zeros((B,), dtype=bool)

        def cond(state):
            r, rem, ch, bad = state
            return (r < Rmax) & jnp.any(rem != 0)

        def body(state):
            r, rem, ch, bad = state
            active = jnp.any(rem != 0, axis=0)
            g = (
                lax.population_count(codes & rem[:, :, None])
                .astype(jnp.int32)
                .sum(axis=0)
            )                                       # (B, N)
            p = jnp.argmax(g, axis=1).astype(jnp.int32)
            newbad = active & (g.max(axis=1) == 0)
            ok = active & ~newbad
            # the chosen partition's words by a one-hot masked reduce over
            # the lane axis: a per-row gather there compiled to a 128 MiB
            # temporary at B=1024
            onehot = lax.broadcasted_iota(jnp.int32, (B, N), 1) == p[:, None]
            sel = jnp.where(onehot[None], codes, jnp.uint32(0)).sum(axis=2)
            rem = jnp.where(ok[None, :], rem & ~sel, rem)
            # bad rows terminate (their chosen stays -1); the caller re-runs
            # the bucket on the host loop to raise the exact engine error
            rem = jnp.where(newbad[None, :], jnp.uint32(0), rem)
            ch = ch.at[r].set(jnp.where(ok, p, jnp.int32(-1)))
            return r + 1, rem, ch, bad | newbad

        _, _, ch, bad = lax.while_loop(
            cond, body, (jnp.int32(0), rem, ch0, bad0)
        )
        return ch, bad

    fn = jax.jit(loop)
    _ROUND_LOOPS[key] = fn
    return fn


def _device_cover_rounds(codes: np.ndarray, rem: np.ndarray):
    """Resolve one packed bucket on device.  codes (B, N, W) uint64, rem
    (B, W) uint64 -> ch (B, R) int64, or None when a query in the bucket is
    uncoverable (the caller's host loop then raises the canonical error)."""
    B, N, W = codes.shape
    if B == 0:
        return np.zeros((0, 0), dtype=np.int64)
    B2 = 1 << max(3, (B - 1).bit_length())  # pow2 pad bounds jit churn
    Rmax = min(N, _WORD * W)
    fn = _round_loop_fn(B2, N, 2 * W, Rmax)
    c32 = np.zeros((2 * W, B2, N), dtype=np.uint32)
    c32[:, :B] = codes.view(np.uint32).reshape(B, N, 2 * W).transpose(2, 0, 1)
    r32 = np.zeros((2 * W, B2), dtype=np.uint32)
    r32[:, :B] = rem.view(np.uint32).reshape(B, 2 * W).T
    ch_d, bad_d = fn(c32, r32)
    ch = np.asarray(ch_d)[:, :B]
    if np.asarray(bad_d)[:B].any():
        return None
    used = int((ch >= 0).any(axis=1).sum())  # rounds are prefix-dense
    return ch[:used].T.astype(np.int64)


@dataclasses.dataclass
class WorkloadCover:
    """Batched cover of a CSR query set.

    spans:       (E,) greedy cover size per query
    cover_ptr:   (E+1,) CSR offsets into cover_parts
    cover_parts: (sum spans,) chosen partitions in greedy selection order
    pin_parts:   (P,) or None — for every pin of the input CSR, the partition
                 that serves it (the replica-selection decision); aligned with
                 the edge_nodes array the cover was computed from
    """

    spans: np.ndarray
    cover_ptr: np.ndarray
    cover_parts: np.ndarray
    pin_parts: np.ndarray | None = None

    def chosen(self, e: int) -> np.ndarray:
        return self.cover_parts[self.cover_ptr[e]: self.cover_ptr[e + 1]]


def _cover_bucket(edge_ptr, edge_nodes, member, b_idx, W, spans, pin_parts):
    """Run batched greedy cover for one word-count bucket.  Returns the
    per-round chosen matrix ch (B, R) with -1 padding, and the backend
    that resolved it: ``device`` (the device round loop) or ``host``."""
    sizes = edge_ptr[b_idx + 1] - edge_ptr[b_idx]
    B = len(b_idx)
    loc_ptr = np.zeros(B + 1, dtype=np.int64)
    np.cumsum(sizes, out=loc_ptr[1:])
    P = int(loc_ptr[-1])
    pin_e = np.repeat(np.arange(B, dtype=np.int64), sizes)
    pos = np.arange(P, dtype=np.int64) - loc_ptr[pin_e]
    pins = edge_nodes[edge_ptr[b_idx][pin_e] + pos]

    # pack the per-query membership submatrices into uint64 words
    codes = np.zeros((B, member.shape[0], W), dtype=np.uint64)
    L = int(sizes.max()) if P else 0
    if P and W == 1 and B * L * member.shape[0] <= 4_000_000:
        # single-word fast pack: pad each query's pins to (B, Lmax) indices
        # into a transposed member copy (dummy index -> all-False row) and
        # SUM the per-slot bit weights — bits are distinct within a query,
        # so the sum is exactly the OR, with no segment reduce.  The dense
        # (B, Lmax, N) temporaries make this a microbatch-sized path; huge
        # one-shot buckets (full-trace replays) keep the reduceat pack,
        # whose memory tracks total pins instead
        mt = np.zeros((member.shape[1] + 1, member.shape[0]), dtype=bool)
        mt[:-1] = member.T
        pinpad = np.full((B, L), member.shape[1], dtype=np.int64)
        pinpad[pin_e, pos] = pins
        bits_w = np.uint64(1) << np.arange(L, dtype=np.uint64)
        codes[:, :, 0] = (
            mt[pinpad] * bits_w[None, :, None]
        ).sum(axis=1, dtype=np.uint64)
    elif P:
        wid = pos >> 6
        bit = (pos & 63).astype(np.uint64)
        # bool * (1 << bit) fuses the astype+shift into one temporary
        shifted = member[:, pins] * (np.uint64(1) << bit)[None, :]  # (N, P)
        seg = pin_e * W + wid
        starts = np.flatnonzero(
            np.concatenate([[True], seg[1:] != seg[:-1]])
        )
        red = np.bitwise_or.reduceat(shifted, starts, axis=1)  # (N, G)
        codes[pin_e[starts], :, wid[starts]] = red.T

    # remaining-items masks: the low |q| bits set
    rem = np.zeros((B, W), dtype=np.uint64)
    for j in range(W):
        bits = np.clip(sizes - _WORD * j, 0, _WORD)
        low = (np.uint64(1) << bits.clip(0, _WORD - 1).astype(np.uint64)) - np.uint64(1)
        rem[:, j] = np.where(bits >= _WORD, np.uint64(0xFFFFFFFFFFFFFFFF), low)

    # whole-bucket backend dispatch: device-resident round loop for big
    # buckets (one transfer total), per-round host loop otherwise.  Both
    # are bit-identical (see _round_loop_fn), so this is purely perf.
    ch = None
    round_backend = _flags.FLAGS.get("span_round_backend", "auto")
    if round_backend == "auto":
        thresh = int(_flags.FLAGS.get("span_round_threshold", 200_000))
        round_backend = "device" if codes.size >= thresh else "numpy"
    if round_backend == "device":
        ch = _device_cover_rounds(codes, rem)
    if ch is not None:
        ENGINE_COUNTERS["device_buckets"] += 1
        ENGINE_COUNTERS["device_rounds"] += ch.shape[1]
        reg = _obs.registry()
        if reg.active:
            reg.inc("cover_buckets", backend="device")
            reg.inc("cover_rounds", ch.shape[1], backend="device")
        spans[b_idx] = (ch >= 0).sum(axis=1)
        _attribute_pins(ch, member, b_idx, edge_ptr, pin_e, pos, pins,
                        pin_parts)
        return ch, "device"

    rounds: list[tuple[np.ndarray, np.ndarray]] = []
    if W == 1:
        # single-word fast path (queries of <= 64 pins, the dominant online
        # serving shape): same greedy rounds with the word axis squeezed and
        # the still-active queries kept COMPACT (codes_a/rem_a/eidx shrink
        # together), so each round runs a minimal number of numpy dispatches
        # — identical gains, argmax, and tie-breaks to the generic loop
        eidx = np.flatnonzero(rem[:, 0])
        codes_a = codes[eidx, :, 0]
        rem_a = rem[eidx, 0]
        ar = np.arange(B, dtype=np.int64)
        while len(eidx):
            g = _gain_matrix_w1(codes_a, rem_a)
            p = g.argmax(axis=1)                # ties -> lowest partition id
            a = ar[: len(p)]
            gmax = g[a, p]
            if not gmax.all():
                bad = int(eidx[int(np.argmax(gmax == 0))])
                e = int(b_idx[bad])
                raise ValueError(
                    f"query {e} contains items not stored on any partition"
                )
            rounds.append((eidx, p))
            rem_a &= ~codes_a[a, p]
            alive = rem_a != 0
            if not alive.all():
                eidx = eidx[alive]
                codes_a = codes_a[alive]
                rem_a = rem_a[alive]
    else:
        active = np.flatnonzero(rem.any(axis=1))
        while len(active):
            sub = codes[active]                     # (A, N, W)
            g = _gain_matrix(sub, rem[active])      # (A, N)
            p = g.argmax(axis=1)                    # ties -> lowest partition id
            gmax = g[np.arange(len(p)), p]
            if (gmax == 0).any():
                bad = int(active[int(np.argmax(gmax == 0))])
                e = int(b_idx[bad])
                raise ValueError(
                    f"query {e} contains items not stored on any partition"
                )
            rounds.append((active, p))
            newly = sub[np.arange(len(p)), p]       # (A, W)
            rem[active] &= ~newly
            active = active[rem[active].any(axis=1)]

    R = len(rounds)
    ch = np.full((B, R), -1, dtype=np.int64)
    for r, (ai, pi) in enumerate(rounds):
        ch[ai, r] = pi
    ENGINE_COUNTERS["host_buckets"] += 1
    ENGINE_COUNTERS["host_rounds"] += R
    reg = _obs.registry()
    if reg.active:
        reg.inc("cover_buckets", backend="host")
        reg.inc("cover_rounds", R, backend="host")
    spans[b_idx] = (ch >= 0).sum(axis=1)
    _attribute_pins(ch, member, b_idx, edge_ptr, pin_e, pos, pins, pin_parts)
    return ch, "host"


def _attribute_pins(ch, member, b_idx, edge_ptr, pin_e, pos, pins, pin_parts):
    """Replica-selection attribution: for every pin, the first chosen round
    whose partition stores the item serves it (matches `greedy_set_cover`'s
    `accessed` ordering)."""
    if pin_parts is None or not len(pins):
        return
    assigned = np.full(len(pins), -1, dtype=np.int64)
    for r in range(ch.shape[1]):
        pe = ch[pin_e, r]
        idx = np.flatnonzero((assigned < 0) & (pe >= 0))
        if not len(idx):
            continue
        hit = member[pe[idx], pins[idx]]
        sel = idx[hit]
        assigned[sel] = pe[sel]
    pin_parts[edge_ptr[b_idx][pin_e] + pos] = assigned


def batched_cover_csr(
    edge_ptr: np.ndarray,
    edge_nodes: np.ndarray,
    member: np.ndarray,
    with_pin_parts: bool = False,
) -> WorkloadCover:
    """Greedy set cover of every CSR query against `member`, batched.

    Bit-identical to running `cover_for_query` per query (same covers in the
    same order, same lowest-id tie-break, ValueError on unplaced items), but
    one popcount matrix op per greedy round per size bucket instead of E
    Python loops.  Queries must be pin-deduplicated (Hypergraph CSR edges
    always are).  Traced as one ``cover.batch`` span (args ``edges`` and
    the call's ``host_rounds``, ``device_rounds``, ``accel_gain_rounds``)
    around one ``cover.bucket`` span per word-count bucket (args
    ``words``, ``edges``, ``backend``)."""
    tr = _obs.tracer()
    with tr.span("cover.batch", edges=len(edge_ptr) - 1) as sp:
        e0 = dict(ENGINE_COUNTERS) if tr.active else None
        cov = _batched_cover(edge_ptr, edge_nodes, member, with_pin_parts)
        if e0 is not None:
            d = {k: ENGINE_COUNTERS[k] - e0[k] for k in e0}
            sp.set(host_rounds=d["host_rounds"],
                   device_rounds=d["device_rounds"],
                   accel_gain_rounds=d["jax_gain_rounds"]
                   + d["pallas_gain_rounds"] + d["interpret_gain_rounds"])
    return cov


def _batched_cover(edge_ptr, edge_nodes, member, with_pin_parts):
    edge_ptr = np.asarray(edge_ptr, dtype=np.int64)
    edge_nodes = np.asarray(edge_nodes, dtype=np.int64)
    E = len(edge_ptr) - 1
    spans = np.zeros(E, dtype=np.int64)
    pin_parts = (
        np.full(len(edge_nodes), -1, dtype=np.int64) if with_pin_parts else None
    )
    sizes = np.diff(edge_ptr)
    words = np.maximum((sizes + _WORD - 1) // _WORD, 1)
    bucket_chosen: list[tuple[np.ndarray, np.ndarray]] = []
    for W in np.unique(words[sizes > 0]) if E else []:
        b_idx = np.flatnonzero((words == W) & (sizes > 0))
        with _obs.tracer().span("cover.bucket", words=int(W),
                                edges=len(b_idx)) as bucket_span:
            ch, backend = _cover_bucket(edge_ptr, edge_nodes, member, b_idx,
                                        int(W), spans, pin_parts)
            bucket_span.set(backend=backend)
        bucket_chosen.append((b_idx, ch))

    cover_ptr = np.zeros(E + 1, dtype=np.int64)
    np.cumsum(spans, out=cover_ptr[1:])
    cover_parts = np.zeros(int(cover_ptr[-1]), dtype=np.int64)
    for b_idx, ch in bucket_chosen:
        sp = spans[b_idx]
        total = int(sp.sum())
        if not total:
            continue
        # flat (edge-major, round-minor) order matches ch[ch >= 0] row-major
        base = np.zeros(len(b_idx) + 1, dtype=np.int64)
        np.cumsum(sp, out=base[1:])
        within = np.arange(total, dtype=np.int64) - base[
            np.repeat(np.arange(len(b_idx)), sp)
        ]
        cover_parts[np.repeat(cover_ptr[b_idx], sp) + within] = ch[ch >= 0]
    return WorkloadCover(spans, cover_ptr, cover_parts, pin_parts)


def batched_spans_csr(
    edge_ptr: np.ndarray, edge_nodes: np.ndarray, member: np.ndarray
) -> np.ndarray:
    """Spans only (cheapest batched path).  Inherits `batched_cover_csr`'s
    exactness contract: element-wise equal to `query_span` per query."""
    return batched_cover_csr(edge_ptr, edge_nodes, member).spans


def spans_for_workload(hg, placement: Placement) -> np.ndarray:
    """Span of every hyperedge in `hg` under `placement` (batched engine,
    bit-identical to the per-query reference)."""
    return batched_spans_csr(hg.edge_ptr, hg.edge_nodes, placement.member)


# ======================================================== incremental spans
class SpanMaintainer:
    """Per-edge span cache with dirty-set invalidation.

    Exactness contract: membership of an item only affects the covers of
    edges containing that item, so after `notify_items(touched)` recomputing
    just the incident (dirty) edges reproduces a full sweep bit-for-bit.
    Callers MUST notify every item whose membership row changed.

    With ``with_covers=True`` the maintainer additionally keeps every edge's
    full replica selection in FLAT form — ``pin_parts`` holds, for every pin
    of the hypergraph's CSR, the partition that serves it, and ``chosen(e)``
    the partitions of e's cover in greedy selection order.  ``cover(e)``
    synthesizes the {partition: accessed items} dict on demand (partitions in
    selection order, items in pin order — identical to ``cover_for_query``),
    and ``refresh_edges`` re-derives an explicit edge set in one batched
    cover instead of per-edge Python loops.  This is the LMBR consumption
    path: LMBR's move loop invalidates an algorithm-defined edge set
    (narrower than the full incidence of the moved items), so it bypasses
    the dirty set and names its edges directly — and LMBR's vectorized gain
    engine reads ``pin_parts`` directly instead of per-edge dicts."""

    def __init__(self, hg, placement: Placement, with_covers: bool = False):
        self.hg = hg
        self.placement = placement
        self._node_ptr, self._node_edges = hg.incidence()
        self._pin_part: np.ndarray | None = None  # (P,) serving partition
        self._chosen: list[np.ndarray] | None = None  # per edge, greedy order
        if with_covers:
            cov = batched_cover_csr(
                hg.edge_ptr, hg.edge_nodes, placement.member,
                with_pin_parts=True,
            )
            self._spans = cov.spans
            self._pin_part = cov.pin_parts
            self._chosen = [cov.chosen(e).copy() for e in range(hg.num_edges)]
        else:
            self._spans = batched_spans_csr(
                hg.edge_ptr, hg.edge_nodes, placement.member
            )
        self._dirty = np.zeros(hg.num_edges, dtype=bool)

    @property
    def pin_parts(self) -> np.ndarray:
        """Serving partition of every pin, aligned with ``hg.edge_nodes``
        (requires with_covers=True)."""
        return self._pin_part

    def chosen(self, e: int) -> np.ndarray:
        """Partitions of edge e's cover in greedy selection order (requires
        with_covers=True)."""
        return self._chosen[e]

    def cover(self, e: int) -> dict[int, np.ndarray]:
        """Replica selection of edge e (requires with_covers=True): maps each
        chosen partition, in greedy selection order, to the items the edge
        reads from it.  Built on demand from the flat pin attribution."""
        lo, hi = self.hg.edge_ptr[e], self.hg.edge_ptr[e + 1]
        q = self.hg.edge_nodes[lo:hi]
        pp = self._pin_part[lo:hi]
        return {int(p): q[pp == p] for p in self._chosen[e]}

    def refresh_edges(self, edge_ids) -> None:
        """Batched recompute of exactly `edge_ids` — bit-identical to calling
        `cover_for_query` per edge, one engine invocation total."""
        edge_ids = np.asarray(edge_ids, dtype=np.int64)
        if not len(edge_ids):
            return
        ptr, pidx = self.hg.pin_indices(edge_ids)
        nodes = self.hg.edge_nodes[pidx]
        cov = batched_cover_csr(
            ptr, nodes, self.placement.member,
            with_pin_parts=self._pin_part is not None,
        )
        self._spans[edge_ids] = cov.spans
        if self._pin_part is not None:
            self._pin_part[pidx] = cov.pin_parts
            for i, e in enumerate(edge_ids):
                self._chosen[int(e)] = cov.chosen(i).copy()
        self._dirty[edge_ids] = False

    def notify_items(self, items) -> None:
        """Mark every edge incident to `items` dirty."""
        items = np.asarray(items, dtype=np.int64)
        if not len(items):
            return
        cnt = self._node_ptr[items + 1] - self._node_ptr[items]
        total = int(cnt.sum())
        if not total:
            return
        base = np.repeat(self._node_ptr[items], cnt)
        off = np.arange(total, dtype=np.int64) - np.repeat(
            np.concatenate([[0], np.cumsum(cnt[:-1])]), cnt
        )
        self._dirty[self._node_edges[base + off]] = True

    def spans(self) -> np.ndarray:
        d = np.flatnonzero(self._dirty)
        if len(d):
            if self._pin_part is not None:
                self.refresh_edges(d)  # keeps covers consistent with spans
            else:
                ptr, nodes = self.hg.edges_csr(d)
                self._spans[d] = batched_spans_csr(
                    ptr, nodes, self.placement.member
                )
            self._dirty[:] = False
        return self._spans

    def residual_edges(self, min_span: int) -> np.ndarray:
        """Edge ids with span > min_span (pruneHypergraphBySpan keeps these)."""
        return np.flatnonzero(self.spans() > min_span)
