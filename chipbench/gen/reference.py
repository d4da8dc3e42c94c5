"""The plain reference that decides ``correct``.

Per-query greedy set cover, copied from the program's
``repro.core.setcover.cover_for_query`` (the paper's getSpanningPartitions
with getAccessedItems): repeatedly take the partition that holds the most
still-uncovered items of the query, ties to the lowest partition id.  Plus the
two guarantees of a placement: no partition over its capacity, and every
item with a weight stored somewhere.  Imports nothing of the program."""

from __future__ import annotations

import numpy as np


def greedy_cover(query: np.ndarray, member_t: np.ndarray):
    """Cover of one query against ``member_t`` ((items, partitions) bool).

    Returns the chosen partitions in selection order; a query's span is
    their number."""
    sub = member_t[np.asarray(query, dtype=np.int64)]  # (|q|, N)
    remaining = np.ones(len(query), dtype=bool)
    chosen: list[int] = []
    while remaining.any():
        gains = (sub & remaining[:, None]).sum(axis=0)
        p = int(np.argmax(gains))
        if gains[p] == 0:
            raise ValueError("query contains an item stored on no partition")
        newly = sub[:, p] & remaining
        chosen.append(p)
        remaining &= ~newly
    return chosen


def over_capacity(member: np.ndarray, weights: np.ndarray, capacity,
                  tol: float = 1e-9) -> int:
    """Partitions whose stored weight exceeds their capacity."""
    load = member.astype(np.float64) @ np.asarray(weights, dtype=np.float64)
    return int((load > np.asarray(capacity, dtype=np.float64) + tol).sum())


def unplaced(member: np.ndarray, weights: np.ndarray) -> int:
    """Items of positive weight that no partition stores."""
    return int((~member.any(axis=0) & (np.asarray(weights) > 0)).sum())
