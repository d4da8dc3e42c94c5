"""Deployment generators and the plain reference, copied into the
benchmark so that no change to the program can move the yardstick.

A generator module (``chipbench/gen/<generator>.py``, named by a
configuration's ``generator`` key) exposes ``node_weights(cfg, seed)`` and
``query_lists(cfg, seed, stream, count)``; the second returns one query
set as a list of sorted, deduplicated item arrays, and is a pure function
of its arguments."""

import numpy as np


def stream_rng(seed: int, stream: int) -> np.random.Generator:
    """The generator of one independent stream (a fit trace, the order of
    the sizes) of a run seeded with ``seed``."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), int(stream)]))


def lists_to_csr(queries):
    """CSR of a list of already sorted, deduplicated int arrays."""
    ptr = np.zeros(len(queries) + 1, dtype=np.int64)
    np.cumsum([len(q) for q in queries], out=ptr[1:])
    nodes = (np.concatenate(queries).astype(np.int64) if queries
             else np.zeros(0, dtype=np.int64))
    return ptr, nodes
