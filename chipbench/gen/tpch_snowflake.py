"""The paper's synthetic snowflake deployment with TPC-H-like column sizes
(arXiv:1302.4168, the TPC-H experiment of fig. 8): a snowflake schema with
one item per column, column sizes from 25 KB to 28 GB as the paper reports
them at SF=25, and queries that are connected subgraphs of the schema
(joins along the tree plus attribute accesses).  It is not TPC-H's own
schema (8 tables, 61 columns) nor its 22 query templates.

The schema and the queries are copied from the program's
``repro.core.workloads`` (``snowflake_workload``,
``_connected_subgraph_query``): the same schema tree and the same walk,
with its uniform draws taken from numpy in blocks.  The sizes
are one fixed set in one fixed order (`size_set`, `node_weights`), where
the program's ``tpch_heterogeneous`` draws a new random mixture per seed
and rescales it.  Every draw comes from the stream generators of
``chipbench.gen``."""

from __future__ import annotations

import numpy as np

from . import stream_rng

_WEIGHT_STREAM = 1000


def schema_edges(num_items: int, levels: int, degree: int) -> np.ndarray:
    """Edges of the schema tree: tables fan out ``degree`` per level for
    ``levels`` levels, then attribute items hang round-robin off the table
    keys."""
    edges = []
    table_keys = [0]  # item 0 = root fact-table key
    next_item = 1
    frontier = [0]
    level = 1
    while next_item < num_items and level < levels:
        new_frontier = []
        for parent_key in frontier:
            for _ in range(degree):
                if next_item >= num_items:
                    break
                child_key = next_item
                next_item += 1
                edges.append((parent_key, child_key))  # join edge
                table_keys.append(child_key)
                new_frontier.append(child_key)
        frontier = new_frontier
        level += 1
    ti = 0
    while next_item < num_items:
        edges.append((table_keys[ti % len(table_keys)], next_item))
        next_item += 1
        ti += 1
    return np.asarray(edges, dtype=np.int64)


def adjacency(num_items: int, edges: np.ndarray) -> list[list[int]]:
    """Sorted neighbours of every item, as Python ints (the query walk
    reads them one at a time)."""
    adj: list[set[int]] = [set() for _ in range(num_items)]
    for a, b in edges.tolist():
        adj[a].add(b)
        adj[b].add(a)
    return [sorted(x) for x in adj]


class _Draws:
    """Uniform indices from one stream, drawn from numpy in blocks: the
    query walk asks for one index at a time, and a scalar call to the
    generator costs more than the walk's own step."""

    def __init__(self, rng: np.random.Generator, block: int = 1 << 16):
        self.rng, self.block = rng, block
        self.buf, self.pos = [], 0

    def index(self, n: int) -> int:
        """A uniform draw from ``range(n)``."""
        if self.pos == len(self.buf):
            self.buf, self.pos = self.rng.random(self.block).tolist(), 0
        u = self.buf[self.pos]
        self.pos += 1
        return int(u * n)


def connected_query(adj: list[list[int]], draws: _Draws,
                    size: int) -> np.ndarray:
    """Random connected subgraph by frontier growth from a random seed."""
    start = draws.index(len(adj))
    chosen = {start}
    frontier = list(adj[start])
    while len(chosen) < size and frontier:
        v = frontier.pop(draws.index(len(frontier)))
        if v in chosen:
            continue
        chosen.add(v)
        frontier.extend(u for u in adj[v] if u not in chosen)
    return np.asarray(sorted(chosen), dtype=np.int64)


def size_set(cfg: dict) -> np.ndarray:
    """The deployment's column sizes in GB, one fixed set for every seed:
    fact-table columns log-spaced over [fact_lo, hi] and dimension columns
    log-spaced over [lo, dim_hi], both ends included.  The number of fact
    columns is the one that brings the total nearest ``fill`` x
    ``target_min_partitions`` x ``capacity``, and a last scale (within a
    fraction of a percent of 1) makes it exact, so the data needs exactly
    ``target_min_partitions`` partitions while the sizes still span
    [lo, hi]."""
    n = int(cfg["num_items"])
    lo, hi = float(cfg["size_lo_gb"]), float(cfg["size_hi_gb"])
    fact_lo, dim_hi = float(cfg["fact_lo_gb"]), float(cfg["dim_hi_gb"])
    target = (float(cfg["fill"]) * float(cfg["target_min_partitions"])
              * float(cfg["capacity"]))

    def sizes(n_fact: int) -> np.ndarray:
        return np.concatenate([
            np.exp(np.linspace(np.log(fact_lo), np.log(hi), n_fact)),
            np.exp(np.linspace(np.log(lo), np.log(dim_hi), n - n_fact))])

    n_fact = min(range(n + 1), key=lambda k: abs(sizes(k).sum() - target))
    w = sizes(n_fact)
    return w * (target / w.sum())


def node_weights(cfg: dict, seed: int) -> np.ndarray:
    """Column sizes in GB: the fixed `size_set`, dealt to the schema's
    columns in the deployment's own order (stream ``size_order_seed``).
    The data is the deployment's and does not change with the run's seed;
    the traces do."""
    del seed
    rng = stream_rng(int(cfg["size_order_seed"]), _WEIGHT_STREAM)
    return rng.permutation(size_set(cfg))


def query_lists(cfg: dict, seed: int, stream: int, count: int):
    """``count`` queries of stream ``stream`` as sorted item arrays."""
    n = int(cfg["num_items"])
    adj = adjacency(n, schema_edges(n, int(cfg["levels"]), int(cfg["degree"])))
    draws = _Draws(stream_rng(seed, stream))
    lo, hi = int(cfg["min_query"]), int(cfg["max_query"])
    return [connected_query(adj, draws, lo + draws.index(hi - lo + 1))
            for _ in range(int(count))]
