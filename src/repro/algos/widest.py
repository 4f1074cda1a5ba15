"""Widest path (maximum bottleneck bandwidth) — a thin declaration over
the operator API.

A path's *width* is its thinnest edge; the widest path maximizes that
bottleneck — the routing/bandwidth twin of SSSP (max-min instead of
min-plus, both closed semirings).  The whole algorithm is
:data:`repro.core.operators.widest_path`: ``message = min(val_src, w)``,
``combine = max``, identity 0 (unreachable), source seeded at ``INF``
(the empty path is unbounded).  Every load-balancing strategy and both
execution modes apply unchanged — the schedule never knew it was
computing distances in the first place.

On unweighted graphs every edge has implicit width 1, so reachable nodes
get width 1 — use :func:`repro.algos.bfs.bfs` if that is what you want.
"""

from __future__ import annotations

import heapq

import numpy as np

from repro.core.engine import RunResult, make_strategy, run
from repro.core.graph import CSRGraph, INF


def widest_path(graph: CSRGraph, source: int = 0, strategy: str = "WD",
                record_degrees: bool = False, mode: str = "stepped",
                shards=None, partition: str = "degree",
                backend: str = "xla", schedule: str = "bsp", delta=None,
                async_shards: bool = False,
                **strategy_kwargs) -> RunResult:
    """Max-min bottleneck width from ``source`` to every node.

    ``result.dist[v]`` is the largest width over all source→v paths
    (0 = unreachable, INF = the source itself).  ``mode="fused"`` runs
    the traversal as one device dispatch (see :mod:`repro.core.fused`);
    ``backend="pallas"`` swaps the relax kernels (docs/backends.md);
    ``schedule="delta"`` settles *widest* buckets first (the max monoid
    reflects the rank, docs/scheduling.md) and ``async_shards=True``
    relaxes the sharded halo-combine cadence."""
    strat = make_strategy(strategy, **strategy_kwargs)
    return run(graph, source, strat, op="widest_path",
               record_degrees=record_degrees, mode=mode, shards=shards,
               partition=partition, backend=backend, schedule=schedule,
               delta=delta, async_shards=async_shards)


def reference_widest(graph: CSRGraph, source: int) -> np.ndarray:
    """Host-side widest-path oracle for correctness tests: Dijkstra with
    a max-heap on path width (the NetworkX-style reference)."""
    row_ptr = np.asarray(graph.row_ptr)
    col = np.asarray(graph.col)
    wt = (np.ones(graph.num_edges, np.int64) if not graph.weighted
          else np.asarray(graph.wt, np.int64))
    n = graph.num_nodes
    width = np.zeros(n, np.int64)
    width[source] = INF
    heap = [(-int(INF), source)]
    while heap:
        c, u = heapq.heappop(heap)
        c = -c
        if c < width[u]:
            continue
        for e in range(row_ptr[u], row_ptr[u + 1]):
            v = col[e]
            nc = min(c, wt[e])
            if nc > width[v]:
                width[v] = nc
                heapq.heappush(heap, (-int(nc), v))
    return width.astype(np.int32)
