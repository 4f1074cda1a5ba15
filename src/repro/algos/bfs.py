"""Breadth-first search as level propagation (paper §IV processing kernel).

A thin declaration over the operator API: BFS is the
:data:`repro.core.operators.shortest_path` operator on an unweighted
graph (every edge weight 1, so min-plus relaxation counts levels).

BFS is the memory-bound member of the pair: almost no arithmetic per edge,
so strategy overheads dominate unless the graph is large (paper Fig. 8).
Computing the minimum level distributes over +1, which is exactly the
distributivity property edge-based parallelism requires (§II-B).
"""

from __future__ import annotations

from repro.core.engine import RunResult, make_strategy, run, run_batch
from repro.core.graph import CSRGraph
from repro.core.multi_source import BatchRunResult


def _unweighted(graph: CSRGraph) -> CSRGraph:
    if not graph.weighted:
        return graph
    return CSRGraph(graph.row_ptr, graph.col, None,
                    graph.num_nodes, graph.num_edges, graph.max_degree)


def bfs(graph: CSRGraph, source: int = 0, strategy: str = "WD",
        record_degrees: bool = False, mode: str = "stepped",
        shards=None, partition: str = "degree", backend: str = "xla",
        schedule: str = "bsp", delta=None, async_shards: bool = False,
        **strategy_kwargs) -> RunResult:
    """``mode="fused"`` runs the traversal as one device dispatch (see
    :mod:`repro.core.fused`); ``"stepped"`` keeps per-iteration stats;
    ``shards=S`` partitions the graph over S devices (fused mode,
    SHARDABLE strategies — docs/sharding.md); ``backend="pallas"`` swaps
    the relax kernels for the fused Pallas lowering (docs/backends.md);
    ``schedule="delta"`` settles level buckets in priority order (all
    unit weights are light, so buckets are Δ levels wide) and
    ``async_shards=True`` relaxes the sharded halo-combine cadence
    (docs/scheduling.md)."""
    strat = make_strategy(strategy, **strategy_kwargs)
    return run(_unweighted(graph), source, strat,
               record_degrees=record_degrees, mode=mode, shards=shards,
               partition=partition, backend=backend, schedule=schedule,
               delta=delta, async_shards=async_shards)


def bfs_batch(graph: CSRGraph, sources, mode: str = "stepped",
              shards=None, partition: str = "degree",
              backend: str = "xla", schedule: str = "bsp",
              delta=None) -> BatchRunResult:
    """Level-propagate from K sources concurrently (dist is ``[K, N]``)."""
    return run_batch(_unweighted(graph), sources, mode=mode, shards=shards,
                     partition=partition, backend=backend,
                     schedule=schedule, delta=delta)
