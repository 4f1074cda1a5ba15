"""Single-source shortest paths (Bellman-Ford relaxation to fixed point,
paper Fig. 2 pseudocode).  A thin declaration over the operator API —
the :data:`repro.core.operators.shortest_path` operator on a weighted
graph.  The compute-heavier kernel of the pair: per-edge add + compare +
scatter-min, so load balancing pays off most here (paper Fig. 7 — every
proposed strategy beats the baseline)."""

from __future__ import annotations

from repro.core.engine import RunResult, make_strategy, run, run_batch
from repro.core.graph import CSRGraph
from repro.core.multi_source import BatchRunResult


def sssp(graph: CSRGraph, source: int = 0, strategy: str = "WD",
         record_degrees: bool = False, mode: str = "stepped",
         shards=None, partition: str = "degree", backend: str = "xla",
         schedule: str = "bsp", delta=None, async_shards: bool = False,
         **strategy_kwargs) -> RunResult:
    """``mode="fused"`` runs the traversal as one device dispatch (see
    :mod:`repro.core.fused`); ``"stepped"`` keeps per-iteration stats;
    ``shards=S`` partitions the graph over S devices (fused mode,
    SHARDABLE strategies — docs/sharding.md); ``backend="pallas"`` swaps
    the relax kernels for the fused Pallas lowering (docs/backends.md);
    ``schedule="delta"`` settles distance buckets in priority order —
    delta-stepping, the classic SSSP win on high-diameter graphs
    (``delta=`` overrides the auto-tuned bucket width) — and
    ``async_shards=True`` relaxes the sharded halo-combine cadence
    (docs/scheduling.md)."""
    assert graph.weighted, "SSSP needs a weighted graph"
    strat = make_strategy(strategy, **strategy_kwargs)
    return run(graph, source, strat, record_degrees=record_degrees,
               mode=mode, shards=shards, partition=partition,
               backend=backend, schedule=schedule, delta=delta,
               async_shards=async_shards)


def sssp_batch(graph: CSRGraph, sources, mode: str = "stepped",
               shards=None, partition: str = "degree",
               backend: str = "xla", schedule: str = "bsp",
               delta=None) -> BatchRunResult:
    """Shortest paths from K sources concurrently (dist is ``[K, N]``)."""
    assert graph.weighted, "SSSP needs a weighted graph"
    return run_batch(graph, sources, mode=mode, shards=shards,
                     partition=partition, backend=backend,
                     schedule=schedule, delta=delta)
