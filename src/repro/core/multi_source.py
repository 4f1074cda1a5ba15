"""Batched multi-source fixed-point engine (serving workload).

``repro.core.engine.run`` answers one query (one source) per call.  A
serving deployment answers many BFS/SSSP queries against the *same* graph
concurrently, so this module batches K sources into one fixed-point run:

* ``dist`` becomes ``[K, N]`` and the frontier a ``[K, N]`` boolean mask;
* the per-iteration relax is the WD (merge-path) kernel ``vmap``-ed over
  the source axis — one fused device dispatch per iteration for all K
  queries, instead of K host round-trips;
* frontier capacities are *shared* across the batch: every iteration takes
  the widest live frontier / largest edge total over the K sources, rounds
  it up with :func:`repro.core.worklist.bucket`, and dispatches one jitted
  specialization.  Sources whose frontier is already empty ride along as
  fully-masked lanes (their compacted worklist is all ``-1``), which keeps
  shapes uniform — the batch analogue of the paper's padded-lane imbalance.

Queries of different depths finish at different iterations; a finished row
simply stops producing frontier bits.  :func:`refill_slot` swaps a fresh
source into a finished row without touching the other K-1 rows, which is
what the continuous-batching serving loop in
``examples/serve_graph_queries.py`` builds on.

Execution modes (``run_batch(..., mode=)``):

* ``"stepped"`` — the loop above: one ``batched_wd_relax`` dispatch per
  iteration, with the host in between syncing the mask
  (``np.asarray(mask_b)``) to size worklist capacities and collect
  per-iteration stats.  **Host-stepped**: do not call from traced code.
* ``"fused"`` — the whole batch to its fixed point in one
  ``lax.while_loop`` dispatch (K queries × zero host syncs), via
  :func:`repro.core.fused.run_batch_fixed_point`: the dense-mask WD step
  vmapped over sources, capacities fixed at the graph's static shapes, so
  no per-iteration bucketing (and no per-iteration ``iter_stats``).

Fused-safety note for contributors: :func:`init_batch`,
:func:`refill_slot` and :func:`batched_wd_relax` are pure jitted device
functions (safe to compose into traced code); :func:`run_batch` itself is
a host driver — its ``int()``/``np.asarray`` syncs must never move inside
a ``jit``/``while_loop`` boundary.
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import operators
from repro.core.graph import CSRGraph
from repro.core.operators import EdgeOp
from repro.core.schedule import DEFAULT_SCHEDULE, Schedule
from repro.core.strategies import IterStats, wd_relax
from repro.core.worklist import bucket, compact_mask


@dataclasses.dataclass
class BatchRunResult:
    dist: np.ndarray                 # [K, N] final distances / levels
    sources: np.ndarray              # [K] the batched source nodes
    iterations: int                  # fixed-point iterations for the batch
    total_seconds: float
    edges_relaxed: int               # summed over all K sources
    iter_stats: list
    strategy: str = "WD-batch"
    mode: str = "stepped"            # "stepped" or "fused"
    #: shard count (1 = single-device); ``edges_relaxed`` counts each
    #: relaxed edge exactly once across shards (see docs/sharding.md)
    shards: int = 1
    #: relax-kernel backend ("xla" or "pallas", docs/backends.md)
    backend: str = "xla"
    #: work ordering: "bsp" iterations or "delta" bucket epochs; under
    #: delta, ``iterations`` counts the SLOWEST row's epochs
    #: (docs/scheduling.md)
    schedule: str = "bsp"
    #: bucket width of a delta batch (None for BSP)
    delta: Optional[int] = None
    #: slowest row's relax rounds (== iterations for BSP)
    relax_rounds: Optional[int] = None
    #: trailing rows that are padding, not real queries (``pad_to=`` —
    #: the serving tier's K-bucketing; ``dist[:K - pad_lanes]`` are the
    #: requested rows).  ``edges_relaxed`` includes padded lanes' work
    #: (they relax real edges), so occupancy accounting lives with the
    #: caller that chose the bucket (repro.serve, docs/serving.md).
    pad_lanes: int = 0

    def __post_init__(self):
        if self.relax_rounds is None:
            self.relax_rounds = self.iterations

    @property
    def mteps(self) -> float:
        if self.total_seconds <= 0:
            return 0.0
        return self.edges_relaxed / self.total_seconds / 1e6

    @property
    def queries_per_second(self) -> float:
        if self.total_seconds <= 0:
            return 0.0
        return self.sources.shape[0] / self.total_seconds


@partial(jax.jit, static_argnames=("cap", "cap_work", "op", "backend",
                                   "sched"))
def batched_wd_relax(g: CSRGraph, dist_b, mask_b, *, cap: int,
                     cap_work: int,
                     op: EdgeOp = operators.shortest_path,
                     backend: str = "xla",
                     sched: Schedule = DEFAULT_SCHEDULE):
    """One relax iteration for all K sources: vmap of compact + WD relax.

    ``cap`` (frontier slots) and ``cap_work`` (edge lanes) are shared by
    the whole batch — the largest per-source requirement, bucketed.  The
    edge operator rides into the vmapped body as a static closure, so all
    K rows relax under identical semantics; ``backend`` picks the relax
    lowering per row and ``sched`` the work-assignment schedule
    (docs/backends.md, docs/schedules.md)."""
    def one(dist, mask):
        frontier = compact_mask(mask, cap)
        cursor = jnp.zeros((cap,), jnp.int32)
        return wd_relax(g, dist, frontier, cursor, cap_work=cap_work, op=op,
                        backend=backend, sched=sched)

    return jax.vmap(one)(dist_b, mask_b)


@partial(jax.jit, static_argnames=("num_nodes", "op"))
def init_batch(num_nodes: int, sources: jax.Array,
               op: EdgeOp = operators.shortest_path):
    """Initial ``[K, N]`` values / frontier-mask for a batch of sources."""
    k = sources.shape[0]
    rows = jnp.arange(k)
    dist = (jnp.full((k, num_nodes), op.identity, op.dtype)
            .at[rows, sources].set(op.seed(sources)))
    mask = jnp.zeros((k, num_nodes), jnp.bool_).at[rows, sources].set(True)
    return dist, mask


@partial(jax.jit, static_argnames=("op",))
def refill_slot(dist_b, mask_b, slot: jax.Array, source: jax.Array,
                op: EdgeOp = operators.shortest_path):
    """Admit a new query into row ``slot``: reset its value row and seed its
    frontier at ``source``.  Other rows are untouched, so in-flight queries
    keep converging — continuous batching for graph queries."""
    n = dist_b.shape[1]
    row = (jnp.full((n,), op.identity, op.dtype)
           .at[source].set(op.seed(source)))
    frontier_row = jnp.zeros((n,), jnp.bool_).at[source].set(True)
    return dist_b.at[slot].set(row), mask_b.at[slot].set(frontier_row)


def run_batch(graph: CSRGraph, sources, *, max_iterations: int = 100000,
              mode: str = "stepped", op="shortest_path",
              shards: Optional[int] = None,
              partition: str = "degree",
              backend: str = "xla", schedule: str = "bsp",
              delta: Optional[int] = None,
              pad_to: Optional[int] = None,
              work_schedule: Optional[Schedule] = None) -> BatchRunResult:
    """Fixed-point driver over K sources at once.

    Semantics match K independent ``engine.run`` calls exactly (same
    operator relax per source); only the batching differs.  With the
    default ``shortest_path`` operator, ``graph.wt is None`` ⇒ BFS
    levels, else SSSP distances; pass any
    :class:`repro.core.operators.EdgeOp` (or registered name) as ``op``
    for other semantics.  ``mode="fused"`` runs the whole batch in one
    device dispatch (see module docstring); ``shards=S`` additionally
    partitions the graph over S devices and maps the *sharded* WD step
    over the source axis — bit-identical dist/iterations/edges to the
    single-device batch (:mod:`repro.core.shard`, docs/sharding.md).
    ``backend="pallas"`` routes every row's WD relax through the fused
    Pallas kernel — bit-identical again, sharded or not
    (docs/backends.md).  ``schedule="delta"`` (fused mode, single
    device, idempotent operators) runs every row as its own
    delta-stepping traversal — rows settle different buckets in the
    same joint dispatch, so ``iterations``/``relax_rounds`` report the
    slowest row (:mod:`repro.core.priority`, docs/scheduling.md).
    ``pad_to=P`` rounds the batch up to P lanes (duplicating the first
    source) so differently-sized batches share one compiled [P, N]
    executable — the serving tier's K-bucketing (docs/serving.md);
    ``BatchRunResult.pad_lanes`` counts the synthetic trailing rows.
    ``work_schedule`` supplies the work-assignment
    :class:`~repro.core.schedule.Schedule` (worklist floor, tile/chunk
    shapes — docs/schedules.md); default is the pre-extraction constants.
    """
    if mode not in ("stepped", "fused"):
        raise ValueError(
            f"mode must be 'stepped' or 'fused', got {mode!r}")
    if shards is not None and mode != "fused":
        raise ValueError(
            "sharded batches run the whole fixed point on-device under "
            "shard_map, i.e. the fused engine; pass mode='fused' "
            "(docs/sharding.md)")
    from repro.core.engine import _check_backend, _check_schedule
    _check_backend(None, backend, shards)
    if backend == "pallas":
        graph = graph.plain()        # whole weight tables (engine.run)
    op = operators.resolve(op)
    _check_schedule(None, schedule, delta, op, shards, False)
    if schedule == "delta" and mode != "fused":
        raise ValueError(
            "batched delta-stepping vmaps whole per-row traversals, a "
            "fused-only construction; pass mode='fused' "
            "(docs/scheduling.md)")
    np_dtype = np.dtype(op.dtype)
    sources = np.asarray(sources, np.int32)
    pad_lanes = 0
    if pad_to is not None:
        # K-bucketing for the serving tier (repro.serve): round the batch
        # up to a caller-chosen bucket so repeated batches of different
        # sizes share one [pad_to, N] compiled executable.  Pad lanes
        # re-run the first real source (node 0 on an empty batch) — they
        # converge with the batch and the caller slices them off.
        if pad_to < sources.shape[0]:
            raise ValueError(
                f"pad_to={pad_to} is smaller than the batch "
                f"({sources.shape[0]} sources); pick a bucket >= K")
        pad_lanes = pad_to - int(sources.shape[0])
        if pad_lanes:
            fill = sources[0] if sources.shape[0] else np.int32(0)
            sources = np.concatenate(
                [sources, np.full(pad_lanes, fill, np.int32)])
    k = int(sources.shape[0])
    n = graph.num_nodes
    if k == 0:
        return BatchRunResult(dist=np.zeros((0, n), np_dtype),
                              sources=sources, iterations=0,
                              total_seconds=0.0, edges_relaxed=0,
                              iter_stats=[], mode=mode, shards=shards or 1,
                              backend=backend, schedule=schedule,
                              delta=delta, pad_lanes=pad_lanes)
    if graph.num_edges == 0:
        dist = np.full((k, n), op.identity, np_dtype)
        dist[np.arange(k), sources] = op.seed(sources)
        return BatchRunResult(dist=dist, sources=sources, iterations=0,
                              total_seconds=0.0, edges_relaxed=0,
                              iter_stats=[], mode=mode, shards=shards or 1,
                              backend=backend, schedule=schedule,
                              delta=delta, pad_lanes=pad_lanes)

    sched = work_schedule if work_schedule is not None else DEFAULT_SCHEDULE
    t0 = time.perf_counter()
    dist_b, mask_b = init_batch(n, jnp.asarray(sources), op=op)

    if schedule == "delta":
        from repro.core import priority
        from repro.core.strategies import make_strategy
        wd = make_strategy("WD", schedule=sched)
        dplan = priority.plan_delta(wd, wd.setup(graph), graph, op=op,
                                    delta=delta)
        dist_b, iterations, rounds, edges = priority.run_batch_fixed_point(
            dplan, dist_b, mask_b, op=op, max_iterations=max_iterations,
            backend=backend)
        total_s = time.perf_counter() - t0
        return BatchRunResult(dist=np.asarray(dist_b), sources=sources,
                              iterations=iterations, total_seconds=total_s,
                              edges_relaxed=edges, iter_stats=[],
                              mode="fused", backend=backend,
                              schedule="delta", delta=dplan.delta,
                              relax_rounds=rounds, pad_lanes=pad_lanes)

    if shards is not None:
        from repro.core import shard
        mesh = shard.shard_mesh(shards)
        sharded, _info = shard.partition(graph, shards, method=partition,
                                         mesh=mesh)
        dist_b, iterations, edges = shard.run_batch_fixed_point(
            sharded, dist_b, mask_b, mesh=mesh, op=op,
            max_iterations=max_iterations, sched=sched, backend=backend)
        total_s = time.perf_counter() - t0
        return BatchRunResult(dist=np.asarray(dist_b), sources=sources,
                              iterations=iterations, total_seconds=total_s,
                              edges_relaxed=edges, iter_stats=[],
                              mode="fused", shards=shards, backend=backend,
                              pad_lanes=pad_lanes)

    if mode == "fused":
        from repro.core import fused
        dist_b, iterations, edges = fused.run_batch_fixed_point(
            graph, dist_b, mask_b, op=op, max_iterations=max_iterations,
            backend=backend, sched=sched)
        total_s = time.perf_counter() - t0
        return BatchRunResult(dist=np.asarray(dist_b), sources=sources,
                              iterations=iterations, total_seconds=total_s,
                              edges_relaxed=edges, iter_stats=[],
                              mode="fused", backend=backend,
                              pad_lanes=pad_lanes)

    degrees = np.asarray(graph.degrees)
    iter_stats: list[IterStats] = []
    edges = 0
    it = 0
    while it < max_iterations:
        mask_np = np.asarray(mask_b)
        counts = mask_np.sum(axis=1)
        widest = int(counts.max())
        if widest == 0:
            break
        # per-source edge totals; the batch dispatches at the largest
        totals = mask_np.astype(np.int64) @ degrees.astype(np.int64)
        cap = bucket(widest, sched.min_bucket)
        cap_work = bucket(int(totals.max()), sched.min_bucket)
        dist_b, mask_b = batched_wd_relax(graph, dist_b, mask_b,
                                          cap=cap, cap_work=cap_work, op=op,
                                          backend=backend, sched=sched)
        jax.block_until_ready(dist_b)
        edges += int(totals.sum())
        iter_stats.append(IterStats(frontier_size=widest,
                                    edges_processed=int(totals.sum()),
                                    kernel="WD"))
        it += 1
    total_s = time.perf_counter() - t0
    return BatchRunResult(dist=np.asarray(dist_b), sources=sources,
                          iterations=it, total_seconds=total_s,
                          edges_relaxed=edges, iter_stats=iter_stats,
                          backend=backend, pad_lanes=pad_lanes)
