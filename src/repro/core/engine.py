"""Data-driven execution engine (paper Fig. 2 / Fig. 4 outer loop).

Runs a relax-style propagation algorithm to a fixed point under any
registered load-balancing strategy (the paper's five plus the adaptive
AD).  *What* is propagated is an :class:`repro.core.operators.EdgeOp`
(``op=`` on every entry point, default ``shortest_path`` — BFS levels on
unweighted graphs, SSSP distances on weighted ones; see
docs/operators.md).  Two execution modes (see docs/architecture.md for
the dispatch-timeline picture):

* ``mode="stepped"`` (default) — one jit dispatch per frontier iteration,
  with the frontier counted/compacted on the host between dispatches.
  This is the stats-rich path: per-iteration :class:`IterStats`,
  ``record_degrees`` for the balance analysis, kernel/overhead time split.
* ``mode="fused"`` — the whole traversal as **one** ``lax.while_loop``
  dispatch (:mod:`repro.core.fused`): no host round-trips, so dispatch
  latency stops polluting MTEPS.  Distances, iteration counts and edge
  totals are bit-identical to stepped mode; per-iteration stats are not
  collected (``iter_stats`` is empty).

Batched multi-source execution lives in :mod:`repro.core.multi_source`
and is exposed here as :func:`run_batch` (same two modes).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fused as _fused
from repro.core import operators
from repro.core import priority as _priority
from repro.core import shard as _shard
from repro.core.graph import CSRGraph, INF
from repro.core.schedule import Schedule
from repro.core.strategies import (
    BACKENDS, AdaptiveStrategy, EdgeBased, FRONTIER_INIT, IterStats,
    NodeSplitting, PALLAS_BACKEND, PRIORITY_SCHEDULE, SHARDABLE,
    StrategyBase, make_strategy, pallas_relax)  # noqa: F401  (make_strategy re-exported: engine.make_strategy)

#: work-ordering schedules engine.run/fixed_point/run_batch accept:
#: "bsp" relaxes the whole frontier every iteration (bulk-synchronous,
#: the default and the paper's framing); "delta" settles distance
#: buckets in priority order (repro.core.priority, docs/scheduling.md)
SCHEDULES = ("bsp", "delta")


@dataclasses.dataclass
class RunResult:
    dist: np.ndarray                 # [N] final distances / levels
    iterations: int
    total_seconds: float
    #: strategy set-up, plans and the seeded start arrays (prep,
    #: conversion) — the interval of the ``engine.setup`` host span
    setup_seconds: float
    kernel_seconds: float            # useful relax time (paper's split)
    overhead_seconds: float          # scan/compaction/push bookkeeping
    edges_relaxed: int
    iter_stats: list
    strategy: str
    state_bytes: int                 # device bytes held by the strategy
    mode: str = "stepped"            # "stepped" or "fused"
    #: relax-kernel backend of the run: "xla" (gather/scatter HLOs) or
    #: "pallas" (fused scatter-combine kernels, repro.kernels.relax) —
    #: bit-identical results either way (docs/backends.md)
    backend: str = "xla"
    #: shard count of the run (1 = single-device).  ``edges_relaxed``
    #: counts each relaxed edge exactly once ACROSS shards (every shard
    #: sums only the masked degrees of nodes it owns and the totals are
    #: psum-folded once), so :attr:`mteps` needs no per-shard correction
    #: and stays directly comparable to single-device figures.
    shards: int = 1
    #: work ordering of the run: "bsp" iterations or "delta" bucket
    #: epochs (docs/scheduling.md).  ``iterations`` counts the schedule's
    #: own outer unit — frontier iterations for BSP, bucket epochs for
    #: delta, halo-combine epochs for async shards — and that unit is
    #: what ``max_iterations`` caps.
    schedule: str = "bsp"
    #: bucket width of a delta run (None for BSP)
    delta: Optional[int] = None
    #: relax rounds — the finer-grained unit comparable ACROSS schedules
    #: (a BSP iteration is one round; a delta epoch spends one round per
    #: light-closure pass plus one per non-empty heavy pass; an async
    #: epoch's rounds follow the deepest shard's local loop).  Filled
    #: with ``iterations`` when the schedule has no finer unit.
    relax_rounds: Optional[int] = None
    #: True when shards ran ahead asynchronously between halo combines
    #: (engine.run(..., async_shards=True) — docs/scheduling.md)
    async_shards: bool = False
    #: the resolved work-assignment :class:`repro.core.schedule.Schedule`
    #: the run executed under (concrete MDT etc.) — NOT the work-ordering
    #: string above; see docs/schedules.md for the naming split.  None on
    #: degenerate no-edge runs.
    work_schedule: Optional[Schedule] = None
    #: AD's kernel choices, ``{kernel: iterations}`` over the iterations
    #: AD ran (fused and stepped); empty for the other strategies and
    #: for the shards=/delta paths
    kernel_counts: dict = dataclasses.field(default_factory=dict)
    #: relax blocks the single-device fused loop ran (the lane blocks of
    #: its relax batches, ``fused._relax_batch``) and the sum of their
    #: widths; ``edges_relaxed / lanes_run`` is the share of lanes that
    #: did work.  None on the paths that do not count them (stepped,
    #: shards=, schedule="delta")
    relax_batches: Optional[int] = None
    lanes_run: Optional[int] = None

    def __post_init__(self):
        if self.relax_rounds is None:
            self.relax_rounds = self.iterations

    @property
    def traversal_seconds(self) -> float:
        """Time spent in the fixed-point loop, excluding one-off strategy
        setup (NS graph morph, EP COO conversion, ...)."""
        return max(self.total_seconds - self.setup_seconds, 0.0)

    @property
    def mteps(self) -> float:
        """Millions of traversed edges per second of *traversal* time.

        Setup is excluded so fused/stepped (and per-strategy) comparisons
        aren't skewed by one-off prep; use :attr:`mteps_with_setup` for
        the end-to-end figure."""
        if self.traversal_seconds <= 0:
            return 0.0
        return self.edges_relaxed / self.traversal_seconds / 1e6

    @property
    def mteps_with_setup(self) -> float:
        if self.total_seconds <= 0:
            return 0.0
        return self.edges_relaxed / self.total_seconds / 1e6


def ready(x):
    """Block until ``x``'s device computations finish, then return it.

    The public readiness helper for host-stepped drivers and examples —
    use this instead of reaching for ``jax.block_until_ready`` (or the
    old private ``engine._ready``) so timing loops across the repo block
    the same way."""
    jax.block_until_ready(x)
    return x


_ready = ready    # backwards-compat alias (pre-operator-API imports)


def _check_sharding(strategy: StrategyBase, mode: str,
                    shards: Optional[int]) -> None:
    """Validate a ``shards=`` request (shared by run/fixed_point)."""
    if shards is None:
        return
    if mode != "fused":
        raise ValueError(
            "sharded execution runs the whole traversal on-device under "
            "shard_map, i.e. the fused engine; pass mode='fused' "
            "(docs/sharding.md)")
    if SHARDABLE not in strategy.capabilities:
        raise ValueError(
            f"strategy {strategy.name!r} does not declare the "
            f"{SHARDABLE!r} capability; sharding is gated on BS/WD/HP/NS "
            f"(EP's COO worklist and AD's global frontier statistics "
            f"stay single-device — docs/sharding.md)")


def _check_backend(strategy: Optional[StrategyBase], backend: str,
                   shards: Optional[int]) -> None:
    """Validate a ``backend=`` request (shared by run/fixed_point and,
    with ``strategy=None``, by the WD-only batch driver).

    ``shards`` no longer restricts the backend: every SHARDABLE
    strategy's Pallas lowering runs per-shard under ``shard_map`` with
    the ghost combine fused into the kernel epilogue
    (:mod:`repro.core.shard`, docs/backends.md) — the sharding gate
    itself lives in :func:`_check_sharding`."""
    del shards
    if backend not in BACKENDS:
        raise ValueError(
            f"backend must be one of {BACKENDS}, got {backend!r}")
    if backend == "xla":
        return
    if strategy is not None and PALLAS_BACKEND not in strategy.capabilities:
        raise ValueError(
            f"strategy {strategy.name!r} does not declare the "
            f"{PALLAS_BACKEND!r} capability; its kernels have no Pallas "
            f"lowering — use backend='xla' (docs/backends.md)")


#: relax kernels each fused lowering dispatches under backend="pallas"
#: (repro.kernels.relax: "lanes" = relax_lanes, "wd" = wd_relax_lanes)
_PALLAS_KERNELS = {"BS": ("lanes",), "NS": ("lanes",), "EP": ("lanes",),
                   "WD": ("wd",), "HP": ("lanes", "wd"),
                   "AD": ("lanes", "wd")}


def _check_pallas_fit(strategy: StrategyBase, n_alloc: int, num_edges: int,
                      splan=None) -> None:
    """Refuse ``backend="pallas"`` with a ``ValueError`` naming the bytes
    when the strategy's kernels cannot keep this graph's tables in VMEM
    (:func:`repro.kernels.relax.check_vmem`) — before anything compiles.
    Sharded runs are checked per shard: the replicated ``[N]`` values
    plus the widest shard's slot and edge tables."""
    kernels = _PALLAS_KERNELS.get(_fused.fused_kernel_name(type(strategy)),
                                  ("lanes", "wd"))
    f, e = n_alloc, num_edges
    if splan is not None:
        f = splan.sharded.nodes_per_shard
        e = splan.sharded.edges_per_shard
    sched = _fused._sched_of(strategy)
    pallas_relax.check_vmem(kernels, n=n_alloc, f=f, e=e,
                            **pallas_relax.tile_kwargs(sched))


def _check_schedule(strategy: Optional[StrategyBase], schedule: str,
                    delta: Optional[int], op, shards: Optional[int],
                    async_shards: bool) -> None:
    """Validate the work-ordering knobs (shared by run/fixed_point).

    ``op`` must already be resolved.  The rules (docs/scheduling.md):
    delta-stepping needs a strategy with delta-phase lowerings
    (:data:`PRIORITY_SCHEDULE`), an idempotent operator (reordering
    changes non-idempotent fixed points) and a single device (bucket
    membership reads the global value array); async shards need sharded
    execution to exist at all, an idempotent operator (stale reads are
    only safe for monotone monoids) and the BSP schedule."""
    if schedule not in SCHEDULES:
        raise ValueError(
            f"schedule must be one of {SCHEDULES}, got {schedule!r}")
    if delta is not None and schedule != "delta":
        raise ValueError(
            f"delta= sets the bucket width of schedule='delta'; it has no "
            f"meaning under schedule={schedule!r}")
    if schedule == "delta":
        if strategy is not None and (
                PRIORITY_SCHEDULE not in strategy.capabilities):
            raise ValueError(
                f"strategy {strategy.name!r} does not declare the "
                f"{PRIORITY_SCHEDULE!r} capability; delta-stepping is "
                f"gated on the node-centric strategies (EP's edge "
                f"worklist has no per-node value to bucket by — "
                f"docs/scheduling.md)")
        if not op.idempotent:
            raise ValueError(
                f"schedule='delta' reorders relaxations; operator "
                f"{op.name!r} (combine={op.combine!r}) is not idempotent, "
                f"so its fixed point depends on relax order — use "
                f"schedule='bsp' (docs/scheduling.md)")
        if shards is not None:
            raise ValueError(
                "schedule='delta' is single-device (bucket selection "
                "reads the global value array); combine it with "
                "async_shards=False, shards=None — or use the BSP "
                "schedule for sharded runs (docs/scheduling.md)")
    if async_shards:
        if shards is None:
            raise ValueError(
                "async_shards=True relaxes the halo-combine cadence of "
                "SHARDED execution; pass shards= (and mode='fused') — "
                "docs/scheduling.md")
        if not op.idempotent:
            raise ValueError(
                f"async_shards=True lets shards relax against stale "
                f"ghost values, which is only safe for idempotent "
                f"monotone monoids; operator {op.name!r} has "
                f"combine={op.combine!r} (docs/scheduling.md)")


def run(graph: CSRGraph, source: int, strategy: StrategyBase, *,
        max_iterations: int = 100000, record_degrees: bool = False,
        mode: str = "stepped", op="shortest_path",
        shards: Optional[int] = None,
        partition: str = "degree", backend: str = "xla",
        schedule: str = "bsp", delta: Optional[int] = None,
        async_shards: bool = False) -> RunResult:
    """Fixed-point driver.  With the default ``shortest_path`` operator,
    ``graph.wt is None`` ⇒ BFS levels, else SSSP distances; any other
    :class:`repro.core.operators.EdgeOp` (or registered name) swaps the
    relax semantics without touching the schedule.

    ``mode="stepped"`` dispatches one jitted relax per frontier iteration
    and collects per-iteration stats; ``mode="fused"`` runs the whole
    traversal as one on-device ``while_loop`` dispatch (same values,
    iteration count and edge total — see :mod:`repro.core.fused`).
    ``record_degrees`` needs the host in the loop, so it requires stepped
    mode.

    ``shards=S`` (fused mode, :data:`repro.core.strategies.SHARDABLE`
    strategies only) partitions the graph over S devices and runs the
    fused kernels per-shard under ``shard_map``, combining ghost values
    with the operator's monoid at every chunk boundary — bit-identical
    dist/iterations/edges to the single-device paths
    (:mod:`repro.core.shard`; ``partition`` picks the node split:
    ``"degree"`` balances edges per shard, ``"contiguous"`` node
    counts).

    ``backend="pallas"`` (strategies declaring
    :data:`repro.core.strategies.PALLAS_BACKEND`) dispatches every
    relax through the fused scatter-combine kernels of
    :mod:`repro.kernels.relax` instead of XLA gather/scatter —
    bit-identical dist/iterations/edges in both modes, and it composes
    with ``shards=``: the kernels run per-shard with the ghost combine
    fused into the kernel epilogue (docs/backends.md).

    ``schedule="delta"`` (strategies declaring
    :data:`repro.core.strategies.PRIORITY_SCHEDULE`; idempotent
    operators; single-device) orders relaxations by distance bucket —
    delta-stepping, :mod:`repro.core.priority`.  ``delta=`` overrides
    the auto-tuned bucket width; ``iterations`` then counts bucket
    epochs (what ``max_iterations`` caps) and ``relax_rounds`` the
    BSP-comparable relax count.  ``async_shards=True`` (with
    ``shards=``) lets every shard relax its local frontier to a local
    fixed point between halo combines instead of combining every chunk
    — same final values for idempotent operators, fewer collectives;
    ``iterations`` then counts combine epochs (docs/scheduling.md).

    The edge layout is the graph's own (``CSRGraph.from_edges`` packs
    weights that fit beside their heads, docs/architecture.md), except
    under ``backend="pallas"``, whose kernels hold whole weight tables:
    ``engine.setup`` then decodes the words once (``CSRGraph.plain``)."""
    if mode not in ("stepped", "fused"):
        raise ValueError(
            f"mode must be 'stepped' or 'fused', got {mode!r}")
    if mode == "fused" and record_degrees:
        raise ValueError(
            "record_degrees collects per-iteration host-side stats; "
            "use mode='stepped'")
    if record_degrees and schedule != "bsp":
        raise ValueError(
            "record_degrees reports per-BSP-iteration frontier degrees; "
            "it has no bucket-epoch equivalent — use schedule='bsp'")
    op = operators.resolve(op)
    _check_sharding(strategy, mode, shards)
    _check_backend(strategy, backend, shards)
    _check_schedule(strategy, schedule, delta, op, shards, async_shards)
    if graph.num_edges == 0:        # degenerate: nothing to relax
        dist = np.full(graph.num_nodes, op.identity,
                       np.dtype(op.dtype))
        dist[source] = op.seed(source)
        return RunResult(dist=dist, iterations=0, total_seconds=0.0,
                         setup_seconds=0.0, kernel_seconds=0.0,
                         overhead_seconds=0.0, edges_relaxed=0,
                         iter_stats=[], strategy=strategy.name,
                         state_bytes=0, mode=mode, shards=shards or 1,
                         backend=backend, schedule=schedule, delta=delta,
                         async_shards=async_shards)
    # host spans (engine.setup here, engine.dispatch / engine.wait /
    # engine.readback around the fused call): no-ops unless a profiler runs
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("engine.setup"):
        if backend == "pallas":
            graph = graph.plain()
        state = strategy.setup(graph)
        splan = None
        dplan = None
        if shards is not None:
            # partitioning is one-off host preprocessing, booked as setup
            # like the NS morph / EP COO conversion
            splan = _shard.plan_shards(strategy, state, graph, shards,
                                       method=partition)
        if schedule == "delta":
            # the light/heavy edge split is host preprocessing too
            dplan = _priority.plan_delta(strategy, state, graph, op=op,
                                         delta=delta)
            delta = dplan.delta          # surface the auto-tuned width
        _ready(jax.tree_util.tree_leaves(state))

        if isinstance(strategy, NodeSplitting):
            n_alloc = strategy.split_info.graph.num_nodes
        else:
            n_alloc = graph.num_nodes
        if backend == "pallas":
            _check_pallas_fit(strategy, n_alloc, graph.num_edges, splan)

        dist = (jnp.full((n_alloc,), op.identity, op.dtype)
                .at[source].set(op.seed(source)))
        mask = jnp.zeros((n_alloc,), jnp.bool_).at[source].set(True)
    setup_s = time.perf_counter() - t0

    if mode == "fused":
        rounds = None
        counters = {}
        t_start = time.perf_counter()
        if splan is not None:
            dist, iterations, edges, rounds = _shard.run_fixed_point(
                splan, dist, mask, op=op, max_iterations=max_iterations,
                async_mode=async_shards, backend=backend)
        elif dplan is not None:
            dist, iterations, rounds, edges = _priority.run_fixed_point(
                dplan, dist, mask, op=op, max_iterations=max_iterations,
                backend=backend)
        else:
            dist, iterations, edges = _fused.run_fixed_point(
                graph, state, strategy, dist, mask, op=op,
                max_iterations=max_iterations, backend=backend,
                counters=counters)
        total_s = time.perf_counter() - t_start
        with jax.profiler.TraceAnnotation("engine.readback"):
            if isinstance(strategy, NodeSplitting):
                dist = strategy.split_info.extract_original(dist)
            dist = np.asarray(dist)
        state_bytes = strategy.state_bytes(state)
        if splan is not None:
            state_bytes += splan.sharded.device_bytes()
        if dplan is not None:
            state_bytes += dplan.device_bytes()
        # one dispatch: the kernel/overhead split collapses — the whole
        # traversal is kernel time, setup is the only host-side overhead
        return RunResult(
            dist=dist, iterations=iterations,
            total_seconds=total_s + setup_s, setup_seconds=setup_s,
            kernel_seconds=total_s, overhead_seconds=setup_s,
            edges_relaxed=edges, iter_stats=[], strategy=strategy.name,
            state_bytes=state_bytes, mode="fused", shards=shards or 1,
            backend=backend, schedule=schedule, delta=delta,
            relax_rounds=rounds, async_shards=async_shards,
            work_schedule=getattr(strategy, "resolved_schedule", None),
            **counters)

    iter_stats: list[IterStats] = []
    kernel_s = 0.0
    edges = 0
    rounds = None
    t_start = time.perf_counter()

    # only forward backend= when it deviates from the default: a
    # third-party strategy without the PALLAS_BACKEND capability (whose
    # iterate may predate the backend kwarg) must keep running
    # unchanged on the XLA path — the capability gate above already
    # rejected it for backend="pallas"
    extra = {} if backend == "xla" else {"backend": backend}

    if dplan is not None:
        # stepped delta: one jitted bucket epoch per dispatch; the host
        # syncs the frontier count between epochs (the delta analogue of
        # the per-iteration stepped loop) and records which bucket each
        # epoch settled — the invariant tests read it back
        count, it, rounds = 1, 0, 0
        while count > 0 and it < max_iterations:
            tk = time.perf_counter()
            dist, mask, b, r, e = _priority.step_epoch(
                dplan, dist, mask, op=op, backend=backend)
            ready(dist)
            kernel_s += time.perf_counter() - tk
            edges += e
            rounds += r
            iter_stats.append(IterStats(
                frontier_size=int(count), edges_processed=int(e),
                sub_iterations=int(r), bucket=int(b),
                kernel=f"delta:{dplan.kernel}"))
            count = int(jnp.sum(mask))
            it += 1
    elif isinstance(strategy, EdgeBased):
        wl, count = strategy.initial_worklist(state, source)
        it = 0
        while count > 0 and it < max_iterations:
            tk = time.perf_counter()
            relaxed = count          # worklist entries relaxed this round
            dist, new_mask, wl, count = strategy.relax_and_push(
                state, dist, wl, count, op=op, **extra)
            ready(dist)
            kernel_s += time.perf_counter() - tk
            edges += relaxed
            iter_stats.append(IterStats(frontier_size=int(relaxed),
                                        edges_processed=int(relaxed)))
            it += 1
    else:
        count, it = 1, 0
        while count > 0 and it < max_iterations:
            tk = time.perf_counter()
            dist, new_mask, stats = strategy.iterate(
                state, dist, mask, count, op=op,
                record_degrees=record_degrees, **extra)
            ready(dist)
            kernel_s += time.perf_counter() - tk
            iter_stats.append(stats)
            edges += stats.edges_processed
            mask = new_mask
            count = int(jnp.sum(mask))
            it += 1

    total_s = time.perf_counter() - t_start
    if isinstance(strategy, NodeSplitting):
        dist = strategy.split_info.extract_original(dist)
    state_bytes = strategy.state_bytes(state)
    if dplan is not None:
        state_bytes += dplan.device_bytes()
    return RunResult(
        dist=np.asarray(dist), iterations=len(iter_stats),
        total_seconds=total_s + setup_s, setup_seconds=setup_s,
        kernel_seconds=kernel_s,
        overhead_seconds=max(total_s - kernel_s, 0.0) + setup_s,
        edges_relaxed=int(edges), iter_stats=iter_stats,
        strategy=strategy.name,
        state_bytes=state_bytes, mode="stepped",
        backend=backend, schedule=schedule, delta=delta,
        relax_rounds=rounds,
        work_schedule=getattr(strategy, "resolved_schedule", None),
        kernel_counts=(dict(strategy.kernel_counts)
                       if isinstance(strategy, AdaptiveStrategy) else {}))


def fixed_point(graph: CSRGraph, strategy: StrategyBase, init, *,
                op="shortest_path", mode: str = "stepped",
                max_iterations: int = 100000,
                shards: Optional[int] = None,
                partition: str = "degree", backend: str = "xla",
                schedule: str = "bsp", delta: Optional[int] = None,
                async_shards: bool = False):
    """Run a strategy to its fixed point from a caller-supplied seeding.

    The escape hatch under :func:`run` for algorithms whose initial state
    is not "one source at distance zero": ``init(n_alloc)`` must return
    the initial ``(values, frontier_mask)`` pair on the strategy's
    allocation (``n_alloc`` is the split graph's node count for NS —
    children may be seeded arbitrarily; the first ``ns_activate`` mirror
    overwrites them with their parent's value).  ``connected_components``
    seeds every node with its own label this way.

    Requires a strategy with the :data:`repro.core.strategies.FRONTIER_INIT`
    capability (EP's edge worklist cannot represent an arbitrary dense
    frontier).  ``shards=S`` runs the fused kernels per-shard under
    ``shard_map`` (fused mode + SHARDABLE strategies only — see
    :func:`run` and docs/sharding.md); ``backend="pallas"`` swaps the
    relax lowering (see :func:`run` and docs/backends.md);
    ``schedule="delta"`` / ``async_shards=True`` swap the work ordering
    (see :func:`run` and docs/scheduling.md).  Returns
    ``(values, iterations, edges_relaxed)`` with ``values`` a host array
    on the *original* node allocation.

    ``max_iterations`` caps the schedule's own outer unit — BSP frontier
    iterations, delta bucket epochs, async combine epochs — identically
    in stepped and fused mode: a delta run capped at K stops after K
    epochs whether the epochs were host-stepped or fused
    (docs/scheduling.md pins this contract)."""
    if mode not in ("stepped", "fused"):
        raise ValueError(
            f"mode must be 'stepped' or 'fused', got {mode!r}")
    if FRONTIER_INIT not in strategy.capabilities:
        raise ValueError(
            f"strategy {strategy.name!r} does not declare the "
            f"{FRONTIER_INIT!r} capability; seeding an arbitrary frontier "
            f"needs a node strategy")
    op = operators.resolve(op)
    _check_sharding(strategy, mode, shards)
    _check_backend(strategy, backend, shards)
    _check_schedule(strategy, schedule, delta, op, shards, async_shards)
    if backend == "pallas":
        graph = graph.plain()        # whole weight tables: see run()
    state = strategy.setup(graph)
    if isinstance(strategy, NodeSplitting):
        n_alloc = strategy.split_info.graph.num_nodes
    else:
        n_alloc = graph.num_nodes
    splan = None
    if shards is not None:
        splan = _shard.plan_shards(strategy, state, graph, shards,
                                   method=partition)
    if backend == "pallas":
        _check_pallas_fit(strategy, n_alloc, graph.num_edges, splan)
    dist, mask = init(n_alloc)

    if splan is not None:
        dist, it, edges, _rounds = _shard.run_fixed_point(
            splan, dist, mask, op=op, max_iterations=max_iterations,
            async_mode=async_shards, backend=backend)
    elif schedule == "delta":
        dplan = _priority.plan_delta(strategy, state, graph, op=op,
                                     delta=delta)
        if mode == "fused":
            dist, it, _rounds, edges = _priority.run_fixed_point(
                dplan, dist, mask, op=op, max_iterations=max_iterations,
                backend=backend)
        else:
            count, it, edges = int(jnp.sum(mask)), 0, 0
            while count > 0 and it < max_iterations:
                dist, mask, _b, _r, e = _priority.step_epoch(
                    dplan, dist, mask, op=op, backend=backend)
                ready(dist)
                edges += e
                count = int(jnp.sum(mask))
                it += 1
    elif mode == "fused":
        dist, it, edges = _fused.run_fixed_point(
            graph, state, strategy, dist, mask, op=op,
            max_iterations=max_iterations, backend=backend)
    else:
        # same third-party-compat rule as run(): backend= only deviates
        # from the default for strategies that declared PALLAS_BACKEND
        extra = {} if backend == "xla" else {"backend": backend}
        count, it, edges = int(jnp.sum(mask)), 0, 0
        while count > 0 and it < max_iterations:
            dist, mask, stats = strategy.iterate(state, dist, mask, count,
                                                 op=op, **extra)
            ready(dist)
            edges += stats.edges_processed
            count = int(jnp.sum(mask))
            it += 1
    if isinstance(strategy, NodeSplitting):
        dist = strategy.split_info.extract_original(dist)
    return np.asarray(dist), it, edges


def run_batch(graph: CSRGraph, sources, *, max_iterations: int = 100000,
              mode: str = "stepped", op="shortest_path",
              shards: Optional[int] = None, partition: str = "degree",
              backend: str = "xla", schedule: str = "bsp",
              delta: Optional[int] = None, pad_to: Optional[int] = None):
    """Run K sources concurrently against one graph (dist is ``[K, N]``).

    Thin wrapper over :func:`repro.core.multi_source.run_batch`; kept here
    so single-source and batched entry points live side by side.
    ``shards=S`` (fused mode only) shards the graph over S devices and
    maps the sharded WD step over the source axis (docs/sharding.md);
    ``backend="pallas"`` swaps the relax lowering, sharded or not
    (docs/backends.md); ``schedule="delta"`` (fused mode only) vmaps
    whole per-row delta-stepping traversals (docs/scheduling.md);
    ``pad_to=P`` K-buckets the batch onto a shared [P, N] executable
    (docs/serving.md)."""
    from repro.core import multi_source
    return multi_source.run_batch(graph, sources,
                                  max_iterations=max_iterations, mode=mode,
                                  op=op, shards=shards, partition=partition,
                                  backend=backend, schedule=schedule,
                                  delta=delta, pad_to=pad_to)


def reference_distances(graph: CSRGraph, source: int) -> np.ndarray:
    """Host-side Dijkstra/BFS oracle for correctness tests."""
    import heapq
    row_ptr = np.asarray(graph.row_ptr)
    col = np.asarray(graph.col)
    wt = (np.ones(graph.num_edges, np.int64) if not graph.weighted
          else np.asarray(graph.wt, np.int64))
    n = graph.num_nodes
    dist = np.full(n, np.iinfo(np.int64).max)
    dist[source] = 0
    heap = [(0, source)]
    while heap:
        d, u = heapq.heappop(heap)
        if d > dist[u]:
            continue
        for e in range(row_ptr[u], row_ptr[u + 1]):
            v = col[e]
            nd = d + wt[e]
            if nd < dist[v]:
                dist[v] = nd
                heapq.heappush(heap, (nd, v))
    out = np.full(n, INF, np.int64)
    reach = dist < np.iinfo(np.int64).max
    out[reach] = dist[reach]
    return out.astype(np.int32)
