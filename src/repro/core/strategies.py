"""The load-balancing strategies (paper §II–III), adapted to TPU/JAX.

Strategy        unit of work                     graph format
--------        ------------                     ------------
BS  (baseline)  node; lane loops over its edges  CSR
EP  (edge)      edge; flat COO worklist          COO (2E–3E memory)
WD  (workload   E/T-edge block over the active   CSR + prefix sum
     decomp.)   frontier via merge-path search
NS  (node       node, after splitting deg>MDT    CSR (rebuilt host-side)
     split)     nodes into ⌈deg/MDT⌉ children
HP  (hier.)     ≤MDT edges/node/sub-iteration;   CSR
                hybrid fallback to WD
AD  (adaptive)  per-iteration choice of BS/WD/HP CSR
                from frontier statistics (arXiv:1911.09135)

Strategies live in the :data:`STRATEGIES` registry; new ones are added with
the :func:`register` decorator (which also records the strategy's declared
*capabilities*, e.g. :data:`FRONTIER_INIT`) and instantiated via
:func:`make_strategy`.

Every kernel and driver here is parameterized over an
:class:`repro.core.operators.EdgeOp` — the per-edge message + combine
monoid that gives the relax its meaning (SSSP, CC labels, widest path,
...).  Strategies schedule the work; the operator defines it.  The
default everywhere is ``operators.shortest_path``, which reproduces the
paper's BFS/SSSP semantics bit-for-bit.

Two kinds of code live here — keep them apart (docs/architecture.md):

* **fused-safe relax kernels** (``bs_relax``, ``ep_relax``, ``wd_relax``,
  ``hp_sub_relax``, ``ns_activate``, ``_apply_relax``, the push/compact
  helpers): pure jitted ``(arrays) -> (arrays)`` functions with static
  shapes and **no host syncs** — safe to call from traced code, and the
  basis for the dense-mask variants in :mod:`repro.core.fused`.
* **host-stepped drivers** (every ``Strategy.iterate`` /
  ``relax_and_push`` / ``setup``): orchestration that may freely sync to
  the host (``int(...)``, ``np.asarray``) to count frontiers, pick
  capacity buckets and collect stats.  These must NEVER be called from
  inside ``jit``/``while_loop``-traced code — a single ``int()`` there
  reintroduces the per-iteration host round-trip the fused engine
  exists to remove.

CUDA-thread semantics map to dense vectorized batches:
  * atomicMin/Max/Add        →  dist.at[d].min/max/add     (op.scatter)
  * worklist push w/chunking →  flag → cumsum → run_fill   (1 slot/node)
  * Thrust inclusive_scan    →  jnp.cumsum
  * find_offsets kernel      →  vectorized searchsorted (merge-path)
Load imbalance materializes as masked/padded lanes — measurable as wasted
FLOPs/bytes rather than warp divergence (see repro.core.balance).

Every relax kernel additionally takes ``backend="xla" | "pallas"``
(:data:`BACKENDS`): "xla" is the gather/scatter lowering described
above; "pallas" routes the *same chunk schedule* through the fused
scatter-combine kernels of :mod:`repro.kernels.relax` (gather + message
+ activation + segment combine in VMEM, and for WD the merge-path
search fused with the relax), with bit-identical results — see
docs/backends.md.  Strategies advertise support via the
:data:`PALLAS_BACKEND` capability.
"""

from __future__ import annotations

import dataclasses
import math
import time
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import node_split, operators
from repro.core.graph import CSRGraph, COOGraph
from repro.core.operators import EdgeOp
from repro.core.schedule import (
    DEFAULT_SCHEDULE, Schedule, default_schedule, resolve_overrides)
from repro.core.worklist import bucket, compact_mask, prefix_sum, run_fill
from repro.kernels import relax as pallas_relax


#: execution backends of the relax kernels.  "xla" is the plain
#: gather/scatter lowering; "pallas" routes the same chunk schedule
#: through the fused scatter-combine kernels in repro.kernels.relax
#: (bit-identical results — docs/backends.md).
BACKENDS = ("xla", "pallas")


# ---------------------------------------------------------------------------
# shared relax primitive: dist[dst] = combine(dist[dst], message(dist[src], w))
# ---------------------------------------------------------------------------

def _edge_weight(g, eidx: jax.Array) -> jax.Array:
    """Weights of COO edges ``eidx`` (ones when unweighted); a CSR graph
    reads its edges through ``CSRGraph.edge_pair``."""
    if g.wt is not None:
        return g.wt[eidx]
    return jnp.ones(eidx.shape, jnp.int32)


def _plain_tables(g: CSRGraph) -> CSRGraph:
    """``g`` for a Pallas kernel that takes whole ``col``/``wt`` tables:
    the plain layout, which ``engine`` hands every Pallas traversal
    (``CSRGraph.plain``, decoded once before the loop)."""
    if g.wt_shift is not None:
        raise ValueError(
            "backend='pallas' reads plain weight tables; pass "
            "graph.plain() (engine.run and run_batch do)")
    return g


def _apply_relax(dist, updated, src, dst, w, valid, *,
                 op: EdgeOp = operators.shortest_path):
    """Vectorized operator relax over a batch of (src, dst, w) with a
    validity mask: candidates from ``op.message``, folded by
    ``op.scatter`` (the deterministic stand-in for the CUDA atomic), with
    ``op.improves`` deciding which destinations join the next frontier.

    With the default ``shortest_path`` operator this is exactly
    ``dist[dst] = min(dist[dst], dist[src] + w)``."""
    src_c = jnp.clip(src, 0, dist.shape[0] - 1)
    dst_c = jnp.clip(dst, 0, dist.shape[0] - 1)
    cand = op.message(dist[src_c], w)
    improve = valid & op.improves(cand, dist[dst_c])
    dist = op.scatter(dist, dst_c, cand, improve)
    updated = updated.at[dst_c].max(improve)
    return dist, updated, improve


def relax_fn(backend: str, sched: Schedule = DEFAULT_SCHEDULE):
    """The relax primitive for a backend: :func:`_apply_relax` (XLA
    gather/scatter) or the signature-compatible Pallas drop-in
    (``repro.kernels.relax.apply_relax`` — fused scatter-combine in
    VMEM).  Every kernel below dispatches through this, so the chunk
    schedule — and therefore the bit-exact results — never depends on
    the backend.  ``sched`` supplies the Pallas block/lane shapes
    (``tile_r``/``tile_c``/``chunk``); the XLA lowering has no block
    shapes to read."""
    if backend == "xla":
        return _apply_relax
    if backend == "pallas":
        return partial(pallas_relax.apply_relax,
                       **pallas_relax.tile_kwargs(sched))
    raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")


# ---------------------------------------------------------------------------
# BS — node-based baseline (LonestarGPU-style)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cap", "op", "backend", "sched"))
def bs_relax(g: CSRGraph, dist, frontier, *, cap: int,
             op: EdgeOp = operators.shortest_path, backend: str = "xla",
             sched: Schedule = DEFAULT_SCHEDULE):
    """Each frontier slot ("thread") walks its own adjacency list.

    The walk runs for max-degree-in-frontier steps with lanes masked once
    their node is exhausted — the TPU manifestation of the paper's
    node-based imbalance (idle lanes ∝ degree variance)."""
    del cap  # shapes already carry it; kept for bucketed specialization
    relax = relax_fn(backend, sched)
    mask = frontier >= 0
    f = jnp.where(mask, frontier, 0)
    deg = jnp.where(mask, g.row_ptr[f + 1] - g.row_ptr[f], 0)
    fmax = jnp.max(deg)
    base = g.row_ptr[f]
    updated = jnp.zeros((dist.shape[0],), jnp.bool_)

    def cond(c):
        return c[0] < fmax

    def body(c):
        d, dist, updated = c
        valid = mask & (d < deg)
        eidx = jnp.clip(base + d, 0, g.num_edges - 1)
        dst, w = g.edge_pair(eidx)
        dist, updated, _ = relax(dist, updated, f, dst, w, valid, op=op)
        return d + 1, dist, updated

    _, dist, updated = jax.lax.while_loop(
        cond, body, (jnp.int32(0), dist, updated))
    return dist, updated


# ---------------------------------------------------------------------------
# EP — edge-based parallelism over a COO edge worklist
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cap", "op", "backend", "sched"))
def ep_relax(coo: COOGraph, dist, edge_wl, *, cap: int,
             op: EdgeOp = operators.shortest_path, backend: str = "xla",
             sched: Schedule = DEFAULT_SCHEDULE):
    """One lane per worklist edge — near-perfect balance (paper §II-B)."""
    del cap
    mask = edge_wl >= 0
    e = jnp.where(mask, edge_wl, 0)
    src, dst = coo.src[e], coo.dst[e]
    w = _edge_weight(coo, e)
    updated = jnp.zeros((dist.shape[0],), jnp.bool_)
    dist, updated, improve = relax_fn(backend, sched)(dist, updated, src,
                                                      dst, w, mask, op=op)
    return dist, updated, improve, dst


@partial(jax.jit, static_argnames=("cap_out",))
def ep_push_chunked(row_ptr, updated_mask, total, *, cap_out: int):
    """Work-chunked push (§IV-D): ONE output-range reservation per updated
    node (flag → compact → run_fill)."""
    cap_nodes = updated_mask.shape[0]
    (nodes,) = jnp.nonzero(updated_mask, size=cap_nodes, fill_value=0)
    nvalid = jnp.sum(updated_mask)
    deg = jnp.where(jnp.arange(cap_nodes) < nvalid,
                    row_ptr[nodes + 1] - row_ptr[nodes], 0)
    wl, _ = run_fill(row_ptr[nodes], deg, total, cap_out)
    return wl


@partial(jax.jit, static_argnames=("cap_out",))
def ep_push_unchunked(row_ptr, improve, dst, total, *, cap_out: int):
    """Per-edge push (the default the paper compares against in Fig. 11):
    every improving *edge* pushes its destination's full adjacency run, so
    a node updated by k edges is pushed k times — reproducing the worklist
    explosion + redundancy the paper describes."""
    deg = jnp.where(improve, row_ptr[dst + 1] - row_ptr[dst], 0)
    wl, _ = run_fill(row_ptr[dst], deg, total, cap_out)
    return wl


# ---------------------------------------------------------------------------
# WD — workload decomposition (merge-path over the frontier's edges)
# ---------------------------------------------------------------------------

def _merge_path_lanes(work, prefix, base, edge_pair, *, num_edges: int,
                      pad: int, src_ids=None):
    """``lanes(lo, size) -> (src, dst, w)`` of merge-path lanes
    ``lo .. lo+size-1`` — the XLA lowering of the paper's
    ``find_offsets`` search.

    Lane ``k`` belongs to node ``n`` when ``prefix[n-1] <= k <
    prefix[n]`` (``prefix`` is the inclusive scan of ``work``) and reads
    edge ``base[n] + k``.  Nodes with work are first compacted to slots,
    each holding at least one lane; a block of ``size`` lanes then spans
    at most ``size`` slots from the first one it touches, so ranking the
    block takes a scatter + scan over ``size`` entries instead of a
    binary search per lane.  ``src_ids`` maps node -> source id (default:
    the node itself); ``pad`` is the largest ``size`` asked for.
    ``edge_pair(eidx) -> (dst, w)`` reads the edges
    (``CSRGraph.edge_pair``)."""
    n = work.shape[0]
    big = jnp.iinfo(jnp.int32).max
    live = work > 0
    slot = jnp.where(live, prefix_sum(live.astype(jnp.int32)) - 1, n + pad)
    node_of = jnp.zeros((n,), jnp.int32).at[slot].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")
    slot_end = jnp.full((n + pad,), big, jnp.int32).at[slot].set(
        prefix, mode="drop")

    def lanes(lo, size):
        first = jnp.searchsorted(slot_end[:n], lo, side="right")
        ends = jax.lax.dynamic_slice(slot_end, (first,), (size,)) - lo
        counts = jnp.zeros((size,), jnp.int32).at[
            jnp.where(ends < size, ends, size)].add(1, mode="drop")
        s = jnp.minimum(first + prefix_sum(counts), n - 1)
        node = node_of[s]
        k = lo + jnp.arange(size, dtype=jnp.int32)
        eidx = jnp.clip(base[node] + k, 0, num_edges - 1)
        src = node if src_ids is None else src_ids[node]
        return (src, *edge_pair(eidx))
    return lanes


def _merge_relax(g: CSRGraph, dist, f, start, work, *, cap_work: int,
                 op: EdgeOp, backend: str, sched: Schedule):
    """Relax ``work[i]`` edges of frontier slot ``i`` (node ``f[i]``,
    from edge ``start[i]`` on) over ``cap_work`` lanes, as one batch
    (lanes ranked by :func:`_merge_path_lanes`)."""
    prefix = prefix_sum(work)
    exclusive = prefix - work
    total = prefix[-1]
    updated = jnp.zeros((dist.shape[0],), jnp.bool_)
    if backend == "pallas":
        prop, upd, _ = pallas_relax.wd_relax_lanes(
            dist, prefix, exclusive, start, f, g.col, _plain_tables(g).wt,
            cap_work=cap_work, op=op, **pallas_relax.tile_kwargs(sched))
        return pallas_relax.apply_proposal(dist, prop, op), updated | upd
    src, dst, w = _merge_path_lanes(
        work, prefix, start - exclusive, g.edge_pair,
        num_edges=g.num_edges, pad=cap_work, src_ids=f)(0, cap_work)
    dist, updated, _ = _apply_relax(
        dist, updated, src, dst, w,
        jnp.arange(cap_work, dtype=jnp.int32) < total, op=op)
    return dist, updated


@partial(jax.jit, static_argnames=("cap_work", "op", "backend", "sched"))
def wd_relax(g: CSRGraph, dist, frontier, cursor, *, cap_work: int,
             op: EdgeOp = operators.shortest_path, backend: str = "xla",
             sched: Schedule = DEFAULT_SCHEDULE):
    """Block-distribute the frontier's edges across ``cap_work`` lanes.

    prefix-sum over (remaining) frontier degrees, then every work item k
    locates its (node, local edge) in it — the vectorized equivalent of
    the paper's ``find_offsets`` + per-thread while-walk (Fig. 4), with
    no serialization (:func:`_merge_relax`).

    ``backend="pallas"`` routes through
    :func:`repro.kernels.relax.wd_relax_lanes`, which fuses the
    merge-path search *and* the relax in one kernel — the ``node_idx``
    array never materializes (this replaces the old
    ``use_pallas=True`` find_offsets-only fast path)."""
    mask = frontier >= 0
    f = jnp.where(mask, frontier, 0)
    deg = jnp.where(mask, g.row_ptr[f + 1] - g.row_ptr[f] - cursor, 0)
    return _merge_relax(g, dist, f, g.row_ptr[f] + cursor,
                        jnp.maximum(deg, 0), cap_work=cap_work, op=op,
                        backend=backend, sched=sched)


# ---------------------------------------------------------------------------
# NS — node splitting (split graph built host-side in node_split.py)
# ---------------------------------------------------------------------------

@jax.jit
def ns_activate(dist2, mask2, child_parent):
    """Reflect parent attributes onto children (paper §III-B) and activate
    children alongside their parent — children share the parent's outgoing
    edges, so whenever the parent has work, so do they.  This extra
    gather pass is the 'extra atomics' cost of NS.

    The mirror is a straight gather of the parent's value, which is
    operator-generic: children receive no in-edges (destinations in the
    split graph are always parent ids), so a child's value is *only* ever
    the parent's — for min/max operators the gather coincides with the
    old ``combine(child, parent)`` fold, and for additive operators it is
    the only correct choice (a fold would double-count)."""
    dist2 = dist2[child_parent]
    mask2 = mask2 | mask2[child_parent]
    return dist2, mask2


# ---------------------------------------------------------------------------
# HP — hierarchical processing (≤ MDT edges per node per sub-iteration)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cap_work", "mdt", "op", "backend",
                                   "sched"))
def hp_sub_relax(g: CSRGraph, dist, sub, cursor, *, cap_work: int, mdt: int,
                 op: EdgeOp = operators.shortest_path,
                 backend: str = "xla",
                 sched: Schedule = DEFAULT_SCHEDULE):
    """One sub-iteration: every sublist node processes its next ≤MDT edges
    (the valid lanes of a [sublist, MDT] tile — all bounded by MDT, i.e.
    balanced within the threshold, §III-C), laid out over ``cap_work``
    lanes by the merge path.  Returns the surviving sublist mask and the
    next sub-iteration's lane count."""
    mask = sub >= 0
    n = jnp.where(mask, sub, 0)
    deg = g.row_ptr[n + 1] - g.row_ptr[n]
    tile = jnp.where(mask, jnp.clip(deg - cursor, 0, mdt), 0)
    dist, updated = _merge_relax(g, dist, n, g.row_ptr[n] + cursor, tile,
                                 cap_work=cap_work, op=op, backend=backend,
                                 sched=sched)
    new_cursor = cursor + mdt
    alive = mask & (new_cursor < deg)
    next_work = jnp.sum(jnp.where(alive,
                                  jnp.clip(deg - new_cursor, 0, mdt), 0))
    return dist, updated, new_cursor, alive, next_work


@partial(jax.jit, static_argnames=("cap_out",))
def compact_pair(nodes, cursor, alive, *, cap_out: int):
    """Compact (node, cursor) pairs that survive a sub-iteration."""
    (idx,) = jnp.nonzero(alive, size=cap_out, fill_value=-1)
    ok = idx >= 0
    idx_c = jnp.where(ok, idx, 0)
    return (jnp.where(ok, nodes[idx_c], -1).astype(jnp.int32),
            jnp.where(ok, cursor[idx_c], 0).astype(jnp.int32))


# ---------------------------------------------------------------------------
# Strategy drivers (host-side orchestration, bucketed jit dispatch)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class IterStats:
    frontier_size: int
    edges_processed: int
    sub_iterations: int = 1
    frontier_degrees: Optional[np.ndarray] = None  # for balance analysis
    kernel: Optional[str] = None     # relax kernel used (AD records choices)
    #: bucket index settled by a delta-stepping epoch (None for BSP
    #: iterations) — strictly increasing over a run for monotone
    #: operators, which the priority test harness asserts
    bucket: Optional[int] = None


#: capability: the strategy can start from an arbitrary dense
#: (dist, frontier-mask) pair — multi-source seeding, CC's
#: every-node-active init, engine.fixed_point.  Node strategies have it;
#: EP does not (its state is an edge worklist derived from one source).
FRONTIER_INIT = "frontier_init"

#: capability: the strategy's fused kernel has a multi-device lowering in
#: :mod:`repro.core.shard` (``engine.run(..., shards=)``).  BS/WD/HP/NS
#: declare it; EP does not (its COO edge worklist is device-local) and
#: AD does not (its per-iteration kernel choice consumes global frontier
#: statistics) — see docs/sharding.md.
SHARDABLE = "shardable"

#: capability: every kernel the strategy dispatches accepts
#: ``backend="pallas"`` (the fused scatter-combine kernels of
#: :mod:`repro.kernels.relax`) with bit-identical results — the gate
#: ``engine.run(..., backend=)`` checks.  All six built-ins declare it;
#: a third-party strategy whose ``iterate`` ignores the ``backend``
#: kwarg must not (docs/backends.md).
PALLAS_BACKEND = "pallas_backend"

#: capability: the strategy's kernels have delta-stepping phase lowerings
#: in :mod:`repro.core.priority`, so ``engine.run(..., schedule="delta")``
#: may order its relaxations by distance bucket.  The five node-centric
#: built-ins (BS/WD/NS/HP/AD) declare it; EP does not — its edge worklist
#: has no per-node tentative value to bucket by (docs/scheduling.md).
PRIORITY_SCHEDULE = "priority_schedule"

#: capabilities a plain StrategyBase subclass declares unless it says
#: otherwise at registration (or via a ``capabilities`` class attribute).
#: Deliberately excludes :data:`SHARDABLE`, :data:`PALLAS_BACKEND` and
#: :data:`PRIORITY_SCHEDULE`: a third-party strategy is single-device,
#: XLA-only and BSP-only until it ships the corresponding lowerings and
#: says so.
DEFAULT_CAPABILITIES = frozenset({FRONTIER_INIT})

#: what the four built-in shardable strategies declare
SHARDED_CAPABILITIES = frozenset({FRONTIER_INIT, SHARDABLE,
                                  PALLAS_BACKEND, PRIORITY_SCHEDULE})


class StrategyBase:
    """A strategy = host preprocessing + one frontier-relax iteration.

    ``setup`` and ``iterate`` are host-stepped entry points (they may
    sync device values); the jitted kernels they dispatch are the
    fused-safe parts.  ``iterate`` receives the :class:`EdgeOp` defining
    the relax semantics (``op``) and must thread it to every kernel it
    dispatches.  A strategy additionally gains ``mode="fused"`` support
    by having a dense-mask lowering mapped in ``repro.core.fused._plan``,
    and declares what callers may assume about it through its
    ``capabilities`` set (see :data:`FRONTIER_INIT` and
    :func:`register`).

    Every strategy carries a work-assignment :class:`Schedule`
    (docs/schedules.md): pass ``schedule=`` to declare one, or rely on
    the strategy's registered default.  Constructor threshold kwargs
    (``mdt=``, ``switch_threshold=``, ...) remain as per-field overrides
    of that schedule.  ``setup`` resolves auto fields (MDT from the
    degree histogram) into ``resolved_schedule`` — the concrete value
    the fused/priority/sharded lowerings take as their one static
    argument."""

    name = "base"
    #: declared capability flags; third-party strategies override this in
    #: the class body or via ``register(capabilities=...)``
    capabilities: frozenset = DEFAULT_CAPABILITIES

    def __init__(self, schedule: Optional[Schedule] = None):
        self.schedule = (schedule if schedule is not None
                         else default_schedule(self.name))
        #: concrete schedule after ``setup`` (auto fields resolved);
        #: strategies with auto fields overwrite this there
        self.resolved_schedule = self.schedule

    #: peak auxiliary device bytes (graph copies etc.) — feeds the paper's
    #: memory-requirement axis (Fig. 9)
    def setup(self, graph: CSRGraph) -> Any:
        return graph

    def state_bytes(self, state) -> int:
        return state.device_bytes()

    def iterate(self, state, dist, updated_mask, count, *,
                op: EdgeOp = operators.shortest_path,
                record_degrees=False, backend: str = "xla"):
        raise NotImplementedError


#: name -> strategy class.  Populated by :func:`register`; drivers resolve
#: user-facing strategy names ("BS", ..., "AD") through this table, and
#: algorithms gate on the class's declared ``capabilities`` (via
#: :func:`strategy_capabilities`) instead of isinstance checks, so
#: third-party registrations compose.
STRATEGIES: dict[str, type] = {}


def register(cls=None, *, name: Optional[str] = None,
             capabilities: Optional[frozenset] = None):
    """Class decorator adding a :class:`StrategyBase` subclass to the
    registry under ``name`` (default: the class's ``name`` attribute).

    ``capabilities`` declares what callers may assume about the strategy
    (e.g. :data:`FRONTIER_INIT`); when omitted, the class's
    ``capabilities`` attribute wins — *including inherited ones*, so a
    subclass of a restricted strategy (e.g. a tuned EP variant) stays
    restricted unless it explicitly re-declares."""
    def _register(c):
        if not (isinstance(c, type) and issubclass(c, StrategyBase)):
            raise TypeError(f"{c!r} is not a StrategyBase subclass")
        key = name or c.name
        if key in STRATEGIES:
            raise ValueError(f"strategy {key!r} already registered "
                             f"({STRATEGIES[key]!r})")
        caps = capabilities
        if caps is None:
            caps = getattr(c, "capabilities", DEFAULT_CAPABILITIES)
        c.capabilities = frozenset(caps)
        STRATEGIES[key] = c
        return c
    return _register(cls) if cls is not None else _register


def make_strategy(name: str, **kwargs) -> StrategyBase:
    """Instantiate a registered strategy by name."""
    try:
        cls = STRATEGIES[name]
    except KeyError:
        raise KeyError(f"unknown strategy {name!r}; registered: "
                       f"{sorted(STRATEGIES)}") from None
    return cls(**kwargs)


def strategy_capabilities(name: str) -> frozenset:
    """Declared capability flags of a registered strategy."""
    try:
        cls = STRATEGIES[name]
    except KeyError:
        raise KeyError(f"unknown strategy {name!r}; registered: "
                       f"{sorted(STRATEGIES)}") from None
    return cls.capabilities


@register
class NodeBased(StrategyBase):
    name = "BS"
    capabilities = SHARDED_CAPABILITIES

    def iterate(self, g, dist, updated_mask, count, *,
                op: EdgeOp = operators.shortest_path, record_degrees=False,
                backend: str = "xla"):
        sched = self.schedule
        cap = bucket(count, sched.min_bucket)
        frontier = compact_mask(updated_mask, cap)
        stats = _frontier_stats(g, frontier, count, record_degrees)
        dist, new_mask = bs_relax(g, dist, frontier, cap=cap, op=op,
                                  backend=backend, sched=sched)
        return dist, new_mask, stats


@register
class EdgeBased(StrategyBase):
    """EP.  State = COO graph (+ the 2E/3E memory bill) + edge worklist.

    No :data:`FRONTIER_INIT`: the worklist is seeded from one source's
    adjacency run, so algorithms needing an arbitrary initial frontier
    (CC's all-nodes-active seeding) must pick a node strategy."""
    name = "EP"
    capabilities = frozenset({PALLAS_BACKEND})

    def __init__(self, chunked: bool = True, wl_capacity_factor: float = 4.0,
                 memory_budget_bytes: Optional[int] = None,
                 schedule: Optional[Schedule] = None):
        super().__init__(schedule=resolve_overrides(self.name, schedule))
        self.chunked = chunked
        self.wl_capacity_factor = wl_capacity_factor
        self.memory_budget_bytes = memory_budget_bytes

    def setup(self, graph: CSRGraph):
        coo = graph.to_coo()
        need = coo.device_bytes()
        if self.memory_budget_bytes is not None and need > self.memory_budget_bytes:
            # Faithful reproduction of "EP fails to execute for large
            # graphs due to insufficient memory" (paper §IV).
            raise MemoryError(
                f"EP COO storage needs {need} bytes > budget "
                f"{self.memory_budget_bytes} (paper §II-B memory wall)")
        self._degrees = np.asarray(graph.degrees)
        return coo

    def initial_worklist(self, coo: COOGraph, source: int):
        deg = int(self._degrees[source])
        cap = bucket(deg, self.schedule.min_bucket)
        start = int(np.asarray(coo.row_ptr)[source])
        wl = np.full(cap, -1, np.int32)
        wl[:deg] = np.arange(start, start + deg, dtype=np.int32)
        return jnp.asarray(wl), deg

    def relax_and_push(self, coo, dist, edge_wl, count, *,
                       op: EdgeOp = operators.shortest_path,
                       backend: str = "xla"):
        cap = edge_wl.shape[0]
        min_bucket = self.schedule.min_bucket
        dist, new_mask, improve, dst = ep_relax(coo, dist, edge_wl, cap=cap,
                                                op=op, backend=backend,
                                                sched=self.schedule)
        if self.chunked:
            nodes_np = np.asarray(new_mask)
            total = int(self._degrees[nodes_np].sum())
            wl = ep_push_chunked(coo.row_ptr, new_mask, total,
                                 cap_out=bucket(total, min_bucket))
        else:
            improve_np, dst_np = np.asarray(improve), np.asarray(dst)
            total = int(self._degrees[dst_np[improve_np]].sum())
            if total > 2 * coo.num_edges:
                # worklist explosion (paper §II-B): duplicates spawn
                # duplicates geometrically — apply the condensing pass the
                # paper describes (sort+unique), charged as overhead
                uniq = np.unique(dst_np[improve_np])
                total = int(self._degrees[uniq].sum())
                starts = np.asarray(coo.row_ptr)[uniq]
                lens = self._degrees[uniq]
                wl_np = np.full(bucket(total, min_bucket), -1, np.int32)
                out = np.concatenate([np.arange(s, s + l) for s, l in
                                      zip(starts, lens)]) if total else []
                wl_np[: total] = out
                wl = jnp.asarray(wl_np)
            else:
                wl = ep_push_unchunked(coo.row_ptr, improve, dst, total,
                                       cap_out=bucket(total, min_bucket))
        return dist, new_mask, wl, total


@register
class WorkloadDecomposition(StrategyBase):
    name = "WD"
    capabilities = SHARDED_CAPABILITIES

    def setup(self, graph: CSRGraph):
        self._degrees = np.asarray(graph.degrees)
        return graph

    def iterate(self, g, dist, updated_mask, count, *,
                op: EdgeOp = operators.shortest_path, record_degrees=False,
                edge_total=None, backend: str = "xla"):
        sched = self.schedule
        cap = bucket(count, sched.min_bucket)
        frontier = compact_mask(updated_mask, cap)
        stats = _frontier_stats(g, frontier, count, record_degrees)
        # edge_total lets callers that already synced the mask (AD) pass
        # their degree sum; otherwise reuse the one _frontier_stats just
        # computed — no second device-to-host transfer + gather
        total = (int(stats.edges_processed)
                 if edge_total is None else int(edge_total))
        cursor = jnp.zeros((cap,), jnp.int32)
        dist, new_mask = wd_relax(g, dist, frontier, cursor,
                                  cap_work=bucket(total, sched.min_bucket),
                                  op=op, backend=backend, sched=sched)
        stats.edges_processed = total
        return dist, new_mask, stats


@register
class NodeSplitting(StrategyBase):
    name = "NS"
    capabilities = SHARDED_CAPABILITIES

    def __init__(self, histogram_bins: Optional[int] = None,
                 mdt: Optional[int] = None,
                 schedule: Optional[Schedule] = None):
        super().__init__(schedule=resolve_overrides(
            self.name, schedule, histogram_bins=histogram_bins, mdt=mdt))
        self.histogram_bins = self.schedule.histogram_bins
        self.mdt = self.schedule.mdt
        self.split_info: Optional[node_split.SplitGraph] = None

    def setup(self, graph: CSRGraph):
        degrees = np.asarray(graph.degrees)
        self.resolved_schedule = self.schedule.resolved(degrees)
        self.split_info = node_split.split_graph(
            graph, self.resolved_schedule.mdt)
        return self.split_info

    def iterate(self, sg, dist, updated_mask, count, *,
                op: EdgeOp = operators.shortest_path, record_degrees=False,
                backend: str = "xla"):
        sched = self.schedule
        g2 = sg.graph
        # mirror parent dist onto children + co-activate children
        dist, mask2 = ns_activate(dist, updated_mask, sg.child_parent)
        count2 = int(jnp.sum(mask2))
        cap = bucket(count2, sched.min_bucket)
        frontier = compact_mask(mask2, cap)
        stats = _frontier_stats(g2, frontier, count2, record_degrees)
        dist, new_mask = bs_relax(g2, dist, frontier, cap=cap, op=op,
                                  backend=backend, sched=sched)
        return dist, new_mask, stats

    def state_bytes(self, sg):
        return sg.graph.device_bytes() + sg.child_parent.size * 4


@register
class HierarchicalProcessing(StrategyBase):
    name = "HP"
    capabilities = SHARDED_CAPABILITIES

    def __init__(self, histogram_bins: Optional[int] = None,
                 mdt: Optional[int] = None,
                 switch_threshold: Optional[int] = None,
                 schedule: Optional[Schedule] = None):
        super().__init__(schedule=resolve_overrides(
            self.name, schedule, histogram_bins=histogram_bins, mdt=mdt,
            switch_threshold=switch_threshold))
        self.histogram_bins = self.schedule.histogram_bins
        self.mdt = self.schedule.mdt
        self.switch_threshold = self.schedule.switch_threshold

    def setup(self, graph: CSRGraph):
        degrees = np.asarray(graph.degrees)
        self._degrees = degrees
        self.resolved_schedule = self.schedule.resolved(degrees)
        self.mdt_value = self.resolved_schedule.mdt
        self._wd = WorkloadDecomposition(schedule=self.schedule)
        self._wd.setup(graph)
        return graph

    def iterate(self, g, dist, updated_mask, count, *,
                op: EdgeOp = operators.shortest_path, record_degrees=False,
                backend: str = "xla"):
        sched = self.schedule
        cap = bucket(count, sched.min_bucket)
        frontier = compact_mask(updated_mask, cap)
        stats = _frontier_stats(g, frontier, count, record_degrees)
        acc_mask = jnp.zeros((dist.shape[0],), jnp.bool_)
        mdt = self.mdt_value

        # Hybrid: small super list -> straight WD (paper §III-C)
        if count <= sched.switch_threshold:
            dist, new_mask, sub_stats = self._wd.iterate(
                g, dist, updated_mask, count, op=op, backend=backend)
            stats.edges_processed = sub_stats.edges_processed
            return dist, new_mask, stats

        sub, cursor = frontier, jnp.zeros((cap,), jnp.int32)
        live = count
        subiters = 0
        f_np = np.asarray(frontier)
        work = int(np.minimum(self._degrees[f_np[f_np >= 0]], mdt).sum())
        while live > sched.switch_threshold:
            dist, upd, cursor, alive, work = hp_sub_relax(
                g, dist, sub, cursor,
                cap_work=bucket(work, sched.min_bucket), mdt=mdt, op=op,
                backend=backend, sched=sched)
            acc_mask = acc_mask | upd
            live = int(jnp.sum(alive))
            work = int(work)
            subiters += 1
            if live:
                cap2 = bucket(live, sched.min_bucket)
                sub, cursor = compact_pair(sub, cursor, alive, cap_out=cap2)
        if live > 0:
            # finish the small sublist with cursor-aware WD
            mask = sub >= 0
            rem = np.asarray(
                jnp.where(mask, g.row_ptr[jnp.where(mask, sub, 0) + 1]
                          - g.row_ptr[jnp.where(mask, sub, 0)] - cursor, 0))
            total = int(np.maximum(rem, 0).sum())
            if total > 0:
                dist, upd = wd_relax(g, dist, sub, cursor,
                                     cap_work=bucket(total, sched.min_bucket),
                                     op=op, backend=backend, sched=sched)
                acc_mask = acc_mask | upd
            subiters += 1
        stats.sub_iterations = subiters
        return dist, acc_mask, stats


def _frontier_stats(g, frontier, count, record_degrees) -> IterStats:
    """Host-stepped stats for one frontier (syncs the worklist).

    ``edges_processed`` is always filled — it is the degree sum the
    iteration will relax, which keeps stepped ``RunResult.edges_relaxed``
    (and MTEPS) meaningful for BS/NS/HP and bit-identical to fused runs;
    ``record_degrees`` additionally keeps the per-node degree array for
    the balance analysis."""
    stats = IterStats(frontier_size=int(count), edges_processed=0)
    f = np.asarray(frontier)
    f = f[f >= 0]
    row_ptr = np.asarray(g.row_ptr)
    degrees = row_ptr[f + 1] - row_ptr[f]
    stats.edges_processed = int(degrees.sum())
    if record_degrees:
        stats.frontier_degrees = degrees
    return stats


# ---------------------------------------------------------------------------
# AD — adaptive strategy selection (Jatala et al., arXiv:1911.09135)
# ---------------------------------------------------------------------------

def choose_kernel(count: int, degree_sum: int, max_degree: int,
                  imbalance: float, *, mdt: int,
                  small_frontier: int = 512,
                  imbalance_threshold: float = 4.0,
                  hp_edges_threshold: int = 1 << 15) -> str:
    """Pick the relax kernel for one iteration from frontier statistics.

    Host-side reference implementation of the decision structure; if you
    change it, mirror the change in ``repro.core.fused._ad_step``, which
    evaluates the same branches on device for ``mode="fused"``.

    The decision structure follows arXiv:1911.09135 (which switches load
    balancers at runtime from frontier size and degree distribution):

    * small or near-uniform frontier → BS: the per-node loop has zero
      scan/search overhead and its imbalance penalty is bounded by the
      frontier's own degree spread;
    * large skewed frontier with edge volume past ``hp_edges_threshold``
      and nodes exceeding MDT → HP: bound per-node work to MDT per
      sub-iteration so one hub cannot serialize the whole tile;
    * everything else → WD: merge-path edge distribution, perfectly
      balanced at the cost of a prefix-sum + binary search per iteration.
    """
    if degree_sum == 0 or count == 0:
        # degenerate frontier: a seeded run whose source is isolated (or
        # an empty mask) has no edges to balance, and the imbalance
        # ratio is 0/0 — BS's per-node loop is the cheapest no-op
        return "BS"
    if not math.isfinite(imbalance):
        # a caller-computed ratio can still arrive inf/NaN
        # (max_degree / 0-mean); comparing NaN would silently fail every
        # branch, so pin it to "maximally skewed" explicitly
        imbalance = math.inf
    if count <= small_frontier and imbalance <= imbalance_threshold:
        return "BS"
    if max_degree > mdt and degree_sum >= hp_edges_threshold:
        return "HP"
    return "WD"


@register
class AdaptiveStrategy(StrategyBase):
    """AD: per-iteration strategy switching on frontier statistics.

    Keeps BS, WD and HP sub-strategies warm against the same CSR state and
    delegates each frontier iteration to whichever kernel
    :func:`choose_kernel` selects from host-computed frontier statistics
    (frontier size, degree sum, imbalance factor — the same quantities
    ``repro.core.balance.analyze`` reports).  All three kernels share the
    ``dist`` layout, so switching mid-run is free — no state conversion
    between iterations (the property arXiv:1911.09135 exploits).
    """
    name = "AD"
    # no SHARDABLE (the selector consumes global frontier statistics —
    # docs/sharding.md) but the three delegate kernels all take the
    # pallas backend and all three have delta-stepping phase lowerings,
    # so AD composes with both transparently
    capabilities = frozenset({FRONTIER_INIT, PALLAS_BACKEND,
                              PRIORITY_SCHEDULE})

    def __init__(self, small_frontier: Optional[int] = None,
                 imbalance_threshold: Optional[float] = None,
                 hp_edges_threshold: Optional[int] = None,
                 histogram_bins: Optional[int] = None,
                 mdt: Optional[int] = None,
                 schedule: Optional[Schedule] = None,
                 cost_model=None, online: bool = False):
        super().__init__(schedule=resolve_overrides(
            self.name, schedule, small_frontier=small_frontier,
            imbalance_threshold=imbalance_threshold,
            hp_edges_threshold=hp_edges_threshold,
            histogram_bins=histogram_bins, mdt=mdt))
        sched = self.schedule
        self.small_frontier = sched.small_frontier
        # Schedule.__post_init__ canonicalized this to float32: the fused
        # selector compares in f32 on device, so the host side must hold
        # the same representable value or the two could disagree within
        # one rounding step
        self.imbalance_threshold = sched.imbalance_threshold
        self.hp_edges_threshold = sched.hp_edges_threshold
        self.histogram_bins = sched.histogram_bins
        self.mdt = sched.mdt
        #: measured cost model (repro.core.costmodel.CostModel) — when
        #: set, per-iteration choice comes from its fitted per-kernel
        #: linear model instead of the fixed arXiv:1911.09135 tree
        self.cost_model = cost_model
        #: refine the cost model online from per-iteration wall times
        #: (host-stepped mode only; implies a block_until_ready per step)
        self.online = bool(online)
        self.kernel_counts: dict[str, int] = {}

    def setup(self, graph: CSRGraph):
        self._degrees = np.asarray(graph.degrees)
        self.resolved_schedule = self.schedule.resolved(self._degrees)
        self.mdt_value = self.resolved_schedule.mdt
        self._kernels = {
            "BS": NodeBased(schedule=self.schedule),
            "WD": WorkloadDecomposition(schedule=self.schedule),
            "HP": HierarchicalProcessing(mdt=self.mdt_value,
                                         schedule=self.schedule),
        }
        for k in self._kernels.values():
            k.setup(graph)
        self.kernel_counts = {}
        return graph

    def iterate(self, g, dist, updated_mask, count, *,
                op: EdgeOp = operators.shortest_path, record_degrees=False,
                backend: str = "xla"):
        # host-stepped: the mask sync below is the price of host-side
        # statistics.  The fused AD (repro.core.fused._ad_step) computes
        # the same statistics on device — mean/imbalance deliberately in
        # float32 with the same op order here, so the two selectors can
        # never disagree at a threshold boundary.
        fdeg = self._degrees[np.asarray(updated_mask)]
        degree_sum = int(fdeg.sum())
        max_degree = int(fdeg.max(initial=0))
        mean = np.float32(degree_sum) / np.float32(max(int(count), 1))
        imbalance = (float(np.float32(max_degree) / mean)
                     if mean > 0 else 1.0)
        if self.cost_model is not None:
            choice = self.cost_model.choose(int(count), degree_sum)
        else:
            choice = choose_kernel(
                int(count), degree_sum, max_degree,
                imbalance, mdt=self.mdt_value,
                small_frontier=self.small_frontier,
                imbalance_threshold=self.imbalance_threshold,
                hp_edges_threshold=self.hp_edges_threshold)
        self.kernel_counts[choice] = self.kernel_counts.get(choice, 0) + 1
        extra = {"edge_total": degree_sum} if choice == "WD" else {}
        t0 = (time.perf_counter()
              if (self.online and self.cost_model is not None) else None)
        dist, new_mask, stats = self._kernels[choice].iterate(
            g, dist, updated_mask, count, op=op,
            record_degrees=record_degrees, backend=backend, **extra)
        if t0 is not None:
            jax.block_until_ready(dist)
            self.cost_model.observe(choice, degree_sum, int(count),
                                    time.perf_counter() - t0)
        stats.kernel = choice
        if stats.edges_processed == 0:
            stats.edges_processed = degree_sum
        return dist, new_mask, stats
