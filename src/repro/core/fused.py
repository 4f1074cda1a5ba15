"""Fused on-device fixed-point engine: one dispatch per traversal.

The stepped drivers in :mod:`repro.core.engine` pay a host round-trip per
frontier iteration: sync ``count = int(jnp.sum(mask))``, compact the
frontier on the host side of the jit boundary, pick a capacity bucket, and
re-dispatch a freshly specialized kernel.  On small frontiers that
dispatch latency — not relax work — dominates measured MTEPS, muddying the
kernel-vs-overhead split the paper's Fig. 8–11 analysis depends on.

This module runs an **entire** traversal — any
:class:`repro.core.operators.EdgeOp` semantics: BFS/SSSP, CC min-labels,
widest paths, additive propagation — as a single ``jax.lax.while_loop``
dispatch, the way Gunrock-style frameworks and the GPU load-balancing
programming model of Osama et al. (arXiv:2301.04792) fuse the traversal
into one device-resident loop:

* the frontier is a dense ``[N]`` boolean mask — no host compaction, no
  per-iteration capacity bucketing.  Work lanes are capacity-padded to the
  graph's static shape (``[N]`` node lanes or ``[E]`` edge lanes) with
  validity masks, so every shape inside the loop is fixed;
* the loop condition is ``frontier_any & (it < max_iterations)``,
  evaluated on device;
* host-side ``nonzero``/``cumsum`` compaction is replaced by an on-device
  prefix-sum over masked degrees + ``searchsorted`` (the same merge-path
  structure as the stepped WD kernel);
* the carry accumulates ``(iterations, edges_relaxed)`` so the resulting
  :class:`repro.core.engine.RunResult` stays comparable with stepped runs.

Every registered strategy has a fused lowering (see :func:`_plan`):

========  =================================================================
kernel    dense-mask semantics (chunk boundaries match the stepped driver,
          so ``dist``/``iterations``/``edges_relaxed`` are bit-identical)
========  =================================================================
``BS``    all ``N`` lanes walk their adjacency list in lockstep edge
          columns up to the frontier's max degree (non-frontier lanes
          masked) — same per-column relax batches as ``bs_relax``
``WD``    prefix-sum over masked degrees + searchsorted across ``E`` edge
          lanes — the dense analogue of ``wd_relax``'s merge path
``HP``    ``lax.cond`` hybrid: small frontiers take the WD path (as the
          stepped driver does below ``switch_threshold``); large ones run
          MDT-wide tiles in an inner ``while_loop`` plus a cursor-aware
          WD tail — sub-iteration boundaries match ``hp_sub_relax``
``EP``    all ``E`` edge lanes, valid where the edge's source is in the
          frontier; the loop condition uses the frontier's *edge* total so
          iteration counts match the edge-worklist driver
``NS``    BS on the split graph, with the parent→child mirror
          (``ns_activate`` semantics) folded into the loop body
``AD``    evaluates :func:`repro.core.strategies.choose_kernel`'s decision
          structure on device — frontier statistics (count, degree sum,
          max degree, imbalance) feed a branch index into ``lax.switch``
          over the BS/WD/HP bodies; kernel choices are tallied in the
          carry and surfaced as ``AdaptiveStrategy.kernel_counts``
========  =================================================================

Every step (and the fixed-point dispatcher) additionally takes
``backend="xla" | "pallas"``: "pallas" routes the per-chunk relax
through the fused scatter-combine kernels of :mod:`repro.kernels.relax`
while keeping the chunk schedule — and therefore dist/iterations/edge
totals — bit-identical (docs/backends.md).

Dispatch accounting: :data:`DISPATCH_COUNTS` increments once per traversal
(host side, per ``_fixed_point`` call) and :data:`TRACE_COUNTS` increments
only while jit traces (i.e. per compilation).  Counters are keyed per
backend (``"WD"`` for XLA, ``"pallas:WD"`` for Pallas), so tests can
assert both "exactly one dispatch per traversal, zero recompiles when
shapes repeat" and "switching backend does not recompile the XLA
path".

Everything in this module is fused-safe: no ``int()``, ``np.asarray`` or
other host syncs inside traced code.  Host-side statistics (per-iteration
``IterStats``, ``record_degrees``, balance analysis) are deliberately out
of scope — that is what stepped mode remains for.
"""

from __future__ import annotations

from collections import Counter
from functools import partial
from typing import Any, Optional

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import operators
from repro.core.graph import CSRGraph
from repro.core.operators import EdgeOp
from repro.core.schedule import DEFAULT_SCHEDULE, Schedule
from repro.core.strategies import (
    AdaptiveStrategy, EdgeBased, HierarchicalProcessing, NodeBased,
    NodeSplitting, WorkloadDecomposition, _apply_relax, _edge_weight,
    _merge_path_lanes, pallas_relax, relax_fn)
from repro.core.worklist import prefix_sum

#: traversals started, per kernel — incremented once per fused fixed-point
#: call on the host side.  ``DISPATCH_COUNTS[k]`` growing by exactly 1 per
#: ``engine.run(mode="fused")`` is the "one dispatch per traversal" claim.
DISPATCH_COUNTS: Counter = Counter()

#: jit traces, per kernel — incremented inside the traced function, so it
#: only moves when XLA (re)compiles.  Steady shapes ⇒ steady counts.
TRACE_COUNTS: Counter = Counter()


# ---------------------------------------------------------------------------
# dense-mask relax steps.  Each maps (dist [N], mask [N]) -> (dist, new
# frontier mask, edges relaxed this iteration) with static shapes only.
# ---------------------------------------------------------------------------

def _masked_degrees(g: CSRGraph, mask: jax.Array) -> jax.Array:
    """Out-degree where the node is in the frontier, 0 elsewhere."""
    return jnp.where(mask, g.row_ptr[1:] - g.row_ptr[:-1], 0)


#: base of the two-limb int32 edge accumulator carried through the loop.
#: int64 is unavailable without jax_enable_x64, and a single int32 would
#: silently wrap once a traversal relaxes > 2^31 edges (long-diameter or
#: re-relaxation-heavy runs); two limbs keep totals exact below 2^51.
_LIMB = 1 << 20


def _limb_add(hi, lo, e):
    """(hi, lo) + e with the invariant lo < _LIMB (e any int32 >= 0)."""
    e_hi = e // _LIMB
    lo = lo + (e - e_hi * _LIMB)
    return hi + e_hi + lo // _LIMB, lo % _LIMB


#: lane-block sizes of one relax batch in the XLA lowering.  A batch
#: runs in the smallest block that holds it, so an iteration costs what
#: its frontier relaxes, not the graph's size; a batch past the largest
#: block runs as a loop of largest blocks (see :func:`_relax_batch`).
_BLOCK_SIZES = (1 << 8, 1 << 12, 1 << 16, 1 << 20)


def _block_sizes(cap: int, backend: str) -> tuple:
    """Static block sizes for batches of at most ``cap`` lanes, ascending.
    The last size holds every batch, except in the XLA lowering of a
    batch that can exceed :data:`_BLOCK_SIZES`' largest.  Pallas batches
    run as one block of the full width: its VMEM check already bounds
    the graph, and one width is one kernel to compile."""
    top = 1 << (max(int(cap), 1) - 1).bit_length()
    if backend != "xla":
        return (top,)
    sizes = tuple(s for s in _BLOCK_SIZES if s < top)
    return sizes + (top,) if top <= _BLOCK_SIZES[-1] else sizes


def _snapshot_relax(snap, dist, updated, src, dst, w, valid, *,
                    op: EdgeOp):
    """One block of a multi-block batch: candidates and the improve test
    read ``snap``, the values at the batch's start, and the improving
    candidates fold into ``dist`` — so the blocks together relax exactly
    as one ``_apply_relax`` over all their lanes would."""
    src_c = jnp.clip(src, 0, snap.shape[0] - 1)
    dst_c = jnp.clip(dst, 0, snap.shape[0] - 1)
    cand = op.message(snap[src_c], w)
    improve = valid & op.improves(cand, snap[dst_c])
    return (op.scatter(dist, dst_c, cand, improve),
            updated.at[dst_c].max(improve))


def _relax_batch(dist, updated, total, lanes, *, cap: int, op: EdgeOp,
                 backend: str, sched: Schedule):
    """Relax lanes ``[0, total)`` as ONE batch — the semantics of one
    ``relax_fn`` call over all of them — in blocks sized to ``total``.

    ``lanes(lo, size)`` returns the ``(src, dst, w)`` of lanes
    ``lo .. lo+size-1``; lanes at or past ``total`` are masked.
    ``cap`` bounds ``total`` statically.  The batch runs in the smallest
    block of :func:`_block_sizes` that holds it (one ``lax.switch``
    branch per size); past the largest, a loop of blocks reads from a
    snapshot of the values at entry (:func:`_snapshot_relax`).  The set
    of relaxed ``(src, dst, w)`` triples is the same either way, and the
    monoid fold does not depend on lane order, so results are
    bit-identical to one dense batch."""
    relax = relax_fn(backend, sched)
    sizes = _block_sizes(cap, backend)

    def one_block(size):
        def run(c):
            src, dst, w = lanes(0, size)
            valid = jnp.arange(size, dtype=jnp.int32) < total
            dist, updated, _ = relax(c[0], c[1], src, dst, w, valid, op=op)
            return dist, updated
        return run

    branches = [one_block(s) for s in sizes]
    if max(int(cap), 1) > sizes[-1]:
        size = sizes[-1]

        def loop(c):
            snap = c[0]

            def body(b, c):
                lo = b * size
                src, dst, w = lanes(lo, size)
                valid = lo + jnp.arange(size, dtype=jnp.int32) < total
                return _snapshot_relax(snap, c[0], c[1], src, dst, w, valid,
                                       op=op)
            return lax.fori_loop(0, (total + size - 1) // size, body, c)
        branches.append(loop)
    if len(branches) == 1:
        return branches[0]((dist, updated))
    idx = jnp.sum((total > jnp.asarray(sizes, jnp.int32)).astype(jnp.int32))
    return lax.switch(idx, branches, (dist, updated))


def _merge_path_relax(g: CSRGraph, dist, updated, work, cursor=None, *,
                      op: EdgeOp = operators.shortest_path,
                      backend: str = "xla",
                      sched: Schedule = DEFAULT_SCHEDULE):
    """One synchronous merge-path relax over the ``work`` edges.

    ``work[n]`` is how many edges node ``n`` contributes; lane ``k``
    finds its (node, local-edge) pair in the prefix sum — the on-device
    replacement for host compaction — and the lanes relax as one batch
    (:func:`_relax_batch`).  ``cursor`` (optional) offsets every node's
    read position into its adjacency list (the HP tail).  Returns
    ``(dist, updated, total_work)``.

    ``backend="pallas"`` fuses the search and the relax in one kernel
    (``repro.kernels.relax.wd_relax_lanes``) — the per-lane node index
    never materializes."""
    prefix = prefix_sum(work)
    exclusive = prefix - work
    total = prefix[-1]
    start = g.row_ptr[:-1] if cursor is None else g.row_ptr[:-1] + cursor
    if backend == "pallas":
        src_ids = jnp.arange(g.num_nodes, dtype=jnp.int32)
        prop, upd, _ = pallas_relax.wd_relax_lanes(
            dist, prefix, exclusive, start, src_ids, g.col, g.wt,
            cap_work=g.num_edges, op=op, **pallas_relax.tile_kwargs(sched))
        return (pallas_relax.apply_proposal(dist, prop, op),
                updated | upd, total)
    lanes = _merge_path_lanes(work, prefix, start - exclusive, g.col, g.wt,
                              num_edges=g.num_edges,
                              pad=_block_sizes(g.num_edges, backend)[-1])
    dist, updated = _relax_batch(dist, updated, total, lanes,
                                 cap=g.num_edges, op=op, backend=backend,
                                 sched=sched)
    return dist, updated, total


def _bs_step(g: CSRGraph, dist, mask, *,
             op: EdgeOp = operators.shortest_path, backend: str = "xla",
             sched: Schedule = DEFAULT_SCHEDULE):
    """Dense BS: every frontier node walks its own adjacency list in
    lockstep.

    Column ``d`` relaxes the ``d``-th edge of every frontier node — the
    same relax batches, in the same order, as ``bs_relax`` over a
    compacted frontier, so intra-iteration propagation is identical.
    The frontier is ordered by degree once per iteration, so column
    ``d``'s lanes are a prefix of that order and a column costs the
    nodes still walking, not ``N`` (:func:`_relax_batch`)."""
    deg = _masked_degrees(g, mask)
    n = g.num_nodes
    pad = _block_sizes(n, backend)[-1]
    order = jnp.argsort(deg, descending=True, stable=True).astype(jnp.int32)
    walking = -deg[order]                   # ascending: -degree by rank
    order = jnp.pad(order, (0, pad))
    base = g.row_ptr[:-1]
    fmax = jnp.max(deg)
    updated = jnp.zeros_like(mask)

    def cond(c):
        return c[0] < fmax

    def body(c):
        d, dist, updated = c

        def lanes(lo, size):
            src = lax.dynamic_slice(order, (lo,), (size,))
            eidx = jnp.clip(base[src] + d, 0, g.num_edges - 1)
            return src, g.col[eidx], _edge_weight(g, eidx)
        # frontier nodes with degree > d walk column d
        live = jnp.searchsorted(walking, -d, side="left").astype(jnp.int32)
        dist, updated = _relax_batch(dist, updated, live, lanes, cap=n,
                                     op=op, backend=backend, sched=sched)
        return d + 1, dist, updated

    _, dist, updated = lax.while_loop(cond, body,
                                      (jnp.int32(0), dist, updated))
    return dist, updated, jnp.sum(deg)


def _wd_step(g: CSRGraph, dist, mask, *,
             op: EdgeOp = operators.shortest_path, backend: str = "xla",
             sched: Schedule = DEFAULT_SCHEDULE):
    """Dense WD: merge-path over the frontier's edges, ``E`` lanes.

    One synchronous ``_merge_path_relax`` over the masked degrees — same
    snapshot semantics as ``wd_relax``."""
    deg = _masked_degrees(g, mask)
    updated = jnp.zeros_like(mask)
    dist, updated, total = _merge_path_relax(g, dist, updated, deg, op=op,
                                             backend=backend, sched=sched)
    return dist, updated, total


def _hp_step(g: CSRGraph, dist, mask, *, sched: Schedule = DEFAULT_SCHEDULE,
             op: EdgeOp = operators.shortest_path, backend: str = "xla"):
    """Dense HP: the stepped driver's hybrid, on device.

    ``count <= sched.switch_threshold`` → straight WD (one synchronous
    pass); otherwise MDT-wide tiles in an inner while_loop until the live
    sublist shrinks to the threshold, then a cursor-aware WD tail over the
    remainder.  Chunk boundaries — and therefore intra-iteration value
    propagation — match ``HierarchicalProcessing.iterate`` exactly."""
    mdt = sched.mdt or 1
    switch_threshold = sched.switch_threshold
    deg = _masked_degrees(g, mask)
    count = jnp.sum(mask.astype(jnp.int32))
    n = g.num_nodes

    def small(dist):
        dist, updated, _ = _wd_step(g, dist, mask, op=op, backend=backend,
                                    sched=sched)
        return dist, updated

    def big(dist):
        def live(cursor):
            return jnp.sum((mask & (cursor < deg)).astype(jnp.int32))

        def cond(c):
            i, cursor = c[0], c[1]
            # do-while: the stepped driver always runs the first
            # sub-iteration (entry was gated on count > switch_threshold)
            return (i == 0) | (live(cursor) > switch_threshold)

        def body(c):
            # one sub-iteration: the next <= MDT edges of every live node,
            # one relax batch (the [N, MDT] tile's valid lanes)
            i, cursor, dist, updated = c
            tile = jnp.clip(deg - cursor, 0, mdt)
            dist, updated, _ = _merge_path_relax(g, dist, updated, tile,
                                                 cursor, op=op,
                                                 backend=backend,
                                                 sched=sched)
            return i + 1, cursor + mdt, dist, updated

        i0 = jnp.int32(0)
        cursor0 = jnp.zeros((n,), jnp.int32)
        upd0 = jnp.zeros_like(mask)
        _, cursor, dist, updated = lax.while_loop(
            cond, body, (i0, cursor0, dist, upd0))

        # cursor-aware WD tail over the surviving sublist (≤ threshold
        # nodes, all remaining edges in one synchronous pass)
        rem = jnp.where(mask, jnp.maximum(deg - cursor, 0), 0)
        dist, updated, _ = _merge_path_relax(g, dist, updated, rem, cursor,
                                             op=op, backend=backend,
                                             sched=sched)
        return dist, updated

    dist, updated = lax.cond(count <= switch_threshold, small, big, dist)
    return dist, updated, jnp.sum(deg)


def _ep_step(g: CSRGraph, edge_src, dist, mask, *,
             op: EdgeOp = operators.shortest_path, backend: str = "xla",
             sched: Schedule = DEFAULT_SCHEDULE):
    """Dense EP: all ``E`` edge lanes, valid where the source is live.

    The dense analogue of a chunked edge worklist — deduplicated by
    construction, one synchronous relax per iteration."""
    valid = mask[edge_src]
    eidx = jnp.arange(g.num_edges, dtype=jnp.int32)
    updated = jnp.zeros_like(mask)
    dist, updated, _ = relax_fn(backend, sched)(
        dist, updated, edge_src, g.col, _edge_weight(g, eidx), valid, op=op)
    return dist, updated, jnp.sum(valid.astype(jnp.int32))


def _ns_step(g2: CSRGraph, child_parent, dist, mask, *,
             op: EdgeOp = operators.shortest_path, backend: str = "xla",
             sched: Schedule = DEFAULT_SCHEDULE):
    """Dense NS: mirror parent attributes onto children (the
    ``ns_activate`` gather — operator-generic, see strategies.py), then
    dense BS on the split graph."""
    dist = dist[child_parent]
    mask = mask | mask[child_parent]
    return _bs_step(g2, dist, mask, op=op, backend=backend, sched=sched)


def _ad_step(g: CSRGraph, dist, mask, *, sched: Schedule = DEFAULT_SCHEDULE,
             op: EdgeOp = operators.shortest_path, backend: str = "xla",
             coeffs=None):
    """On-device kernel selection for one AD iteration.

    Frontier statistics (count, degree sum, max degree, imbalance =
    max/mean per-node work) produce a branch index for ``lax.switch``
    over the dense BS/WD/HP bodies.  Returns the index so the caller can
    tally the kernel schedule in the loop carry.

    Two selectors, chosen at trace time:

    * ``coeffs is None`` — the fixed arXiv:1911.09135 decision tree on
      ``sched``'s thresholds.  The mean/imbalance arithmetic is float32
      (x64 is off), and the stepped ``AdaptiveStrategy.iterate`` computes
      its imbalance with the SAME float32 op order so the two selectors
      cannot disagree on a threshold within one rounding step — keep them
      in lockstep.
    * ``coeffs`` a ``[3, 3]`` float32 array — the measured cost model
      (:mod:`repro.core.costmodel`): predicted seconds
      ``a + b·degree_sum + c·count`` per kernel in ``_AD_KERNEL_ORDER``
      order, ``argmin`` picks.  Same float32 op order as the host-side
      ``CostModel.choose`` — same lockstep rule.  Degenerate frontiers
      (no edges / empty mask) still take BS on both selectors."""
    mdt = sched.mdt or 1
    deg = _masked_degrees(g, mask)
    count = jnp.sum(mask.astype(jnp.int32))
    degree_sum = jnp.sum(deg)
    max_degree = jnp.max(deg)
    degenerate = (degree_sum == 0) | (count == 0)
    if coeffs is None:
        mean = degree_sum.astype(jnp.float32) / jnp.maximum(
            count, 1).astype(jnp.float32)
        imbalance = jnp.where(mean > 0,
                              max_degree.astype(jnp.float32) / mean,
                              jnp.float32(1.0))
        take_bs = (degenerate
                   | ((count <= sched.small_frontier)
                      & (imbalance
                         <= jnp.float32(sched.imbalance_threshold))))
        take_hp = ((max_degree > mdt)
                   & (degree_sum >= sched.hp_edges_threshold))
        idx = jnp.where(take_bs, 0,
                        jnp.where(take_hp, 2, 1)).astype(jnp.int32)
    else:
        es = degree_sum.astype(jnp.float32)
        cn = count.astype(jnp.float32)
        costs = coeffs[:, 0] + coeffs[:, 1] * es + coeffs[:, 2] * cn
        idx = jnp.where(degenerate, 0,
                        jnp.argmin(costs).astype(jnp.int32))

    dist, updated, edges = lax.switch(
        idx,
        [lambda d: _bs_step(g, d, mask, op=op, backend=backend,
                            sched=sched),
         lambda d: _wd_step(g, d, mask, op=op, backend=backend,
                            sched=sched),
         lambda d: _hp_step(g, d, mask, sched=sched, op=op,
                            backend=backend)],
        dist)
    return dist, updated, edges, idx


# ---------------------------------------------------------------------------
# the single-dispatch fixed point
# ---------------------------------------------------------------------------

_AD_KERNEL_ORDER = ("BS", "WD", "HP")   # lax.switch branch order


def _count_key(kernel: str, backend: str) -> str:
    """Counter key for a (kernel, backend) pair.  The XLA keys keep
    their historical bare names so "switching backend recompiles
    nothing on the XLA path" is directly observable from
    ``TRACE_COUNTS[kernel]``."""
    return kernel if backend == "xla" else f"{backend}:{kernel}"


@partial(jax.jit, static_argnames=(
    "kernel", "max_iterations", "sched", "op", "backend", "measured"))
def _fixed_point(g: CSRGraph, aux, dist, mask, *, kernel: str,
                 max_iterations: int,
                 sched: Schedule = DEFAULT_SCHEDULE,
                 op: EdgeOp = operators.shortest_path,
                 backend: str = "xla", measured: bool = False):
    """Whole traversal, one dispatch.

    ``aux`` is the kernel's side table: per-edge source ids for ``EP``,
    the child→parent map for ``NS``, the ``[3, 3]`` cost-model
    coefficient array for measured ``AD`` (``measured=True``), a
    1-element dummy otherwise.  ``sched`` is the whole work-assignment
    :class:`~repro.core.schedule.Schedule` as ONE static argument —
    frozen and hashable, so equal schedules share a compiled executable
    and a changed field is a deliberate recompile.  ``op`` is the
    (static) edge operator defining the relax semantics, and ``backend``
    picks the relax lowering (XLA gather/scatter vs the Pallas fused
    scatter-combine — same chunk schedule, bit-identical results).  The
    carry is ``(it, dist, mask, edges_hi, edges_lo, kernel_counts)`` —
    the edge total rides in a two-limb int32 accumulator (``_limb_add``)
    so it stays exact past 2^31; ``kernel_counts`` only moves for
    ``AD``."""
    # Python side effect ⇒ counts compilations, keyed per backend so the
    # XLA cache entry observably survives backend switches
    TRACE_COUNTS[_count_key(kernel, backend)] += 1

    def frontier_live(mask):
        if kernel == "EP":
            # the edge-worklist driver stops when the frontier has no
            # outgoing edges, one round before the node drivers
            return jnp.sum(_masked_degrees(g, mask)) > 0
        return jnp.any(mask)

    def cond(c):
        it, _, mask = c[0], c[1], c[2]
        return frontier_live(mask) & (it < max_iterations)

    def body(c):
        it, dist, mask, e_hi, e_lo, kcounts = c
        if kernel == "BS":
            dist, new_mask, e = _bs_step(g, dist, mask, op=op,
                                         backend=backend, sched=sched)
        elif kernel == "WD":
            dist, new_mask, e = _wd_step(g, dist, mask, op=op,
                                         backend=backend, sched=sched)
        elif kernel == "HP":
            dist, new_mask, e = _hp_step(g, dist, mask, sched=sched,
                                         op=op, backend=backend)
        elif kernel == "EP":
            dist, new_mask, e = _ep_step(g, aux, dist, mask, op=op,
                                         backend=backend, sched=sched)
        elif kernel == "NS":
            dist, new_mask, e = _ns_step(g, aux, dist, mask, op=op,
                                         backend=backend, sched=sched)
        elif kernel == "AD":
            dist, new_mask, e, idx = _ad_step(
                g, dist, mask, sched=sched, op=op, backend=backend,
                coeffs=aux if measured else None)
            kcounts = kcounts.at[idx].add(1)
        else:  # pragma: no cover - guarded by _plan
            raise ValueError(f"unknown fused kernel {kernel!r}")
        e_hi, e_lo = _limb_add(e_hi, e_lo, e)
        return it + 1, dist, new_mask, e_hi, e_lo, kcounts

    carry = (jnp.int32(0), dist, mask, jnp.int32(0), jnp.int32(0),
             jnp.zeros((len(_AD_KERNEL_ORDER),), jnp.int32))
    it, dist, mask, e_hi, e_lo, kcounts = lax.while_loop(cond, body, carry)
    return dist, it, e_hi, e_lo, kcounts


# ---------------------------------------------------------------------------
# strategy instance -> fused lowering
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class FusedPlan:
    """How to run one strategy as a single fused dispatch."""
    kernel: str
    graph: CSRGraph            # graph the loop runs on (split graph for NS)
    aux: Optional[jax.Array]   # EP edge sources / NS child_parent /
    #                            measured-AD cost coefficients
    static: dict               # static kwargs for _fixed_point: the
    #                            resolved Schedule (+ measured for AD v2)


def fused_kernel_name(cls) -> Optional[str]:
    """The fused kernel a strategy *class* lowers to, or ``None``.

    The class-level companion of :func:`_plan` (same precedence order),
    usable without a set-up instance — the capability cross-checker
    (:mod:`repro.analysis.capabilities`) uses it to decide whether a
    declared ``SHARDABLE``/``PRIORITY_SCHEDULE`` flag is backed by an
    actual lowering.  Keep the two in sync."""
    for klass, kernel in ((AdaptiveStrategy, "AD"),
                          (HierarchicalProcessing, "HP"),
                          (NodeSplitting, "NS"),
                          (EdgeBased, "EP"),
                          (WorkloadDecomposition, "WD"),
                          (NodeBased, "BS")):
        if isinstance(cls, type) and issubclass(cls, klass):
            return kernel
    return None


def _sched_of(strategy) -> Schedule:
    """The schedule a fused lowering should run: the instance's resolved
    one (concrete MDT), falling back to the declared / default schedule
    for third-party strategies that skip ``StrategyBase.__init__``."""
    sched = getattr(strategy, "resolved_schedule", None)
    if sched is None:
        sched = getattr(strategy, "schedule", None)
    return sched if isinstance(sched, Schedule) else DEFAULT_SCHEDULE


def _plan(strategy, state, graph: CSRGraph) -> FusedPlan:
    """Map a set-up strategy instance to its fused lowering.

    Raises ``ValueError`` for strategies without one (e.g. user-registered
    strategies whose ``iterate`` is host-stepped only)."""
    if isinstance(strategy, AdaptiveStrategy):
        static = dict(sched=_sched_of(strategy))
        model = getattr(strategy, "cost_model", None)
        if model is not None:
            # measured AD (cost-model v2): the fitted [3, 3] coefficient
            # array rides in the aux slot; `measured` flips _ad_step's
            # selector at trace time
            static["measured"] = True
            return FusedPlan("AD", graph,
                             jnp.asarray(model.coeff_array()), static)
        return FusedPlan("AD", graph, None, static)
    if isinstance(strategy, HierarchicalProcessing):
        return FusedPlan("HP", graph, None, dict(sched=_sched_of(strategy)))
    if isinstance(strategy, NodeSplitting):
        sg = strategy.split_info
        return FusedPlan("NS", sg.graph, sg.child_parent,
                         dict(sched=_sched_of(strategy)))
    if isinstance(strategy, EdgeBased):
        if not strategy.chunked:
            # the unchunked per-edge push (duplicate worklist entries,
            # paper Fig. 11) has no dense equivalent — a dense mask is
            # deduplicated by construction, so fusing it would silently
            # measure the chunked algorithm instead
            raise ValueError(
                "EP with chunked=False has no fused lowering "
                "(dense frontiers are deduplicated by construction); "
                "use mode='stepped'")
        return FusedPlan("EP", graph, state.src,
                         dict(sched=_sched_of(strategy)))
    if isinstance(strategy, WorkloadDecomposition):
        return FusedPlan("WD", graph, None, dict(sched=_sched_of(strategy)))
    if isinstance(strategy, NodeBased):
        return FusedPlan("BS", graph, None, dict(sched=_sched_of(strategy)))
    raise ValueError(
        f"strategy {strategy.name!r} has no fused lowering; "
        f"use mode='stepped'")


def run_fixed_point(graph: CSRGraph, state: Any, strategy, dist0, mask0, *,
                    op: EdgeOp = operators.shortest_path,
                    max_iterations: int = 100000, backend: str = "xla"):
    """Run one strategy's whole traversal as a single fused dispatch.

    ``dist0``/``mask0`` are the initial value/frontier arrays on the
    strategy's allocation (the split graph's for NS) — callers own
    seeding (single source, multi-source CC labels, ...) and extraction;
    ``op`` is the edge operator defining what the traversal computes and
    ``backend`` the relax lowering (docs/backends.md).  Returns
    ``(dist, iterations, edges_relaxed)`` with the first still on
    device; for AD the kernel tally is stored on the strategy as
    ``kernel_counts``, mirroring the stepped driver."""
    plan = _plan(strategy, state, graph)
    DISPATCH_COUNTS[_count_key(plan.kernel, backend)] += 1
    aux = (jnp.zeros((1,), jnp.int32) if plan.aux is None else plan.aux)
    dist, it, e_hi, e_lo, kcounts = _fixed_point(
        plan.graph, aux, dist0, mask0, kernel=plan.kernel,
        max_iterations=max_iterations, op=operators.resolve(op),
        backend=backend, **plan.static)
    jax.block_until_ready(dist)
    if plan.kernel == "AD":
        counts = [int(c) for c in kcounts]
        strategy.kernel_counts = {
            name: c for name, c in zip(_AD_KERNEL_ORDER, counts) if c}
    return dist, int(it), int(e_hi) * _LIMB + int(e_lo)


# ---------------------------------------------------------------------------
# batched multi-source fixed point (K queries, zero host syncs)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("max_iterations", "op", "backend",
                                   "sched"))
def _batch_fixed_point(g: CSRGraph, dist_b, mask_b, *,
                       max_iterations: int,
                       op: EdgeOp = operators.shortest_path,
                       backend: str = "xla",
                       sched: Schedule = DEFAULT_SCHEDULE):
    """All K queries to their fixed points in one dispatch.

    The dense WD step mapped over the source axis inside one while_loop
    — the fused counterpart of ``multi_source.batched_wd_relax``'s
    per-iteration dispatch.  Iterations count until *every* row's
    frontier is empty (the batch's fixed point), matching the stepped
    driver; the edge total sums the per-row masked degree sums."""
    TRACE_COUNTS[_count_key("batch", backend)] += 1

    def cond(c):
        it, _, mask_b = c[0], c[1], c[2]
        return jnp.any(mask_b) & (it < max_iterations)

    def body(c):
        it, dist_b, mask_b, e_hi, e_lo = c
        # rows one after another (lax.map, not vmap): each row's batch
        # picks its own lane-block size, and only that block runs
        dist_b, mask_b, e = lax.map(
            lambda dm: _wd_step(g, dm[0], dm[1], op=op, backend=backend,
                                sched=sched),
            (dist_b, mask_b))
        # fold the K per-row totals one _limb_add at a time (each row is
        # < 2^31, but even the per-row remainders could wrap a plain
        # int32 sum once K is large)
        e_hi, e_lo = lax.fori_loop(
            0, e.shape[0],
            lambda i, c: _limb_add(c[0], c[1], e[i]),
            (e_hi, e_lo))
        return it + 1, dist_b, mask_b, e_hi, e_lo

    it, dist_b, mask_b, e_hi, e_lo = lax.while_loop(
        cond, body, (jnp.int32(0), dist_b, mask_b, jnp.int32(0),
                     jnp.int32(0)))
    return dist_b, it, e_hi, e_lo


def run_batch_fixed_point(graph: CSRGraph, dist_b, mask_b, *,
                          op: EdgeOp = operators.shortest_path,
                          max_iterations: int = 100000,
                          backend: str = "xla",
                          sched: Schedule = DEFAULT_SCHEDULE):
    """Host wrapper for :func:`_batch_fixed_point` (dispatch-counted)."""
    DISPATCH_COUNTS[_count_key("batch", backend)] += 1
    dist_b, it, e_hi, e_lo = _batch_fixed_point(
        graph, dist_b, mask_b, max_iterations=max_iterations,
        op=operators.resolve(op), backend=backend, sched=sched)
    jax.block_until_ready(dist_b)
    return dist_b, int(it), int(e_hi) * _LIMB + int(e_lo)
