"""Sharded multi-device fixed-point engine (docs/sharding.md).

The paper frames edge-based balancing as memory-bound — "unsuitable for
large graphs" (§I) — and at production scale the answer is to partition
the graph across devices, the direction of the work-oriented GPU
load-balancing model of Osama et al. (arXiv:2301.04792) and of
distributed partition/communication layers like Hetu's.  This module
adds a 1-D **node partition** on top of the fused engine:

* :func:`partition` splits a :class:`~repro.core.graph.CSRGraph` into
  ``S`` contiguous node ranges (``method="degree"`` balances *edges* per
  shard via the degree prefix sum; ``"contiguous"`` balances node
  counts), building one local CSR per shard — padded to uniform static
  shapes so the stack rides through ``shard_map`` — plus host-side
  halo/ghost-node maps (:class:`ShardInfo`) quantifying what a sparse
  ghost exchange would move;
* :func:`run_fixed_point` runs the whole traversal as **one dispatch
  per device** under ``shard_map``: every device executes the dense
  fused relax of its own shard's edges against a replicated ``[N]``
  value array, and ghost values are combined with the operator's monoid
  — ``lax.pmin`` / ``lax.pmax`` / delta-``psum`` chosen from
  ``EdgeOp.combine`` — at every **chunk boundary** the single-device
  kernel has (per BS/NS edge column, per HP sub-iteration, once per WD
  iteration, see below), so distances, iteration counts and edge totals
  are **bit-identical** to the single-device fused and stepped paths;
* :func:`run_batch_fixed_point` is the multi-source counterpart: the
  sharded WD step mapped over K sources inside one
  ``lax.while_loop``, mirroring ``fused._batch_fixed_point``.

Why combine-per-chunk and not once per iteration: inside one frontier
iteration the BS/NS column walk and HP's MDT tiles *chain* — a value
written by chunk ``d`` is read by chunk ``d+1``.  The single-device
kernels see every chunk-``d`` write; a shard that combined only at
iteration end would miss writes made by other shards mid-iteration and
converge along a different (Jacobi-like) schedule — same fixed point for
monotone operators, but different iteration counts, breaking the parity
contract.  WD has exactly one chunk per iteration (one merge-path
batch), so there the combine *is* once per iteration.  The combine is
exact, not approximate: integer monoids fold associatively, so splitting
one scatter batch by edge owner and folding across shards reproduces the
single-device scatter bit-for-bit.

Both relax **backends** run per-shard (docs/backends.md).  The default
XLA lowering scatters into the local replica and the chunk-boundary
combine folds whole replicas (:func:`_combine`).  ``backend="pallas"``
dispatches the same fused VMEM kernels the single-device engine uses
(:mod:`repro.kernels.relax`) and fuses the ghost combine into the
kernel **epilogue**: the kernel's dense proposal — the monoid fold of
improving candidates per destination, identity elsewhere — is folded
across shards (``pmin``/``pmax``/``psum``,
:func:`_combine_proposal`) *before* the single elementwise
``apply_proposal``, at exactly the chunk boundaries listed above.
Because the monoid is associative, folding proposals first is
bit-identical to folding post-scatter replicas
(``fold_s(combine(base, prop_s)) == combine(base, fold_s(prop_s))``
for min/max; for ``add`` the local delta *is* the proposal), so the
parity contract holds across the whole backend × shards matrix —
tests/test_sharded.py and tests/test_backends.py enforce it.

Capability gating: only strategies declaring
:data:`repro.core.strategies.SHARDABLE` (BS, WD, HP, NS) accept
``shards=``.  EP stays single-device — its COO edge worklist is a
device-local structure with no owner partition — and AD stays
single-device because its per-iteration kernel choice consumes *global*
frontier statistics; both are documented in docs/sharding.md.

Edge accounting: every shard counts only the masked degrees of the nodes
it **owns**, and the per-shard two-limb totals are ``psum``-folded once
after the loop — each relaxed edge is counted exactly once across
shards, so ``RunResult.mteps`` under sharding is directly comparable to
single-device runs (regression-tested in tests/test_sharded.py).

CPU testing recipe: ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(set **before** importing jax) splits the host into 8 virtual devices;
:func:`shard_mesh` raises with this recipe when too few devices are
visible.
"""

from __future__ import annotations

import dataclasses
from functools import lru_cache, partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import shard_map
from repro.core import operators
from repro.core.fused import (DISPATCH_COUNTS, TRACE_COUNTS, _block_sizes,
                              _count_key, _limb_add, _LIMB, _plan,
                              _relax_batch)
from repro.core.graph import CSRGraph
from repro.core.operators import EdgeOp
from repro.core.schedule import DEFAULT_SCHEDULE, Schedule
from repro.core.strategies import _merge_path_lanes, pallas_relax
from repro.core.worklist import prefix_sum

#: mesh axis name of the 1-D shard partition
AXIS = "shard"

#: fused kernels with a sharded lowering (EP/AD documented out — see
#: module docstring); order has no significance
SHARDED_KERNELS = ("BS", "WD", "HP", "NS")

#: partition methods understood by :func:`partition`
PARTITION_METHODS = ("degree", "contiguous")


# ---------------------------------------------------------------------------
# host-side partitioner
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ShardedCSRGraph:
    """1-D node-partitioned CSR: per-shard local CSRs stacked on axis 0.

    Shard ``s`` owns the contiguous global node range
    ``[node_base[s], node_base[s] + num_local[s])`` and stores those
    nodes' outgoing edges as a *local* CSR (``row_ptr[s]`` indexes into
    ``col[s]``/``wt[s]``; destination ids stay **global** because the
    value array is replicated).  All shards are padded to the widest
    shard (``nodes_per_shard`` / ``edges_per_shard``) so the stack has
    one static shape — padded rows have empty adjacency runs and padded
    edge slots are never validly addressed."""

    row_ptr: jax.Array        # [S, Nmax+1] int32, local offsets
    col: jax.Array            # [S, Emax]   int32, GLOBAL dst ids
    wt: Optional[jax.Array]   # [S, Emax]   int32 (None for BFS inputs)
    node_base: jax.Array      # [S] int32 — first global node id owned
    num_local: jax.Array      # [S] int32 — owned node count
    num_nodes: int            # static: global N
    num_edges: int            # static: global E
    num_shards: int           # static: S
    nodes_per_shard: int      # static: Nmax
    edges_per_shard: int      # static: Emax

    def tree_flatten(self):
        return ((self.row_ptr, self.col, self.wt, self.node_base,
                 self.num_local),
                (self.num_nodes, self.num_edges, self.num_shards,
                 self.nodes_per_shard, self.edges_per_shard))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    def device_bytes(self) -> int:
        total = 0
        for a in (self.row_ptr, self.col, self.wt, self.node_base,
                  self.num_local):
            if a is not None:
                total += a.size * a.dtype.itemsize
        return total


@dataclasses.dataclass
class ShardInfo:
    """Host-side partition bookkeeping: balance + halo/ghost maps.

    ``ghosts[s]`` holds the global ids of *non-owned* destination nodes
    referenced by shard ``s``'s edges — the values shard ``s`` reads
    that some other shard produces.  The engine's dense combine moves
    whole replicas, so these maps are the *information-theoretic* comm
    volume (what a sparse ghost exchange would move); fig15 reports both
    figures."""

    boundaries: np.ndarray    # [S+1] node-range boundaries
    method: str
    nodes: np.ndarray         # [S] owned node counts
    edges: np.ndarray         # [S] owned edge counts
    ghosts: list              # [S] np arrays of ghost (non-owned dst) ids
    cut_edges: np.ndarray     # [S] owned edges whose dst is non-owned

    @property
    def num_shards(self) -> int:
        return len(self.nodes)

    @property
    def cut_share(self) -> float:
        """Edge-cut ratio: fraction of all edges crossing a shard
        boundary — the classic partition-quality metric, and the share
        of relax traffic that is inter-device under a sparse exchange."""
        total = int(self.edges.sum())
        if total == 0:
            return 0.0
        return float(self.cut_edges.sum() / total)

    @property
    def halo_total(self) -> int:
        """Ghost entries summed over shards (one combine's sparse volume)."""
        return int(sum(len(g) for g in self.ghosts))

    @property
    def halo_bytes(self) -> int:
        """int32 bytes a sparse ghost exchange would move per combine."""
        return 4 * self.halo_total

    @property
    def edge_imbalance(self) -> float:
        """max/mean owned edges — 1.0 is a perfectly balanced partition."""
        if self.edges.size == 0 or self.edges.sum() == 0:
            return 1.0
        return float(self.edges.max() / self.edges.mean())


def partition_boundaries(graph: CSRGraph, num_shards: int,
                         method: str = "degree") -> np.ndarray:
    """Contiguous node-range boundaries ``[S+1]`` for ``num_shards``.

    ``"degree"`` cuts the degree prefix sum at multiples of ``E/S``
    (edge-balanced shards — the right default for power-law graphs,
    where equal node counts put almost all edges on one device);
    ``"contiguous"`` splits node ids evenly."""
    if method not in PARTITION_METHODS:
        raise ValueError(f"partition method must be one of "
                         f"{PARTITION_METHODS}, got {method!r}")
    n = graph.num_nodes
    if method == "contiguous":
        bounds = np.round(np.linspace(0, n, num_shards + 1)).astype(np.int64)
    else:
        deg = np.asarray(graph.degrees, np.int64)
        csum = np.cumsum(deg)
        targets = np.arange(1, num_shards) * (graph.num_edges / num_shards)
        # +1: the node whose cumulative degree crosses the target belongs
        # to the LEFT shard — cutting before it would leave every shard
        # up to a heavy early node empty (a hub at node 0 with
        # deg >= E/S would otherwise cascade all cuts to 0)
        cuts = np.searchsorted(csum, targets, side="left") + 1
        bounds = np.concatenate(([0], cuts, [n])).astype(np.int64)
    return np.maximum.accumulate(np.clip(bounds, 0, n))


def partition(graph: CSRGraph, num_shards: int, *,
              method: str = "degree", mesh=None
              ) -> tuple[ShardedCSRGraph, ShardInfo]:
    """Split ``graph`` into ``num_shards`` per-shard local CSRs (host-side
    numpy morph, like :mod:`repro.core.node_split`).  Returns the
    stacked device representation plus host-side :class:`ShardInfo`.
    With ``mesh`` (a :func:`shard_mesh`), each shard's block goes
    straight from the host to its own device; without, the stack lands
    on the default device."""
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    bounds = partition_boundaries(graph, num_shards, method)
    rp = np.asarray(graph.row_ptr, np.int64)
    col = np.asarray(graph.col)
    wt = None if graph.wt is None else np.asarray(graph.wt)

    counts = np.diff(bounds)
    e_counts = rp[bounds[1:]] - rp[bounds[:-1]]
    n_max = max(int(counts.max()), 1) if counts.size else 1
    e_max = max(int(e_counts.max()), 1) if e_counts.size else 1

    row_ptr_s = np.zeros((num_shards, n_max + 1), np.int32)
    col_s = np.zeros((num_shards, e_max), np.int32)
    wt_s = None if wt is None else np.zeros((num_shards, e_max), np.int32)
    ghosts = []
    cut = np.zeros(num_shards, np.int64)
    for s in range(num_shards):
        b0, b1 = int(bounds[s]), int(bounds[s + 1])
        local_rp = rp[b0:b1 + 1] - rp[b0]
        row_ptr_s[s, : b1 - b0 + 1] = local_rp
        row_ptr_s[s, b1 - b0 + 1:] = local_rp[-1]   # padded rows: empty
        e0, e1 = int(rp[b0]), int(rp[b1])
        col_s[s, : e1 - e0] = col[e0:e1]
        if wt is not None:
            wt_s[s, : e1 - e0] = wt[e0:e1]
        crossing = (col[e0:e1] < b0) | (col[e0:e1] >= b1)
        cut[s] = int(crossing.sum())
        ghosts.append(np.unique(col[e0:e1][crossing]))

    if mesh is None:
        put = jnp.asarray
    else:
        put = partial(jax.device_put, device=NamedSharding(mesh, P(AXIS)))
    sharded = ShardedCSRGraph(
        row_ptr=put(row_ptr_s),
        col=put(col_s),
        wt=None if wt_s is None else put(wt_s),
        node_base=put(bounds[:-1].astype(np.int32)),
        num_local=put(counts.astype(np.int32)),
        num_nodes=graph.num_nodes,
        num_edges=graph.num_edges,
        num_shards=num_shards,
        nodes_per_shard=n_max,
        edges_per_shard=e_max,
    )
    info = ShardInfo(boundaries=bounds, method=method,
                     nodes=counts.astype(np.int64),
                     edges=e_counts.astype(np.int64), ghosts=ghosts,
                     cut_edges=cut)
    return sharded, info


@lru_cache(maxsize=None)
def shard_mesh(num_shards: int):
    """1-D device mesh with axis :data:`AXIS` for ``num_shards`` shards.

    Cached per shard count: the mesh is a *static* argument of the
    jitted sharded fixed point, so reusing one object per count keeps
    the jit cache warm across runs."""
    avail = len(jax.devices())
    if num_shards > avail:
        raise ValueError(
            f"{num_shards} shards need {num_shards} devices but only "
            f"{avail} are visible; on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={num_shards} before "
            f"importing jax (docs/sharding.md)")
    return jax.make_mesh((num_shards,), (AXIS,))


# ---------------------------------------------------------------------------
# per-shard dense relax steps (run INSIDE shard_map; fused-safe)
# ---------------------------------------------------------------------------
#
# Each step maps (local CSR block, replicated dist [N], replicated mask
# [N]) -> (combined dist [N], LOCAL updated mask [N], LOCAL owned-degree
# sum).  The caller folds `updated` across shards once per iteration and
# the edge totals once per traversal.

def _squeeze(sg: ShardedCSRGraph):
    """Strip the per-device leading shard axis of length 1."""
    return ShardedCSRGraph(
        row_ptr=sg.row_ptr[0], col=sg.col[0],
        wt=None if sg.wt is None else sg.wt[0],
        node_base=sg.node_base[0], num_local=sg.num_local[0],
        num_nodes=sg.num_nodes, num_edges=sg.num_edges,
        num_shards=sg.num_shards, nodes_per_shard=sg.nodes_per_shard,
        edges_per_shard=sg.edges_per_shard)


def _combine(op: EdgeOp, base, dist):
    """Fold the shards' post-scatter replicas with the operator's monoid.

    ``min``/``max`` are idempotent, so folding whole replicas is exact;
    ``add`` folds the per-shard *deltas* against the chunk's pre-scatter
    ``base`` (folding replicas would multiply ``base`` by S)."""
    if op.combine == "min":
        return lax.pmin(dist, AXIS)
    if op.combine == "max":
        return lax.pmax(dist, AXIS)
    return base + lax.psum(dist - base, AXIS)


def _maybe_combine(op: EdgeOp, base, dist, sync: bool):
    """Chunk-boundary combine in lockstep mode; a no-op in async mode,
    where the shard keeps relaxing against its own (possibly stale)
    replica and the fold happens once per outer epoch instead."""
    return _combine(op, base, dist) if sync else dist


def _combine_proposal(op: EdgeOp, prop):
    """Fold per-shard dense *proposals* across shards — the Pallas
    path's ghost combine, fused into the kernel epilogue.

    A proposal carries the monoid identity for untouched destinations,
    so whole-proposal folds are exact for every built-in combine
    (``add`` included: the local post-scatter delta equals the
    proposal, so ``psum`` of proposals is the delta fold
    :func:`_combine` computes).  Folding proposals *before* the one
    elementwise ``apply_proposal`` is bit-identical to folding the
    post-scatter replicas, by associativity of the monoid."""
    if op.combine == "min":
        return lax.pmin(prop, AXIS)
    if op.combine == "max":
        return lax.pmax(prop, AXIS)
    return lax.psum(prop, AXIS)


def _relax_chunk(dist, updated, src, dst, w, valid, *, op: EdgeOp,
                 sched: Schedule, sync: bool):
    """One direct-mapped Pallas relax batch + its chunk-boundary ghost
    combine: the fused ``relax_lanes`` kernel's dense proposal is folded
    across shards (:func:`_combine_proposal`) before one
    ``apply_proposal`` — the fused-epilogue combine.  ``sync=False``
    (async mode) skips the fold: the relax commits to the local replica
    only."""
    hi = dist.shape[0] - 1
    prop, upd, _ = pallas_relax.relax_lanes(
        dist, jnp.clip(src, 0, hi), jnp.clip(dst, 0, hi), w, valid,
        op=op, **pallas_relax.tile_kwargs(sched))
    if sync:
        prop = _combine_proposal(op, prop)
    return pallas_relax.apply_proposal(dist, prop, op), updated | upd


def _any_across(updated):
    """OR a per-shard boolean mask across shards."""
    return lax.psum(updated.astype(jnp.int32), AXIS) > 0


def _local_weight(sq: ShardedCSRGraph, eidx):
    if sq.wt is not None:
        return sq.wt[eidx]
    return jnp.ones(eidx.shape, jnp.int32)


def _local_frontier(sq: ShardedCSRGraph, mask):
    """(global ids, masked local degrees, membership) of this shard's
    owned slice of the replicated frontier."""
    lanes = jnp.arange(sq.row_ptr.shape[0] - 1, dtype=jnp.int32)
    gids = jnp.clip(sq.node_base + lanes, 0, sq.num_nodes - 1)
    member = (lanes < sq.num_local) & mask[gids]
    deg = jnp.where(member, sq.row_ptr[1:] - sq.row_ptr[:-1], 0)
    return gids, deg, member


def _merge_path_local(sq: ShardedCSRGraph, dist, updated, gids, work,
                      cursor=None, *, op: EdgeOp, backend: str = "xla",
                      sched: Schedule = DEFAULT_SCHEDULE,
                      sync: bool = True):
    """One merge-path relax over this shard's ``Emax`` edge lanes +
    cross-shard combine — the sharded analogue of
    ``fused._merge_path_relax`` (single chunk, so one combine).
    ``backend="pallas"`` fuses the search and the relax in one
    ``wd_relax_lanes`` kernel (its in-kernel rank search resolves every
    lane to the same (node, edge) as ``_merge_path_lanes`` below) and
    folds the proposal across shards in the epilogue.  ``sync=False``
    (async mode) skips the combine: the relax commits to the local
    replica only."""
    prefix = prefix_sum(work)
    exclusive = prefix - work
    total = prefix[-1]
    start = sq.row_ptr[:-1] if cursor is None else sq.row_ptr[:-1] + cursor
    if backend == "pallas":
        prop, upd, _ = pallas_relax.wd_relax_lanes(
            dist, prefix, exclusive, start, gids, sq.col, sq.wt,
            cap_work=sq.edges_per_shard, op=op,
            **pallas_relax.tile_kwargs(sched))
        if sync:
            prop = _combine_proposal(op, prop)
        return (pallas_relax.apply_proposal(dist, prop, op), updated | upd,
                total)
    lanes = _merge_path_lanes(
        work, prefix, start - exclusive, sq.col, sq.wt,
        num_edges=sq.edges_per_shard,
        pad=_block_sizes(sq.edges_per_shard, backend)[-1], src_ids=gids)
    base = dist
    dist, updated = _relax_batch(dist, updated, total, lanes,
                                 cap=sq.edges_per_shard, op=op,
                                 backend=backend, sched=sched)
    return _maybe_combine(op, base, dist, sync), updated, total


def _bs_step(sq: ShardedCSRGraph, dist, mask, *, op: EdgeOp,
             backend: str = "xla", sched: Schedule = DEFAULT_SCHEDULE,
             sync: bool = True):
    """Sharded dense BS: owned lanes walk their adjacency lists in
    lockstep columns; the column count is the *global* frontier max
    degree (``pmax``) so every shard folds the same chunk sequence, and
    the combine runs per column — the chunk boundary at which the
    single-device ``_bs_step`` lets values chain.  ``sync=False`` walks
    only the *local* max degree and never combines (async mode — no
    collectives, shard-dependent trip counts allowed).

    In the XLA lowering the owned lanes are ordered by degree once per
    iteration, as in ``fused._bs_step``, so column ``d`` relaxes only
    the lanes still walking, in a lane block sized to them
    (``_relax_batch``): past the first columns of a skewed frontier a
    column costs its combine, not ``Nmax`` lanes."""
    gids, deg, _ = _local_frontier(sq, mask)
    fmax = lax.pmax(jnp.max(deg), AXIS) if sync else jnp.max(deg)
    updated = jnp.zeros_like(mask)
    n_lanes = deg.shape[0]
    base = sq.row_ptr[:-1]
    order = jnp.argsort(deg, descending=True, stable=True).astype(jnp.int32)
    walking = -deg[order]                   # ascending: -degree by rank
    order = jnp.pad(order, (0, _block_sizes(n_lanes, backend)[-1]))

    def cond(c):
        return c[0] < fmax

    def body(c):
        d, dist, updated = c
        if backend == "pallas":
            eidx = jnp.clip(base + d, 0, sq.edges_per_shard - 1)
            dist, updated = _relax_chunk(
                dist, updated, gids, sq.col[eidx], _local_weight(sq, eidx),
                d < deg, op=op, sched=sched, sync=sync)
            return d + 1, dist, updated

        def lanes(lo, size):
            lane = lax.dynamic_slice(order, (lo,), (size,))
            eidx = jnp.clip(base[lane] + d, 0, sq.edges_per_shard - 1)
            return gids[lane], sq.col[eidx], _local_weight(sq, eidx)
        # owned lanes with degree > d walk column d
        live = jnp.searchsorted(walking, -d, side="left").astype(jnp.int32)
        before = dist
        dist, updated = _relax_batch(dist, updated, live, lanes, cap=n_lanes,
                                     op=op, backend=backend, sched=sched)
        return d + 1, _maybe_combine(op, before, dist, sync), updated

    _, dist, updated = lax.while_loop(cond, body,
                                      (jnp.int32(0), dist, updated))
    return dist, updated, jnp.sum(deg)


def _wd_step(sq: ShardedCSRGraph, dist, mask, *, op: EdgeOp,
             backend: str = "xla", sched: Schedule = DEFAULT_SCHEDULE,
             sync: bool = True):
    """Sharded dense WD: one merge-path batch per shard, one combine per
    iteration (WD's single chunk)."""
    gids, deg, _ = _local_frontier(sq, mask)
    updated = jnp.zeros_like(mask)
    dist, updated, _ = _merge_path_local(sq, dist, updated, gids, deg, op=op,
                                         backend=backend, sched=sched,
                                         sync=sync)
    return dist, updated, jnp.sum(deg)


def _hp_step(sq: ShardedCSRGraph, dist, mask, *,
             sched: Schedule = DEFAULT_SCHEDULE, op: EdgeOp,
             backend: str = "xla", sync: bool = True):
    """Sharded dense HP: the hybrid's branch predicate and the inner
    tile loop's trip count are computed from ``psum``-global counts so
    all shards stay in lockstep; the combine runs per MDT tile (HP's
    sub-iteration chunk boundary) plus once for the WD tail.
    ``sync=False`` decides the branch and tile trip count from *local*
    counts (async shards make local scheduling decisions) and never
    combines."""
    mdt = sched.mdt or 1
    switch_threshold = sched.switch_threshold
    gids, deg, member = _local_frontier(sq, mask)
    local_count = jnp.sum(member.astype(jnp.int32))
    count = lax.psum(local_count, AXIS) if sync else local_count

    def small(dist):
        updated = jnp.zeros_like(mask)
        dist, updated, _ = _merge_path_local(sq, dist, updated, gids, deg,
                                             op=op, backend=backend,
                                             sched=sched, sync=sync)
        return dist, updated

    def big(dist):
        n_lanes = sq.row_ptr.shape[0] - 1

        def live(cursor):
            alive = jnp.sum((cursor < deg).astype(jnp.int32))
            return lax.psum(alive, AXIS) if sync else alive

        def cond(c):
            i, cursor = c[0], c[1]
            # do-while, matching the stepped/fused drivers: entry was
            # gated on count > switch_threshold
            return (i == 0) | (live(cursor) > switch_threshold)

        def body(c):
            # one sub-iteration (the next <= MDT edges of every live
            # owned node) = one merge-path batch + its combine
            i, cursor, dist, updated = c
            tile = jnp.clip(deg - cursor, 0, mdt)
            dist, updated, _ = _merge_path_local(
                sq, dist, updated, gids, tile, cursor, op=op,
                backend=backend, sched=sched, sync=sync)
            return i + 1, cursor + mdt, dist, updated

        cursor0 = jnp.zeros((n_lanes,), jnp.int32)
        upd0 = jnp.zeros_like(mask)
        _, cursor, dist, updated = lax.while_loop(
            cond, body, (jnp.int32(0), cursor0, dist, upd0))

        rem = jnp.maximum(deg - cursor, 0)
        dist, updated, _ = _merge_path_local(sq, dist, updated, gids, rem,
                                             cursor, op=op, backend=backend,
                                             sched=sched, sync=sync)
        return dist, updated

    dist, updated = lax.cond(count <= switch_threshold, small, big, dist)
    return dist, updated, jnp.sum(deg)


def _ns_step(sq: ShardedCSRGraph, child_parent, dist, mask, *, op: EdgeOp,
             backend: str = "xla", sched: Schedule = DEFAULT_SCHEDULE,
             sync: bool = True):
    """Sharded dense NS: the parent→child mirror is a gather on the
    replicated arrays (identical on every shard, no combine needed),
    then sharded BS on the split graph."""
    dist = dist[child_parent]
    mask = mask | mask[child_parent]
    return _bs_step(sq, dist, mask, op=op, backend=backend, sched=sched,
                    sync=sync)


#: kernel -> lockstep step function of the sharded lowering.  The
#: structural record the ``capabilities`` analysis pass (CP001) probes:
#: a kernel's sharded lowering honors ``backend="pallas"`` iff its step
#: takes a ``backend`` parameter to thread into the relax dispatch.
SHARDED_STEPS = {"BS": _bs_step, "WD": _wd_step, "HP": _hp_step,
                 "NS": _ns_step}


# ---------------------------------------------------------------------------
# the sharded single-dispatch fixed point
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=(
    "kernel", "max_iterations", "sched", "op", "mesh", "backend"))
def _sharded_fixed_point(sg: ShardedCSRGraph, aux, dist0, mask0, *,
                         kernel: str, max_iterations: int,
                         sched: Schedule = DEFAULT_SCHEDULE,
                         op: EdgeOp = operators.shortest_path, mesh=None,
                         backend: str = "xla"):
    """Whole sharded traversal: one dispatch, S devices.

    ``dist``/``mask`` are replicated ``[N]`` arrays; the graph stack is
    split over :data:`AXIS`.  ``backend`` picks the per-shard relax
    lowering (XLA scatter vs the Pallas fused kernels with the
    proposal-fold epilogue — see module docstring); both produce
    bit-identical dist/iterations/edges.  The carry mirrors
    ``fused._fixed_point`` minus the AD tally; per-shard edge limbs are
    ``psum``-folded once after the loop so each edge is counted exactly
    once."""
    TRACE_COUNTS[f"shard:{_count_key(kernel, backend)}"] += 1

    def body(sg_blk, aux, dist, mask):
        sq = _squeeze(sg_blk)

        def cond(c):
            it, mask = c[0], c[2]
            return jnp.any(mask) & (it < max_iterations)

        def loop_body(c):
            it, dist, mask, e_hi, e_lo = c
            if kernel == "BS":
                dist, upd, e = _bs_step(sq, dist, mask, op=op,
                                        backend=backend, sched=sched)
            elif kernel == "WD":
                dist, upd, e = _wd_step(sq, dist, mask, op=op,
                                        backend=backend, sched=sched)
            elif kernel == "HP":
                dist, upd, e = _hp_step(sq, dist, mask, sched=sched, op=op,
                                        backend=backend)
            elif kernel == "NS":
                dist, upd, e = _ns_step(sq, aux, dist, mask, op=op,
                                        backend=backend, sched=sched)
            else:  # pragma: no cover - guarded by plan_shards
                raise ValueError(f"unknown sharded kernel {kernel!r}")
            e_hi, e_lo = _limb_add(e_hi, e_lo, e)
            return it + 1, dist, _any_across(upd), e_hi, e_lo

        carry = (jnp.int32(0), dist, mask, jnp.int32(0), jnp.int32(0))
        it, dist, mask, e_hi, e_lo = lax.while_loop(cond, loop_body, carry)
        return dist, it, lax.psum(e_hi, AXIS), lax.psum(e_lo, AXIS)

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(AXIS), P(), P(), P()),
        out_specs=(P(), P(), P(), P()))(
        sg, aux, dist0, mask0)


@partial(jax.jit, static_argnames=(
    "kernel", "max_iterations", "sched", "op", "mesh", "backend"))
def _async_sharded_fixed_point(sg: ShardedCSRGraph, aux, dist0, mask0, *,
                               kernel: str, max_iterations: int,
                               sched: Schedule = DEFAULT_SCHEDULE,
                               op: EdgeOp = operators.shortest_path,
                               mesh=None, backend: str = "xla"):
    """Asynchronous sharded traversal: shards run ahead between combines.

    Each outer **epoch**, every shard drains its *owned* frontier to a
    local fixed point — a collective-free inner ``while_loop`` whose trip
    count is shard-dependent (the very thing the lockstep kernels must
    avoid) — then the replicas are folded once with the operator's monoid
    and nodes whose value the fold improved become the next frontier.
    Stale ghost reads are safe because idempotent monotone monoids only
    ever move values toward the fixed point (the engine gates
    ``async_shards=True`` on ``op.idempotent``); the *final* values are
    exact, while iteration counts and edge totals legitimately differ
    from lockstep runs (docs/scheduling.md).

    ``max_iterations`` caps epochs (= halo combines).  The outer-loop
    condition derives from a carried ``psum``-global liveness bit, so
    every shard agrees on the trip count and the per-epoch collectives
    stay aligned.  Returns ``(dist, epochs, e_hi, e_lo, rounds)`` with
    ``rounds`` the deepest shard's summed inner-loop trips."""
    TRACE_COUNTS[f"shard-async:{_count_key(kernel, backend)}"] += 1

    def body(sg_blk, aux, dist, mask):
        sq = _squeeze(sg_blk)
        ids = jnp.arange(sq.num_nodes, dtype=jnp.int32)
        owned = (ids >= sq.node_base) & (ids < sq.node_base + sq.num_local)

        def eff(mask):
            # NS: a live parent activates its children (the mirror the
            # step kernel applies); children live on whichever shard owns
            # their split id, so the activation must be visible to the
            # inner-loop condition as well
            return (mask | mask[aux]) if kernel == "NS" else mask

        def local_step(dist, mask):
            if kernel == "BS":
                return _bs_step(sq, dist, mask, op=op, backend=backend,
                                sched=sched, sync=False)
            if kernel == "WD":
                return _wd_step(sq, dist, mask, op=op, backend=backend,
                                sched=sched, sync=False)
            if kernel == "HP":
                return _hp_step(sq, dist, mask, sched=sched, op=op,
                                backend=backend, sync=False)
            if kernel == "NS":
                return _ns_step(sq, aux, dist, mask, op=op, backend=backend,
                                sched=sched, sync=False)
            raise ValueError(  # pragma: no cover - guarded by plan_shards
                f"unknown sharded kernel {kernel!r}")

        def inner_cond(c):
            dist, mask = c[0], c[1]
            return jnp.any(eff(mask) & owned)

        def inner_body(c):
            dist, mask, rounds, e_hi, e_lo = c
            # the step relaxes every owned node in the (effective)
            # frontier, so the next local frontier is exactly the nodes
            # this round improved; non-owned activations have no local
            # adjacency — they wait for their owner's next epoch
            dist, upd, e = local_step(dist, eff(mask))
            e_hi, e_lo = _limb_add(e_hi, e_lo, e)
            return dist, upd, rounds + 1, e_hi, e_lo

        def outer_cond(c):
            it, live = c[0], c[1]
            return live & (it < max_iterations)

        def outer_body(c):
            it, live, dist, mask, rounds, e_hi, e_lo = c
            dist, mask, rounds, e_hi, e_lo = lax.while_loop(
                inner_cond, inner_body, (dist, mask, rounds, e_hi, e_lo))
            pre = dist
            dist = _combine(op, pre, dist)       # the epoch's one fold
            changed = op.improves(dist, pre)     # info from other shards
            live = jnp.any(_any_across(changed)) # uniform across shards
            return it + 1, live, dist, changed, rounds, e_hi, e_lo

        carry = (jnp.int32(0), jnp.any(mask), dist, mask, jnp.int32(0),
                 jnp.int32(0), jnp.int32(0))
        it, _live, dist, _mask, rounds, e_hi, e_lo = lax.while_loop(
            outer_cond, outer_body, carry)
        return (dist, it, lax.psum(e_hi, AXIS), lax.psum(e_lo, AXIS),
                lax.pmax(rounds, AXIS))

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(AXIS), P(), P(), P()),
        out_specs=(P(), P(), P(), P(), P()))(
        sg, aux, dist0, mask0)


@dataclasses.dataclass
class ShardedPlan:
    """How to run one strategy's traversal across shards."""
    kernel: str
    sharded: ShardedCSRGraph
    info: ShardInfo
    aux: Optional[jax.Array]     # NS child→parent map
    static: dict                 # static kwargs (the resolved Schedule)
    #                              for _sharded_fixed_point
    mesh: Any


def plan_shards(strategy, state, graph: CSRGraph, num_shards: int, *,
                method: str = "degree", mesh=None) -> ShardedPlan:
    """Map a set-up strategy to its sharded lowering + partition.

    Host-side setup work (numpy partition + mesh construction) — the
    engine books it as ``setup_seconds``.  Raises for strategies whose
    fused kernel has no sharded lowering (EP, AD — see module
    docstring)."""
    plan = _plan(strategy, state, graph)
    if plan.kernel not in SHARDED_KERNELS:
        raise ValueError(
            f"fused kernel {plan.kernel!r} has no sharded lowering; "
            f"shardable kernels: {SHARDED_KERNELS} (EP's COO worklist "
            f"and AD's global frontier statistics stay single-device — "
            f"docs/sharding.md)")
    if mesh is None:
        mesh = shard_mesh(num_shards)
    sharded, info = partition(plan.graph, num_shards, method=method,
                              mesh=mesh)
    return ShardedPlan(plan.kernel, sharded, info, plan.aux, plan.static,
                       mesh)


def run_fixed_point(splan: ShardedPlan, dist0, mask0, *,
                    op: EdgeOp = operators.shortest_path,
                    max_iterations: int = 100000,
                    async_mode: bool = False, backend: str = "xla"):
    """Run one planned sharded traversal (dispatch-counted like
    :func:`repro.core.fused.run_fixed_point`).  Returns
    ``(dist, iterations, edges_relaxed, relax_rounds)`` with ``dist`` on
    device.  ``backend`` picks the per-shard relax lowering (XLA keys
    keep their historical bare counter names, exactly as in
    ``fused._count_key``).  Lockstep mode (the default) keeps the
    bit-parity contract with the single-device paths and reports
    ``relax_rounds == iterations``; ``async_mode=True`` lets shards run
    ahead between halo combines (:func:`_async_sharded_fixed_point`) —
    ``iterations`` then counts combine epochs and ``relax_rounds`` the
    deepest shard's local relax rounds."""
    aux = (jnp.zeros((1,), jnp.int32) if splan.aux is None else splan.aux)
    if async_mode:
        DISPATCH_COUNTS[f"shard-async:{_count_key(splan.kernel, backend)}"] \
            += 1
        dist, it, e_hi, e_lo, rounds = _async_sharded_fixed_point(
            splan.sharded, aux, dist0, mask0, kernel=splan.kernel,
            max_iterations=max_iterations, op=operators.resolve(op),
            mesh=splan.mesh, backend=backend, **splan.static)
    else:
        DISPATCH_COUNTS[f"shard:{_count_key(splan.kernel, backend)}"] += 1
        dist, it, e_hi, e_lo = _sharded_fixed_point(
            splan.sharded, aux, dist0, mask0, kernel=splan.kernel,
            max_iterations=max_iterations, op=operators.resolve(op),
            mesh=splan.mesh, backend=backend, **splan.static)
        rounds = it
    jax.block_until_ready(dist)
    return dist, int(it), int(e_hi) * _LIMB + int(e_lo), int(rounds)


# ---------------------------------------------------------------------------
# sharded batched multi-source fixed point
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("max_iterations", "op", "mesh", "sched",
                                   "backend"))
def _sharded_batch_fixed_point(sg: ShardedCSRGraph, dist_b, mask_b, *,
                               max_iterations: int,
                               op: EdgeOp = operators.shortest_path,
                               mesh=None,
                               sched: Schedule = DEFAULT_SCHEDULE,
                               backend: str = "xla"):
    """All K sources to their fixed points, sharded: the sharded WD step
    mapped over the source axis inside one ``lax.while_loop`` — the
    multi-device counterpart of ``fused._batch_fixed_point``, rows one
    after another (``lax.map``) for the same reason: each row's relax
    batch runs only its own lane-block branch, where ``vmap`` would run
    every branch.  The per-row edge totals are already global after the
    per-row ``psum``, so the limb fold matches it bit-for-bit.  ``backend``
    swaps the per-shard relax lowering exactly as in
    :func:`_sharded_fixed_point`."""
    TRACE_COUNTS[f"shard:{_count_key('batch', backend)}"] += 1

    def body(sg_blk, dist_b, mask_b):
        sq = _squeeze(sg_blk)

        def cond(c):
            it, mask_b = c[0], c[2]
            return jnp.any(mask_b) & (it < max_iterations)

        def loop_body(c):
            it, dist_b, mask_b, e_hi, e_lo = c

            def one(dm):
                dist, upd, e = _wd_step(sq, dm[0], dm[1], op=op,
                                        backend=backend, sched=sched)
                return dist, _any_across(upd), lax.psum(e, AXIS)

            dist_b, mask_b, e = lax.map(one, (dist_b, mask_b))
            e_hi, e_lo = lax.fori_loop(
                0, e.shape[0],
                lambda i, c: _limb_add(c[0], c[1], e[i]),
                (e_hi, e_lo))
            return it + 1, dist_b, mask_b, e_hi, e_lo

        it, dist_b, mask_b, e_hi, e_lo = lax.while_loop(
            cond, loop_body, (jnp.int32(0), dist_b, mask_b, jnp.int32(0),
                              jnp.int32(0)))
        return dist_b, it, e_hi, e_lo

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(AXIS), P(), P()),
        out_specs=(P(), P(), P(), P()))(sg, dist_b, mask_b)


def run_batch_fixed_point(sharded: ShardedCSRGraph, dist_b, mask_b, *,
                          mesh, op: EdgeOp = operators.shortest_path,
                          max_iterations: int = 100000,
                          sched: Schedule = DEFAULT_SCHEDULE,
                          backend: str = "xla"):
    """Host wrapper for :func:`_sharded_batch_fixed_point`."""
    DISPATCH_COUNTS[f"shard:{_count_key('batch', backend)}"] += 1
    dist_b, it, e_hi, e_lo = _sharded_batch_fixed_point(
        sharded, dist_b, mask_b, max_iterations=max_iterations,
        op=operators.resolve(op), mesh=mesh, sched=sched, backend=backend)
    jax.block_until_ready(dist_b)
    return dist_b, int(it), int(e_hi) * _LIMB + int(e_lo)
