"""Distributed graph engine: 1-D node partitioning + frontier exchange.

The Graph500-scale story (paper §IV: "HP will have larger importance as we
explore real-world BigData graphs"): one pod cannot hold the graph, so
nodes are range-partitioned across the data axis, each device relaxes its
own rows with the WD (merge-path) discipline, and cross-partition edge
relaxations are routed to their owner with a bucketed ``all_to_all`` —
the jax-native equivalent of the MPI frontier exchange in distributed BFS
(Buluç-Madduri), composed with the paper's intra-device load balancing.

Messages are (dst, alt-distance) pairs in fixed-capacity per-owner buckets
(static shapes for SPMD); capacity overflow is detected and surfaced (a
real system would re-run the sub-iteration — here the cap is sized to the
worst case E_loc so it cannot drop).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compat import shard_map
from repro.core.graph import CSRGraph, INF


@dataclasses.dataclass
class PartitionedGraph:
    """Per-shard padded CSR: leading axis = partition (sharded over data)."""
    row_ptr: jax.Array      # [Pn, n_loc+1] local offsets
    col: jax.Array          # [Pn, e_loc] global dst ids (padded -1)
    wt: jax.Array           # [Pn, e_loc]
    num_nodes: int
    n_loc: int
    e_loc: int
    num_parts: int


def partition_graph(g: CSRGraph, parts: int) -> PartitionedGraph:
    """Host-side 1-D range partition with per-shard padding."""
    row_ptr = np.asarray(g.row_ptr, np.int64)
    col = np.asarray(g.col)
    wt = (np.asarray(g.wt) if g.weighted
          else np.ones(g.num_edges, np.int32))
    n = g.num_nodes
    n_loc = -(-n // parts)
    e_loc = 1
    shards = []
    for p in range(parts):
        lo, hi = p * n_loc, min((p + 1) * n_loc, n)
        base = row_ptr[lo]
        rp = row_ptr[lo:hi + 1] - base
        rp = np.pad(rp, (0, n_loc + 1 - len(rp)), mode="edge")
        c = col[row_ptr[lo]: row_ptr[hi]]
        w = wt[row_ptr[lo]: row_ptr[hi]]
        shards.append((rp, c, w))
        e_loc = max(e_loc, len(c))
    rps = np.stack([s[0] for s in shards])
    cols = np.stack([np.pad(s[1], (0, e_loc - len(s[1])),
                            constant_values=-1) for s in shards])
    wts = np.stack([np.pad(s[2], (0, e_loc - len(s[2]))) for s in shards])
    return PartitionedGraph(
        row_ptr=jnp.asarray(rps, jnp.int32), col=jnp.asarray(cols, jnp.int32),
        wt=jnp.asarray(wts, jnp.int32), num_nodes=n, n_loc=n_loc,
        e_loc=e_loc, num_parts=parts)


def distributed_sssp(g: CSRGraph, source: int, mesh: Mesh,
                     max_iterations: int = 10000) -> np.ndarray:
    """SSSP over a partitioned graph with WD-balanced local expansion."""
    axis = "data"
    parts = mesh.shape[axis]
    pg = partition_graph(g, parts)
    n_loc, e_loc = pg.n_loc, pg.e_loc
    cap_msg = e_loc                        # worst case: every edge crosses

    def iteration(rp, col, wt, dist_loc, mask_loc):
        """One relax+exchange sub-round on each device (shard_map body).
        All arrays are this device's shard ([n_loc+1], [e_loc], ...)."""
        me = jax.lax.axis_index(axis)
        rp, col, wt = rp[0], col[0], wt[0]
        dist_loc, mask_loc = dist_loc[0], mask_loc[0]
        deg = jnp.where(mask_loc, rp[1:] - rp[:-1], 0)
        prefix = jnp.cumsum(deg)
        total = prefix[-1]
        k = jnp.arange(e_loc, dtype=jnp.int32)
        node = jnp.searchsorted(prefix, k, side="right").astype(jnp.int32)
        node = jnp.clip(node, 0, n_loc - 1)
        local = k - (prefix[node] - deg[node])
        eidx = jnp.clip(rp[node] + local, 0, e_loc - 1)
        valid = (k < total) & (col[eidx] >= 0)
        dst = jnp.where(valid, col[eidx], 0)
        alt = dist_loc[node] + wt[eidx]
        owner = jnp.clip(dst // n_loc, 0, parts - 1)
        # bucket (dst, alt) by owner: position via per-owner cumsum
        onehot = (jax.nn.one_hot(owner, parts, dtype=jnp.int32)
                  * valid[:, None].astype(jnp.int32))
        excl = jnp.cumsum(onehot, axis=0) - onehot       # [e_loc, parts]
        pos = jnp.take_along_axis(excl, owner[:, None], axis=1)[:, 0]
        slot = jnp.where(valid & (pos < cap_msg), owner * cap_msg + pos,
                         parts * cap_msg)
        buf_dst = jnp.full((parts * cap_msg + 1,), -1, jnp.int32
                           ).at[slot].set(jnp.where(valid, dst, -1))
        buf_alt = jnp.full((parts * cap_msg + 1,), INF, jnp.int32
                           ).at[slot].set(jnp.where(valid, alt, INF))
        buf_dst = buf_dst[:-1].reshape(parts, cap_msg)
        buf_alt = buf_alt[:-1].reshape(parts, cap_msg)
        # frontier exchange
        rx_dst = jax.lax.all_to_all(buf_dst, axis, 0, 0, tiled=False)
        rx_alt = jax.lax.all_to_all(buf_alt, axis, 0, 0, tiled=False)
        rx_dst = rx_dst.reshape(-1)
        rx_alt = rx_alt.reshape(-1)
        ok = rx_dst >= 0
        loc_idx = jnp.clip(jnp.where(ok, rx_dst - me * n_loc, 0), 0,
                           n_loc - 1)
        cand = jnp.where(ok, rx_alt, INF)
        improve = cand < dist_loc[loc_idx]
        new_dist = dist_loc.at[loc_idx].min(jnp.where(improve, cand, INF))
        new_mask = jnp.zeros_like(mask_loc).at[loc_idx].max(improve)
        count = jax.lax.psum(jnp.sum(new_mask, dtype=jnp.int32), axis)
        return (new_dist[None], new_mask[None], count[None])

    sharded = jax.jit(shard_map(
        iteration, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis), P(axis))))

    # initial state (host-built, device-sharded)
    dist = np.full((parts, n_loc), INF, np.int32)
    mask = np.zeros((parts, n_loc), bool)
    dist[source // n_loc, source % n_loc] = 0
    mask[source // n_loc, source % n_loc] = True
    sh = NamedSharding(mesh, P(axis))
    dist = jax.device_put(jnp.asarray(dist), sh)
    mask = jax.device_put(jnp.asarray(mask), sh)
    rp = jax.device_put(pg.row_ptr, sh)
    col = jax.device_put(pg.col, sh)
    wt = jax.device_put(pg.wt, sh)

    it, count = 0, 1
    while count > 0 and it < max_iterations:
        dist, mask, counts = sharded(rp, col, wt, dist, mask)
        count = int(np.asarray(counts)[0])
        it += 1
    out = np.asarray(dist).reshape(-1)[: g.num_nodes]
    return out
