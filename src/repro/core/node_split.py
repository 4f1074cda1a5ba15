"""Node splitting (paper §III-B): graph preprocessing that bounds the
maximum outdegree by MDT, plus the histogram heuristic that picks MDT
automatically.

This is morph (structure-changing) work done once, host-side in numpy —
the paper likewise performs splitting as a static preprocessing phase.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import CSRGraph


def find_mdt(degrees: np.ndarray, histogram_bins: int = 10) -> int:
    """Histogram-based automatic MDT (paper §III-B).

    Bin the outdegrees into ``histogram_bins`` ranges over [0, maxDegree],
    take the *tallest* bin (the degree range holding the most nodes) and set
    ``MDT = (upper edge of that bin / bins) × maxDegree``.  Using the bin's
    upper edge reproduces the paper's reported values (roads/ER: MDT 2–4;
    RMAT-class: MDT ≈ maxDegree/bins ≈ 118 for rmat20) and maximizes the
    number of nodes already at ≤ MDT, minimizing the amount of splitting.
    """
    degrees = np.asarray(degrees)
    degrees = degrees[degrees > 0]
    if degrees.size == 0:
        return 1
    max_degree = int(degrees.max())
    if max_degree <= 1:
        return 1
    hist, _ = np.histogram(degrees, bins=histogram_bins,
                           range=(0, max_degree))
    bin_index = int(np.argmax(hist))
    mdt = int(round((bin_index + 1) / histogram_bins * max_degree))
    return max(1, mdt)


@dataclasses.dataclass
class SplitGraph:
    """The split graph + parent bookkeeping.

    Node ids 0..N-1 are the originals (each keeps its first ≤MDT edges);
    children occupy N..N2-1 and carry the remaining edge slices.  Incoming
    edges still target the parent only (dst ids are unchanged), so
    ``child_parent`` lets each iteration mirror parent attributes onto
    children (strategies.ns_mirror)."""

    graph: CSRGraph
    child_parent: jax.Array   # [N2] int32; originals map to themselves
    num_original: int
    mdt: int
    num_children: int

    def extract_original(self, dist: jax.Array) -> jax.Array:
        return dist[: self.num_original]


def split_graph(g: CSRGraph, mdt: int) -> SplitGraph:
    """Split every node with outdegree > MDT into ⌈deg/MDT⌉ pieces, edges
    partitioned contiguously among parent + children (paper Fig. 5)."""
    mdt = max(1, int(mdt))
    row_ptr = np.asarray(g.row_ptr, np.int64)
    col = np.asarray(g.col)
    wt = np.asarray(g.wt) if g.weighted else None
    n = g.num_nodes
    deg = row_ptr[1:] - row_ptr[:-1]

    pieces = np.maximum(1, -(-deg // mdt))          # ⌈deg/MDT⌉, ≥1
    n_children = int((pieces - 1).sum())
    n2 = n + n_children

    # new-node table: originals first, then children grouped by parent
    parent_of = np.arange(n2, dtype=np.int64)
    piece_idx = np.zeros(n2, dtype=np.int64)        # which slice of parent
    child_rows = np.repeat(np.arange(n), pieces - 1)
    parent_of[n:] = child_rows
    # per-parent running piece index 1..pieces-1
    if n_children:
        first_child = np.zeros(n, np.int64)
        np.cumsum(pieces - 1, out=first_child)
        first_child = np.concatenate([[0], first_child[:-1]]) + n
        piece_idx[n:] = np.arange(n_children) - (first_child[child_rows] - n) + 1

    # per-new-node edge slice [start, start+len) of the parent's adjacency
    starts = row_ptr[parent_of] + piece_idx * mdt
    lens = np.minimum(deg[parent_of] - piece_idx * mdt, mdt)
    lens = np.maximum(lens, 0)

    new_row_ptr = np.zeros(n2 + 1, np.int64)
    np.cumsum(lens, out=new_row_ptr[1:])
    total = int(new_row_ptr[-1])
    assert total == g.num_edges, (total, g.num_edges)

    if total:
        gather = (np.repeat(starts, lens)
                  + np.arange(total) - np.repeat(new_row_ptr[:-1], lens))
    else:
        gather = np.zeros(0, np.int64)
    new_col = col[gather]
    new_wt = None if wt is None else wt[gather]

    g2 = CSRGraph(
        row_ptr=jnp.asarray(new_row_ptr, jnp.int32),
        col=jnp.asarray(new_col, jnp.int32),
        wt=None if new_wt is None else jnp.asarray(new_wt, jnp.int32),
        num_nodes=n2,
        num_edges=g.num_edges,
        max_degree=int(lens.max()) if lens.size else 0,
    )
    return SplitGraph(
        graph=g2,
        child_parent=jnp.asarray(parent_of, jnp.int32),
        num_original=n,
        mdt=mdt,
        num_children=n_children,
    )
