"""Graph containers for the load-balancing engine.

Two storage formats, mirroring the paper's discussion (§II):

* :class:`CSRGraph` — compressed sparse row.  ``N + 1 + E`` storage; the
  format required by the node-based (BS), workload-decomposition (WD),
  node-splitting (NS) and hierarchical (HP) strategies.
* :class:`COOGraph` — coordinate list.  ``2E`` (``3E`` weighted) storage;
  required by edge-based parallelism (EP).  The memory blow-up relative to
  CSR is the paper's central argument against EP for large graphs and is
  reproduced faithfully here (see :meth:`COOGraph.device_bytes`).

Both are registered JAX pytrees so they can flow through ``jit`` /
``shard_map`` unchanged.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

INF = jnp.iinfo(jnp.int32).max // 2  # "infinity" that survives + weight


def _field_bytes(*arrays) -> int:
    total = 0
    for a in arrays:
        if a is not None:
            total += a.size * a.dtype.itemsize
    return total


def pack_shift(num_nodes: int, wt: np.ndarray,
               dst: np.ndarray) -> Optional[int]:
    """The shift of the packed edge word for these edges, or ``None``
    when their weights cannot share a word with their heads.

    A head takes the low ``b = max(1, (num_nodes - 1).bit_length())``
    bits and the weight the bits above it, so the word is exact when
    every head lies in ``[0, num_nodes)`` and every weight in
    ``[0, 2**(31 - b))``."""
    b = max(1, (num_nodes - 1).bit_length())
    if not len(dst) or dst.min() < 0 or dst.max() >= num_nodes:
        return None
    if wt.min() < 0 or wt.max() >= (1 << (31 - b)):
        return None
    return b


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(init=False)
class CSRGraph:
    """CSR graph.  ``row_ptr[n] : row_ptr[n+1]`` index into ``col`` and
    the weights.

    Packed edge word: :meth:`from_edges` stores a weighted graph whose
    weights fit beside its heads as one int32 word per edge,
    ``(w << wt_shift) | col`` (:func:`pack_shift`), in place of the
    weight array, so a lane that relaxes an edge reads its head and its
    weight with one gather.  ``col`` stays as it is, so the device bytes
    are the same as the plain layout's.  ``wt_shift`` (static) is
    ``None`` for the plain layout: unweighted graphs, weights that do
    not fit (negative, or too large for the bits above the head), and
    every graph built through the constructor (:meth:`plain`).

    The word's format is known here alone: :meth:`edge_pair` and
    :meth:`edges` give ``(dst, w)`` in either layout, ``wt`` the plain
    weights and ``weighted`` whether there are any."""

    row_ptr: jax.Array           # [N+1] int32
    col: jax.Array               # [E]   int32 — destination node ids
    #: [E] int32 as stored: the weights, or with ``wt_shift`` the packed
    #: words; None for BFS inputs
    wt_word: Optional[jax.Array]
    num_nodes: int               # static
    num_edges: int               # static
    max_degree: int              # static — used for BS padding bounds
    wt_shift: Optional[int] = None   # static — None: plain layout

    def __init__(self, row_ptr, col, wt, num_nodes, num_edges, max_degree):
        """A plain-layout graph: ``wt`` the weights (or None)."""
        self.row_ptr, self.col, self.wt_word = row_ptr, col, wt
        self.num_nodes, self.num_edges = num_nodes, num_edges
        self.max_degree, self.wt_shift = max_degree, None

    @classmethod
    def _stored(cls, row_ptr, col, wt_word, num_nodes, num_edges,
                max_degree, wt_shift):
        g = cls(row_ptr, col, wt_word, num_nodes, num_edges, max_degree)
        g.wt_shift = wt_shift
        return g

    # -- pytree protocol -------------------------------------------------
    def tree_flatten(self):
        return (self.row_ptr, self.col, self.wt_word), (
            self.num_nodes, self.num_edges, self.max_degree, self.wt_shift)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls._stored(*children, *aux)

    # -- edges --------------------------------------------------------------
    def _head(self, word):
        return word & ((1 << self.wt_shift) - 1)

    def _weight(self, word):
        return word >> self.wt_shift

    @property
    def weighted(self) -> bool:
        return self.wt_word is not None

    @property
    def wt(self) -> Optional[jax.Array]:
        """``[E]`` int32 edge weights (None for BFS inputs).  Packed, each
        read decodes the words into a new ``[E]`` array: per-lane readers
        take :meth:`edge_pair` instead."""
        if self.wt_shift is None:
            return self.wt_word
        return self._weight(self.wt_word)

    def edge_pair(self, eidx: jax.Array):
        """``(dst, w)`` of edges ``eidx`` (``w`` ones when unweighted):
        packed, one gather of the word gives both."""
        if self.wt_shift is not None:
            word = self.wt_word[eidx]
            return self._head(word), self._weight(word)
        dst = self.col[eidx]
        return dst, (self.wt_word[eidx] if self.weighted
                     else jnp.ones(eidx.shape, jnp.int32))

    def edges(self):
        """``(dst [E], w [E])`` of every edge (``w`` ones when
        unweighted)."""
        if self.wt_shift is not None:
            return self._head(self.wt_word), self._weight(self.wt_word)
        return self.col, self.weight_or_one()

    def plain(self) -> "CSRGraph":
        """This graph in the plain layout (itself when already plain):
        for readers of whole weight arrays, such as the Pallas kernels'
        VMEM tables.  Packed, it decodes the words once."""
        if self.wt_shift is None:
            return self
        return CSRGraph(self.row_ptr, self.col, self.wt, self.num_nodes,
                        self.num_edges, self.max_degree)

    @property
    def degrees(self) -> jax.Array:
        return self.row_ptr[1:] - self.row_ptr[:-1]

    def device_bytes(self) -> int:
        return _field_bytes(self.row_ptr, self.col, self.wt_word)

    def out_degree(self, nodes: jax.Array) -> jax.Array:
        return self.row_ptr[nodes + 1] - self.row_ptr[nodes]

    def weight_or_one(self) -> jax.Array:
        if self.weighted:
            return self.wt
        return jnp.ones((self.num_edges,), jnp.int32)

    def to_coo(self) -> "COOGraph":
        """Expand CSR to COO — the conversion the paper notes EP requires.

        Source ids are duplicated per edge (the 2E memory cost)."""
        src = expand_row_ptr(self.row_ptr, self.num_edges)
        return COOGraph(src=src, dst=self.col, wt=self.wt,
                        num_nodes=self.num_nodes, num_edges=self.num_edges,
                        max_degree=self.max_degree,
                        row_ptr=self.row_ptr)

    @classmethod
    def from_edges(cls, src: np.ndarray, dst: np.ndarray,
                   wt: Optional[np.ndarray], num_nodes: int,
                   sort: bool = True, dedup: bool = False) -> "CSRGraph":
        """Build (host-side, numpy) a CSR graph from an edge list, in
        the packed layout when the weights fit (:func:`pack_shift`)."""
        src = np.asarray(src, np.int64)
        dst = np.asarray(dst, np.int64)
        if dedup:
            # keep the first occurrence of every (src, dst) pair, in key
            # order (what np.unique(return_index=True) gives, without
            # its stable sort): sorted by src already
            key = src * num_nodes + dst
            order = np.argsort(key)
            key = key[order]
            starts = np.flatnonzero(np.diff(key, prepend=-1))
            idx = (np.minimum.reduceat(order, starts) if len(starts)
                   else starts)
            src, dst = src[idx], dst[idx]
            if wt is not None:
                wt = np.asarray(wt)[idx]
        if sort and not dedup:
            order = np.argsort(src, kind="stable")
            src, dst = src[order], dst[order]
            if wt is not None:
                wt = np.asarray(wt)[order]
        counts = np.bincount(src, minlength=num_nodes)
        row_ptr = np.zeros(num_nodes + 1, np.int32)
        np.cumsum(counts, out=row_ptr[1:])
        max_degree = int(counts.max()) if num_nodes else 0
        col = dst.astype(np.int32)
        shift = None
        if wt is not None:
            wt = np.asarray(wt)
            shift = pack_shift(int(num_nodes), wt, dst)
            if shift is not None:
                wt = (wt.astype(np.int32) << shift) | col
        return cls._stored(
            row_ptr=jnp.asarray(row_ptr, jnp.int32),
            col=jnp.asarray(col, jnp.int32),
            wt_word=None if wt is None else jnp.asarray(wt, jnp.int32),
            num_nodes=int(num_nodes),
            num_edges=int(len(dst)),
            max_degree=max_degree,
            wt_shift=shift,
        )


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class COOGraph:
    """COO graph for edge-based parallelism.  Keeps ``row_ptr`` around for
    work-chunked worklist pushes (reserving one output range per node)."""

    src: jax.Array           # [E] int32
    dst: jax.Array           # [E] int32
    wt: Optional[jax.Array]  # [E] int32
    num_nodes: int
    num_edges: int
    max_degree: int
    row_ptr: Optional[jax.Array] = None  # [N+1] — for chunked pushes

    def tree_flatten(self):
        return (self.src, self.dst, self.wt, self.row_ptr), (
            self.num_nodes, self.num_edges, self.max_degree)

    @classmethod
    def tree_unflatten(cls, aux, children):
        src, dst, wt, row_ptr = children
        return cls(src, dst, wt, aux[0], aux[1], aux[2], row_ptr)

    def device_bytes(self) -> int:
        return _field_bytes(self.src, self.dst, self.wt, self.row_ptr)

    def weight_or_one(self) -> jax.Array:
        if self.wt is not None:
            return self.wt
        return jnp.ones((self.num_edges,), jnp.int32)


@partial(jax.jit, static_argnames=("num_edges",))
def expand_row_ptr(row_ptr: jax.Array, num_edges: int) -> jax.Array:
    """CSR row_ptr -> per-edge source id, via scatter-add + cumulative max.

    Vectorized equivalent of duplicating ``src`` across a node's edges."""
    n = row_ptr.shape[0] - 1
    marks = jnp.zeros((num_edges,), jnp.int32)
    starts = jnp.clip(row_ptr[:-1], 0, num_edges - 1)
    has_edges = (row_ptr[1:] - row_ptr[:-1]) > 0
    ids = jnp.arange(n, dtype=jnp.int32)
    marks = marks.at[starts].max(jnp.where(has_edges, ids, 0))
    return jax.lax.associative_scan(jnp.maximum, marks)


def graph_stats(g: CSRGraph) -> dict:
    """Table-II style stats: max / avg / sigma of outdegrees."""
    deg = np.asarray(g.degrees)
    return {
        "nodes": g.num_nodes,
        "edges": g.num_edges,
        "max_deg": int(deg.max()) if deg.size else 0,
        "avg_deg": float(deg.mean()) if deg.size else 0.0,
        "sigma_deg": float(deg.std()) if deg.size else 0.0,
    }
