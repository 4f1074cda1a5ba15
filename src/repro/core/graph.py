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


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CSRGraph:
    """CSR graph.  ``row_ptr[n] : row_ptr[n+1]`` index into ``col``/``wt``."""

    row_ptr: jax.Array       # [N+1] int32
    col: jax.Array           # [E]   int32 — destination node ids
    wt: Optional[jax.Array]  # [E]   int32 edge weights (None for BFS inputs)
    num_nodes: int           # static
    num_edges: int           # static
    max_degree: int          # static — used for BS padding bounds

    # -- pytree protocol -------------------------------------------------
    def tree_flatten(self):
        return (self.row_ptr, self.col, self.wt), (
            self.num_nodes, self.num_edges, self.max_degree)

    @classmethod
    def tree_unflatten(cls, aux, children):
        row_ptr, col, wt = children
        return cls(row_ptr, col, wt, *aux)

    # -- helpers ----------------------------------------------------------
    @property
    def degrees(self) -> jax.Array:
        return self.row_ptr[1:] - self.row_ptr[:-1]

    def device_bytes(self) -> int:
        return _field_bytes(self.row_ptr, self.col, self.wt)

    def out_degree(self, nodes: jax.Array) -> jax.Array:
        return self.row_ptr[nodes + 1] - self.row_ptr[nodes]

    def weight_or_one(self) -> jax.Array:
        if self.wt is not None:
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
        """Build (host-side, numpy) a CSR graph from an edge list."""
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
        return cls(
            row_ptr=jnp.asarray(row_ptr, jnp.int32),
            col=jnp.asarray(dst, jnp.int32),
            wt=None if wt is None else jnp.asarray(wt, jnp.int32),
            num_nodes=int(num_nodes),
            num_edges=int(len(dst)),
            max_degree=max_degree,
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
