"""The plain reference: scipy's Dijkstra over the benchmark's own arcs.

A copy of ``chip_smoke.py``'s oracle, which shares no code with the
engine: it reads the arcs the benchmark generated, never the program's
CSR, and imports nothing of the program.  Distances are exact int32,
with :data:`UNREACHED` where a node is not reachable (the engine's
documented sentinel, ``int32 max // 2``).
"""

from __future__ import annotations

from typing import Optional

import numpy as np

#: distance of a node the source does not reach
UNREACHED = np.iinfo(np.int32).max // 2


class Reference:
    """Single-source distances over the arcs ``src -> dst`` (weights
    ``wt``, or hop counts when ``wt`` is None) of an ``n``-node graph."""

    def __init__(self, src: np.ndarray, dst: np.ndarray,
                 wt: Optional[np.ndarray], n: int):
        from scipy.sparse import csr_matrix
        self.weighted = wt is not None
        w = wt if self.weighted else np.ones(len(src), np.int32)
        self.matrix = csr_matrix(
            (np.asarray(w, np.float64), (np.asarray(src), np.asarray(dst))),
            shape=(n, n))

    def distances(self, source: int) -> np.ndarray:
        """``[n]`` int32 distances from ``source``."""
        from scipy.sparse.csgraph import dijkstra
        d = dijkstra(self.matrix, directed=True, indices=int(source),
                     unweighted=not self.weighted)
        out = np.full(d.shape, UNREACHED, np.int64)
        reach = np.isfinite(d)
        out[reach] = d[reach]
        return out.astype(np.int32)
