"""Undirected edge lists for the benchmark's graphs.

The configurations state undirected graphs (Graph500's spec; road
networks).  :func:`pairs` reduces a list of endpoint pairs to the
distinct unordered pairs, self-loops dropped; :func:`arcs` turns those
into the directed arc list that the program's CSR takes, one weight per
pair and both directions emitted with that weight, so the CSR is
exactly symmetric.
"""

from __future__ import annotations

import numpy as np


def pairs(u: np.ndarray, v: np.ndarray,
          num_nodes: int) -> tuple[np.ndarray, np.ndarray]:
    """``(lo, hi)`` int32: every distinct unordered pair ``{u[i], v[i]}``
    with ``u[i] != v[i]`` once, ``lo < hi``, in ascending order."""
    u = np.asarray(u, np.int64)
    v = np.asarray(v, np.int64)
    keep = u != v
    lo = np.minimum(u[keep], v[keep])
    hi = np.maximum(u[keep], v[keep])
    key = np.unique(lo * num_nodes + hi)
    return ((key // num_nodes).astype(np.int32),
            (key % num_nodes).astype(np.int32))


def arcs(lo: np.ndarray, hi: np.ndarray, weights: tuple[int, int],
         rng: np.random.Generator):
    """``(src, dst, wt)`` int32 arcs: the pairs ``lo[i] -> hi[i]``, then
    their reverses.  Weights are drawn uniformly from
    ``weights[0]..weights[1]`` (inclusive), one per pair, in pair
    order."""
    w = rng.integers(weights[0], weights[1] + 1, size=len(lo)).astype(
        np.int32)
    return (np.concatenate([lo, hi]), np.concatenate([hi, lo]),
            np.concatenate([w, w]))
