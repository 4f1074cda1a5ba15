"""Synthetic graph generators — numpy reimplementations of the tools the
paper uses (GTgraph RMAT / Erdős–Rényi, Graph500 Kronecker, USA-road-like
grids), scaled by a ``scale`` knob so the benchmark suite runs on CPU.

Every generator is deterministic given ``seed``.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from repro.core.graph import CSRGraph


def _finish(src, dst, num_nodes, weighted, seed, dedup=True) -> CSRGraph:
    # drop self-loops
    keep = src != dst
    src, dst = src[keep], dst[keep]
    wt = None
    if weighted:
        rng = np.random.default_rng(seed + 0x9E3779B9)
        wt = rng.integers(1, 101, size=len(src)).astype(np.int32)
    return CSRGraph.from_edges(src, dst, wt, num_nodes, dedup=dedup)


#: edges per draw of the chunked generator below: large enough to
#: amortise numpy call overhead, small enough to stay cache-resident
_GEN_CHUNK = 1 << 20


def _rmat_edges(scale: int, edge_factor: int, a: float, b: float, c: float,
                seed: int) -> tuple[np.ndarray, np.ndarray]:
    """Recursive-matrix edge generation (Chakrabarti et al.), vectorized.

    Bit by bit, one uniform draw per edge picks the source half and a
    second the destination half.  The draws are made in cache-sized
    chunks in the same order as one ``rng.random(m)`` per pass, so the
    graph for a seed does not depend on the chunk size."""
    n = 1 << scale
    m = n * edge_factor
    rng = np.random.default_rng(seed)
    src = np.zeros(m, np.int32)
    dst = np.zeros(m, np.int32)
    ab = a + b
    c_norm = c / (1.0 - ab) if ab < 1.0 else 0.0
    a_norm = a / ab if ab > 0 else 0.0
    upper = np.empty(m, np.bool_)      # this bit's source is in the upper half
    buf = np.empty(min(m, _GEN_CHUNK))
    for bit in range(scale):
        for lo in range(0, m, _GEN_CHUNK):
            hi = min(lo + _GEN_CHUNK, m)
            r1 = rng.random(hi - lo, out=buf[:hi - lo])
            np.greater(r1, ab, out=upper[lo:hi])
            src[lo:hi] |= upper[lo:hi].astype(np.int32) << bit
        for lo in range(0, m, _GEN_CHUNK):
            hi = min(lo + _GEN_CHUNK, m)
            r2 = rng.random(hi - lo, out=buf[:hi - lo])
            thr = np.where(upper[lo:hi], c_norm, a_norm)
            dst[lo:hi] |= (r2 > thr).astype(np.int32) << bit
    # permute vertex labels so degree doesn't correlate with id
    perm = rng.permutation(n)
    return perm[src], perm[dst]


def rmat_graph(scale: int = 14, edge_factor: int = 8, *,
               weighted: bool = False, seed: int = 1) -> CSRGraph:
    """RMAT graph (paper: rmat20, edge_factor 8, skewed power-law)."""
    src, dst = _rmat_edges(scale, edge_factor, 0.45, 0.22, 0.22, seed)
    return _finish(src, dst, 1 << scale, weighted, seed)


def graph500_graph(scale: int = 16, edge_factor: int = 16, *,
                   weighted: bool = False, seed: int = 2) -> CSRGraph:
    """Graph500 Kronecker parameters (A=.57,B=.19,C=.19) — the paper's
    'large graph' family with extreme degree skew (max deg ~1e6-scale)."""
    src, dst = _rmat_edges(scale, edge_factor, 0.57, 0.19, 0.19, seed)
    return _finish(src, dst, 1 << scale, weighted, seed)


def erdos_renyi_graph(scale: int = 14, edge_factor: int = 4, *,
                      weighted: bool = False, seed: int = 3) -> CSRGraph:
    """Erdős–Rényi G(n, m): uniform random edges (paper's ER20/ER23)."""
    n = 1 << scale
    m = n * edge_factor
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, size=m)
    dst = rng.integers(0, n, size=m)
    return _finish(src, dst, n, weighted, seed)


def road_grid_graph(side: int = 128, *, weighted: bool = False,
                    seed: int = 4, diag_frac: float = 0.05) -> CSRGraph:
    """Road-network stand-in: 2-D grid (large diameter, max degree ≤ 8,
    tiny variance) with a few diagonal shortcuts — matches the USA-road
    degree profile in Table II (max 9, avg ~3, sigma ~2.7)."""
    n = side * side
    ii, jj = np.meshgrid(np.arange(side), np.arange(side), indexing="ij")
    ids = (ii * side + jj).ravel()
    edges = []
    for di, dj in ((0, 1), (1, 0), (0, -1), (-1, 0)):
        ni, nj = ii + di, jj + dj
        ok = (ni >= 0) & (ni < side) & (nj >= 0) & (nj < side)
        edges.append((ids[ok.ravel()], (ni * side + nj).ravel()[ok.ravel()]))
    rng = np.random.default_rng(seed)
    k = int(n * diag_frac)
    extra_s = rng.integers(0, n, size=k)
    extra_d = np.clip(extra_s + rng.integers(1, side, size=k), 0, n - 1)
    edges.append((extra_s, extra_d))
    edges.append((extra_d, extra_s))
    src = np.concatenate([e[0] for e in edges])
    dst = np.concatenate([e[1] for e in edges])
    return _finish(src, dst, n, weighted, seed)


# Benchmark suite mirroring Table II (scaled to CPU budgets).  Names match
# the paper's; `scale` values are reduced but preserve the *shape* of each
# distribution (skew / diameter class), which is what the strategies react to.
GRAPH_SUITE = {
    # paper: rmat20 (1.05M nodes, 8.26M edges, maxdeg 1181)
    "rmat": dict(kind="rmat", scale=14, edge_factor=8),
    # paper: road-FLA/W/USA (maxdeg 9, avg 3)
    "road": dict(kind="road", side=160),
    # paper: ER20/ER23 (maxdeg 10-15, avg 3-4)
    "er": dict(kind="er", scale=14, edge_factor=4),
    # paper: Graph500 (16.78M nodes, 335M edges, maxdeg 924k) — 3 seeds
    "graph500_a": dict(kind="graph500", scale=15, edge_factor=16, seed=11),
    "graph500_b": dict(kind="graph500", scale=15, edge_factor=16, seed=12),
    "graph500_c": dict(kind="graph500", scale=15, edge_factor=16, seed=13),
}


def make_graph(name: str, *, weighted: bool = False,
               scale_override: Optional[int] = None) -> CSRGraph:
    spec = dict(GRAPH_SUITE[name])
    kind = spec.pop("kind")
    if scale_override is not None and "scale" in spec:
        spec["scale"] = scale_override
    if kind == "rmat":
        return rmat_graph(weighted=weighted, **spec)
    if kind == "graph500":
        return graph500_graph(weighted=weighted, **spec)
    if kind == "er":
        return erdos_renyi_graph(weighted=weighted, **spec)
    if kind == "road":
        return road_grid_graph(weighted=weighted, **spec)
    raise ValueError(f"unknown graph kind {kind!r}")
