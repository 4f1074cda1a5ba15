"""Road-network stand-in: a square grid with a few shortcuts.

A copy of the program's ``repro.data.graphs.road_grid_graph`` layout,
kept with the benchmark and made undirected: each node is joined to its
right and lower neighbour, and ``diag_frac * side**2`` shortcuts join a
node to one up to ``side - 1`` ids further on.  The result has a mean
degree near 4, a maximum near 10 and a hop diameter near ``side``, the
shape of the DIMACS USA-road graphs.
"""

from __future__ import annotations

import numpy as np

from bench.edges import arcs, pairs


#: the symmetries of the square that a seed may pick: the four that keep
#: rows as rows.  A transposed labelling changes the device time of an
#: iteration (0.7% on a v5e, with the same relaxations), so it would
#: make the seed change the time of the same work.
SYMMETRIES = 4


def grid_labels(side: int, sym: int) -> np.ndarray:
    """``[side**2]`` int32: the label of each grid node (row-major id)
    under the symmetry ``sym`` (0..7) of the square: bit 0 mirrors the
    columns, bit 1 the rows, bit 2 transposes.  Ids stay in grid order,
    as a road network's ids follow its geography."""
    r, c = np.divmod(np.arange(side * side, dtype=np.int64), side)
    if sym & 1:
        c = side - 1 - c
    if sym & 2:
        r = side - 1 - r
    if sym & 4:
        r, c = c, r
    return (r * side + c).astype(np.int32)


def generate(cfg: dict, seed: int):
    """``(src, dst, wt, num_nodes, labels)`` of the configuration
    ``cfg`` for ``seed``: the same seed gives the same graph.  Which
    pairs are joined, and the weight of each, is drawn from the
    configuration's ``graph_seed``; ``seed`` picks one of the
    :data:`SYMMETRIES` mirror images of the grid, which labels the
    nodes (``labels[s]`` is the label of node ``s`` of the
    structure).  Every seed gives the
    same weighted graph, relabelled: the same traversals from the same
    structural roots do the same work."""
    side = cfg["side"]
    n = side * side
    ids = np.arange(n, dtype=np.int64).reshape(side, side)
    right = (ids[:, :-1].ravel(), ids[:, 1:].ravel())
    down = (ids[:-1, :].ravel(), ids[1:, :].ravel())
    shape_rng = np.random.default_rng([cfg["graph_seed"], 0x60AD])
    k = int(n * cfg["diag_frac"])
    s = shape_rng.integers(0, n, size=k)
    d = np.clip(s + shape_rng.integers(1, side, size=k), 0, n - 1)
    lo, hi = pairs(np.concatenate([right[0], down[0], s]),
                   np.concatenate([right[1], down[1], d]), n)
    sym = int(np.random.default_rng([seed, 0x60AE]).integers(SYMMETRIES))
    labels = grid_labels(side, sym)
    src, dst, wt = arcs(labels[lo], labels[hi], tuple(cfg["weights"]),
                        np.random.default_rng([cfg["graph_seed"], 0x60AF]))
    return src, dst, wt, n, labels
