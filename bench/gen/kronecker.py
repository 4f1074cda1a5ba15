"""Graph500 Kronecker generator (the spec's kernel-1 input).

A copy of the chunked recursive-matrix draw of the program's
``repro.data.graphs._rmat_edges``, kept with the benchmark so that a
change to the program cannot change the yardstick.  ``2**scale`` nodes,
``edgefactor * 2**scale`` endpoint pairs drawn bit by bit with the
initiator ``A, B, C`` (``D = 1 - A - B - C``), made undirected by
:func:`bench.edges.pairs`, weighed, then labelled by a permutation of
the nodes.

Which pairs are joined depends on the configuration alone, so it is
drawn once and kept under ``bench/.cache/`` (git-ignored): every later
run of the configuration in the checkout loads it and only labels and
weighs it.
"""

from __future__ import annotations

import hashlib
import json
import os
import tempfile
from pathlib import Path

import numpy as np

from bench.edges import arcs, pairs

#: endpoint pairs per draw: large enough to amortise numpy's call
#: overhead, small enough to stay cache-resident
_CHUNK = 1 << 20

#: where the drawn structures are kept, one ``.npy`` file each
CACHE = Path(__file__).resolve().parents[1] / ".cache"

#: the configuration's keys that decide which pairs are joined
_SHAPE_KEYS = ("scale", "edgefactor", "A", "B", "C", "graph_seed")


def _pairs(scale: int, edgefactor: int, a: float, b: float, c: float,
           rng: np.random.Generator) -> tuple[np.ndarray, np.ndarray]:
    n = 1 << scale
    m = n * edgefactor
    src = np.zeros(m, np.int32)
    dst = np.zeros(m, np.int32)
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    upper = np.empty(m, np.bool_)
    buf = np.empty(min(m, _CHUNK))
    for bit in range(scale):
        for lo in range(0, m, _CHUNK):
            hi = min(lo + _CHUNK, m)
            r1 = rng.random(hi - lo, out=buf[:hi - lo])
            np.greater(r1, ab, out=upper[lo:hi])
            src[lo:hi] |= upper[lo:hi].astype(np.int32) << bit
        for lo in range(0, m, _CHUNK):
            hi = min(lo + _CHUNK, m)
            r2 = rng.random(hi - lo, out=buf[:hi - lo])
            thr = np.where(upper[lo:hi], c_norm, a_norm)
            dst[lo:hi] |= (r2 > thr).astype(np.int32) << bit
    return src, dst


def structure(cfg: dict) -> np.ndarray:
    """``[2, pairs]`` int32: the distinct undirected pairs ``(lo, hi)``
    of the configuration, drawn from its ``graph_seed``, from the cache
    when an earlier run drew them."""
    shape = {k: cfg[k] for k in _SHAPE_KEYS}
    digest = hashlib.sha256(json.dumps(shape, sort_keys=True).encode())
    path = CACHE / f"kronecker-{digest.hexdigest()[:16]}.npy"
    try:
        return np.load(path)
    except FileNotFoundError:
        pass
    n = 1 << cfg["scale"]
    u, v = _pairs(cfg["scale"], cfg["edgefactor"], cfg["A"], cfg["B"],
                  cfg["C"], np.random.default_rng([cfg["graph_seed"],
                                                   0x6A500]))
    lo_hi = np.stack(pairs(u, v, n))
    CACHE.mkdir(exist_ok=True)
    # written whole under another name and then renamed, so that a run
    # never loads half a file
    with tempfile.NamedTemporaryFile(dir=CACHE, suffix=".tmp",
                                     delete=False) as f:
        np.save(f, lo_hi)
    os.replace(f.name, path)
    return lo_hi


def generate(cfg: dict, seed: int):
    """``(src, dst, wt, num_nodes, labels)`` of the configuration
    ``cfg`` for ``seed``: the same seed gives the same graph.  Which
    pairs are joined, and the weight of each, is drawn from the
    configuration's ``graph_seed``; ``seed`` draws the node labels
    (``labels[s]`` is the label of node ``s`` of the structure).  Every
    seed gives the same weighted graph, relabelled: the same traversals
    from the same structural roots do the same work, as Graph500 runs
    its search keys on one graph."""
    n = 1 << cfg["scale"]
    lo, hi = structure(cfg)
    labels = np.random.default_rng([seed, 0x6A501]).permutation(n).astype(
        np.int32)
    src, dst, wt = arcs(labels[lo], labels[hi], tuple(cfg["weights"]),
                        np.random.default_rng([cfg["graph_seed"], 0x6A502]))
    return src, dst, wt, n, labels
