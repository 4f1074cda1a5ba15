"""The TEPS edge count and the least-bytes model, on hand-built graphs."""

from types import SimpleNamespace

import numpy as np

from bench import harness, reference
from bench.metrics import mteps, relax_per_edge, traversal_roofline

# arcs of a small weighted graph in which SSSP improves node 1 twice:
# 0 -> 1 costs 10 directly, 4 via 2 and 2 via 2 -> 3; node 5 is unreached
SRC = np.array([0, 0, 0, 2, 2, 3, 1, 1, 4], np.int32)
DST = np.array([1, 2, 3, 1, 3, 1, 4, 0, 1], np.int32)
WT = np.array([10, 1, 3, 3, 1, 1, 1, 1, 1], np.int32)
N = 6


def _query(edges, reached=1, relaxed=0, iterations=1, ms=1.0):
    return harness.Query(root=0, ms=ms, reached=reached, edges=edges,
                         relaxed=relaxed, iterations=iterations)


def test_reached_edges_counts_graph_edges():
    degrees = np.bincount(SRC, minlength=N)
    dist = reference.Reference(SRC, DST, WT, N).distances(0)
    assert dist[5] == reference.UNREACHED
    assert harness.reached_edges(dist, degrees) == (5, len(SRC))


def test_mteps_count_is_not_edges_relaxed():
    """SSSP re-relaxes node 1's arcs as its distance improves; the TEPS
    numerator counts each arc of the reached set once."""
    from repro.algos import sssp
    from repro.core.graph import CSRGraph
    g = CSRGraph.from_edges(SRC, DST, WT, N)
    r = sssp(g, 0, strategy="AD", mode="fused")
    dist = np.asarray(r.dist)
    np.testing.assert_array_equal(
        dist, reference.Reference(SRC, DST, WT, N).distances(0))
    _, edges = harness.reached_edges(dist, np.bincount(SRC, minlength=N))
    assert edges == len(SRC)
    assert r.edges_relaxed > edges


def test_mteps_and_relax_per_edge_readers():
    qs = [_query(3_000_000, relaxed=6_000_000), _query(1_000_000,
                                                       relaxed=1_000_000)]
    run = SimpleNamespace(queries=qs, window_s=2.0)
    assert mteps.read(run) == 2.0
    assert relax_per_edge.read(run) == 7 / 4
    assert mteps.read(SimpleNamespace(queries=[], window_s=1.0)) is None


def test_min_bytes_model():
    # per reached node: row_ptr + dist; per edge: col (+ wt) + head dist
    assert traversal_roofline.min_bytes(3, 5, weighted=True) == 3 * 8 + 5 * 12
    assert traversal_roofline.min_bytes(3, 5, weighted=False) == 3 * 8 + 5 * 8


def test_roofline_reader():
    run = SimpleNamespace(
        traffic={"weighted": True}, traced=[_query(10, reached=5)],
        trace={"busy_s": 1e-9 * 160}, peaks={"hbm_bytes_per_s": 1e9})
    # 5 * 8 + 10 * 12 = 160 bytes at 1 GB/s is 160 ns: all of the busy time
    assert abs(traversal_roofline.read(run) - 100.0) < 1e-9
    run.trace = None
    assert traversal_roofline.read(run) is None
