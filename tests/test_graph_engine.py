"""System tests for the paper's core: the five load-balancing strategies
must all compute identical BFS/SSSP results, across graph families."""

import numpy as np
import pytest

from repro.algos import bfs, sssp, connected_components
from repro.core import engine
from repro.core.graph import CSRGraph, INF
from repro.data import (erdos_renyi_graph, graph500_graph, rmat_graph,
                        road_grid_graph)

STRATEGIES = ["BS", "EP", "WD", "NS", "HP"]


def graphs():
    return {
        "rmat": rmat_graph(scale=9, edge_factor=8, weighted=True, seed=7),
        "road": road_grid_graph(side=24, weighted=True, seed=7),
        "er": erdos_renyi_graph(scale=9, edge_factor=4, weighted=True,
                                seed=7),
        "g500": graph500_graph(scale=9, edge_factor=12, weighted=True,
                               seed=7),
    }


GRAPHS = graphs()


@pytest.mark.parametrize("gname", list(GRAPHS))
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_sssp_matches_dijkstra(gname, strategy):
    g = GRAPHS[gname]
    ref = engine.reference_distances(g, 0)
    res = sssp(g, 0, strategy=strategy)
    np.testing.assert_array_equal(res.dist, ref)


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_bfs_levels(strategy):
    g = GRAPHS["rmat"]
    res = bfs(g, 0, strategy=strategy)
    unweighted = CSRGraph(g.row_ptr, g.col, None, g.num_nodes, g.num_edges,
                          g.max_degree)
    ref = engine.reference_distances(unweighted, 0)
    np.testing.assert_array_equal(res.dist, ref)


def test_bfs_is_levels_not_weights():
    g = GRAPHS["road"]
    res = bfs(g, 0, strategy="WD")
    reach = res.dist < INF
    assert reach.sum() > 1
    # levels grow by at most 1 along any edge of the grid
    assert res.dist[reach].max() < g.num_nodes


@pytest.mark.parametrize("strategy", ["BS", "WD", "NS", "HP"])
def test_connected_components_agree(strategy):
    g = GRAPHS["road"]
    labels = connected_components(g, strategy=strategy)
    ref = connected_components(g, strategy="WD")
    np.testing.assert_array_equal(labels, ref)


def test_ep_memory_wall():
    """EP must refuse graphs whose COO exceeds the budget (paper §IV)."""
    g = GRAPHS["g500"]
    strat = engine.make_strategy("EP", memory_budget_bytes=1000)
    with pytest.raises(MemoryError):
        engine.run(g, 0, strat)


def test_ep_unchunked_matches_chunked():
    g = GRAPHS["rmat"]
    ref = engine.reference_distances(g, 0)
    res = sssp(g, 0, strategy="EP", chunked=False)
    np.testing.assert_array_equal(res.dist, ref)
    res2 = sssp(g, 0, strategy="EP", chunked=True)
    np.testing.assert_array_equal(res2.dist, ref)
    # unchunked pushes redundant copies -> strictly more worklist traffic
    assert res.edges_relaxed >= res2.edges_relaxed


def test_disconnected_source():
    src = np.array([0, 1]); dst = np.array([1, 0]); wt = np.array([1, 1])
    g = CSRGraph.from_edges(src, dst, wt, 4)   # nodes 2,3 disconnected
    for s in STRATEGIES:
        res = sssp(g, 0, strategy=s)
        assert res.dist[1] == 1
        assert res.dist[2] == INF and res.dist[3] == INF


def test_single_node_graph():
    g = CSRGraph.from_edges(np.array([], np.int64), np.array([], np.int64),
                            np.array([], np.int64), 1)
    for s in ["BS", "WD", "HP"]:
        res = sssp(CSRGraph(g.row_ptr, g.col,
                            np.zeros(0, np.int32) if g.wt is None else g.wt,
                            1, 0, 0), 0, strategy=s)
        assert res.dist[0] == 0


# ---------------------------------------------------------------------------
# the packed edge word: (w << b) | dst in one int32, b = bits of a head
# ---------------------------------------------------------------------------

#: (nodes, largest weight or None, packs): the weights fit beside a head
#: of b = max(1, (nodes - 1).bit_length()) bits while below 2**(31 - b)
PACKING = [
    (512, 2 ** 22 - 1, True),        # b = 9: the largest weight that fits
    (512, 2 ** 22, False),           # one past it
    (512, -1, False),                # a negative weight
    (512, None, False),              # unweighted
    (1024, 2 ** 21 - 1, True),       # nodes a power of two: b = 10
    (1024, 2 ** 21, False),
    (1, 2 ** 30 - 1, True),          # one node: b = 1
    (1, 2 ** 30, False),
]


@pytest.mark.parametrize("n,w_top,packs", PACKING)
def test_packed_edge_word_layout(n, w_top, packs):
    rng = np.random.default_rng(n)
    e = 40
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    dst[0] = n - 1                   # the largest head
    wt = None
    if w_top is not None:
        wt = rng.integers(0, 100, e)
        wt[e // 2] = w_top
    g = CSRGraph.from_edges(src, dst, wt, n)
    b = max(1, (n - 1).bit_length())
    assert g.wt_shift == (b if packs else None)
    assert g.weighted == (wt is not None)
    order = np.argsort(src, kind="stable")
    np.testing.assert_array_equal(np.asarray(g.col), dst[order])
    if wt is None:
        assert g.wt is None and g.wt_word is None
    else:
        got = np.asarray(g.wt)
        assert got.dtype == np.int32
        np.testing.assert_array_equal(got, wt[order])
    if packs:
        np.testing.assert_array_equal(
            np.asarray(g.wt_word) & ((1 << b) - 1), np.asarray(g.col))
    assert g.device_bytes() == 4 * (n + 1 + e * (1 + g.weighted))
    plain = g.plain()
    assert plain.wt_shift is None and (plain is g) == (not packs)
    assert plain.device_bytes() == g.device_bytes()
    if w_top is not None and w_top >= 0:
        # the top weight decodes exactly inside the fused loop too
        res = sssp(g, int(src[e // 2]), strategy="WD", mode="fused")
        np.testing.assert_array_equal(
            res.dist, engine.reference_distances(plain, int(src[e // 2])))
