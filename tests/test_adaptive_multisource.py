"""Tests for the adaptive (AD) strategy, the strategy registry, and the
batched multi-source engine."""

import numpy as np
import pytest

from repro.algos import bfs, bfs_batch, sssp, sssp_batch
from repro.core import engine, multi_source
from repro.core.graph import CSRGraph, INF
from repro.core.strategies import (StrategyBase, STRATEGIES,
                                   choose_kernel, make_strategy, register)
from repro.data import (erdos_renyi_graph, graph500_graph, rmat_graph,
                        road_grid_graph)


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


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_contains_all_strategies():
    assert set(STRATEGIES) >= {"BS", "EP", "WD", "NS", "HP", "AD"}


def test_make_strategy_unknown_name():
    with pytest.raises(KeyError, match="unknown strategy"):
        make_strategy("NOPE")


def test_register_roundtrip():
    @register(name="_TEST")
    class _Test(StrategyBase):
        name = "_TEST"

    try:
        assert isinstance(make_strategy("_TEST"), _Test)
    finally:
        del STRATEGIES["_TEST"]


def test_register_rejects_non_strategy():
    with pytest.raises(TypeError):
        register(name="_BAD")(object)


# ---------------------------------------------------------------------------
# adaptive strategy
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("gname", list(GRAPHS))
def test_adaptive_sssp_matches_dijkstra(gname):
    g = GRAPHS[gname]
    ref = engine.reference_distances(g, 0)
    res = sssp(g, 0, strategy="AD")
    np.testing.assert_array_equal(res.dist, ref)
    # every iteration recorded which kernel ran, from the AD pool
    assert all(s.kernel in {"BS", "WD", "HP"} for s in res.iter_stats)


def test_adaptive_bfs_levels():
    g = GRAPHS["rmat"]
    res = bfs(g, 0, strategy="AD")
    unweighted = CSRGraph(g.row_ptr, g.col, None, g.num_nodes, g.num_edges,
                          g.max_degree)
    ref = engine.reference_distances(unweighted, 0)
    np.testing.assert_array_equal(res.dist, ref)


def test_adaptive_switches_kernels():
    """On a skewed graph with a tight BS window the selector must actually
    use more than one kernel across the run."""
    g = GRAPHS["rmat"]
    strat = make_strategy("AD", small_frontier=8)
    res = engine.run(g, 0, strat)
    used = {s.kernel for s in res.iter_stats}
    assert len(used) >= 2
    assert sum(res.kernel_counts.values()) == res.iterations


def test_choose_kernel_decision_structure():
    # empty / tiny-uniform frontiers stay on the node-based kernel
    assert choose_kernel(0, 0, 0, 1.0, mdt=4) == "BS"
    assert choose_kernel(10, 30, 4, 1.5, mdt=4) == "BS"
    # small but heavily skewed frontier → WD
    assert choose_kernel(10, 5000, 4000, 100.0, mdt=4) == "WD"
    # huge skewed frontier beyond MDT and the edge threshold → HP
    assert choose_kernel(100_000, 1 << 20, 5000, 50.0, mdt=64) == "HP"
    # large frontier under the HP edge threshold → WD
    assert choose_kernel(100_000, 1 << 10, 5000, 50.0, mdt=64) == "WD"


def test_adaptive_edges_counted():
    g = GRAPHS["er"]
    source = int(np.argmax(np.asarray(g.degrees)))   # giant component
    res = sssp(g, source, strategy="AD")
    assert res.edges_relaxed > 0
    assert res.mteps >= 0


# ---------------------------------------------------------------------------
# batched multi-source engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("gname", ["rmat", "road", "er"])
def test_run_batch_matches_independent_runs(gname):
    g = GRAPHS[gname]
    sources = [0, 3, 17, 42]
    bres = sssp_batch(g, sources)
    assert bres.dist.shape == (len(sources), g.num_nodes)
    for i, s in enumerate(sources):
        single = engine.run(g, s, make_strategy("WD"))
        np.testing.assert_array_equal(bres.dist[i], single.dist)


def test_run_batch_bfs_matches_reference():
    g = GRAPHS["road"]
    unweighted = CSRGraph(g.row_ptr, g.col, None, g.num_nodes, g.num_edges,
                          g.max_degree)
    sources = [0, 100, 250]
    bres = bfs_batch(g, sources)
    for i, s in enumerate(sources):
        ref = engine.reference_distances(unweighted, s)
        np.testing.assert_array_equal(bres.dist[i], ref)


def test_run_batch_duplicate_and_disconnected_sources():
    src = np.array([0, 1]); dst = np.array([1, 0]); wt = np.array([1, 1])
    g = CSRGraph.from_edges(src, dst, wt, 4)   # nodes 2,3 disconnected
    bres = engine.run_batch(g, [0, 0, 2])
    np.testing.assert_array_equal(bres.dist[0], bres.dist[1])
    assert bres.dist[0, 1] == 1
    # source 2 has no outgoing edges: only itself is reached
    assert bres.dist[2, 2] == 0
    assert (np.delete(bres.dist[2], 2) == INF).all()


def test_run_batch_empty_batch_and_empty_graph():
    g = GRAPHS["road"]
    empty = engine.run_batch(g, [])
    assert empty.dist.shape == (0, g.num_nodes)
    assert empty.iterations == 0

    g0 = CSRGraph.from_edges(np.array([], np.int64), np.array([], np.int64),
                             None, 3)
    bres = engine.run_batch(g0, [1])
    assert bres.dist[0, 1] == 0
    assert bres.dist[0, 0] == INF


def test_refill_slot_preserves_other_rows():
    g = GRAPHS["road"]
    import jax.numpy as jnp
    dist_b, mask_b = multi_source.init_batch(
        g.num_nodes, jnp.asarray(np.array([0, 5], np.int32)))
    dist2, mask2 = multi_source.refill_slot(dist_b, mask_b,
                                            np.int32(1), np.int32(9))
    np.testing.assert_array_equal(np.asarray(dist2[0]),
                                  np.asarray(dist_b[0]))
    assert int(np.asarray(dist2[1])[9]) == 0
    assert np.asarray(mask2[1]).sum() == 1


def test_batch_result_throughput_fields():
    g = GRAPHS["er"]
    bres = sssp_batch(g, [0, 1])
    assert bres.iterations > 0
    assert bres.edges_relaxed > 0
    assert bres.queries_per_second > 0
    assert bres.mteps > 0
