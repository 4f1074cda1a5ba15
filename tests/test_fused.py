"""Tests for the fused single-dispatch engine (``mode="fused"``).

The contract: for every registered strategy, a fused traversal is
bit-identical to the stepped one — same distances, same iteration count,
same relaxed-edge total — while issuing exactly one jit dispatch for the
whole traversal (and recompiling nothing when shapes repeat).
"""

import numpy as np
import pytest

from repro.algos import bfs, sssp_batch
from repro.core import engine, fused
from repro.core.graph import CSRGraph, INF
from repro.data import (erdos_renyi_graph, graph500_graph, rmat_graph,
                        road_grid_graph)

STRATEGIES = ["BS", "EP", "WD", "NS", "HP", "AD"]


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


def _run_pair(g, strategy, source=0):
    stepped = engine.run(g, source, engine.make_strategy(strategy))
    fusedr = engine.run(g, source, engine.make_strategy(strategy),
                        mode="fused")
    return stepped, fusedr


# ---------------------------------------------------------------------------
# fused ≡ stepped on the graph zoo
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("gname", list(GRAPHS))
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_fused_matches_stepped(gname, strategy):
    g = GRAPHS[gname]
    stepped, fusedr = _run_pair(g, strategy)
    np.testing.assert_array_equal(fusedr.dist, stepped.dist)
    assert fusedr.iterations == stepped.iterations
    assert fusedr.edges_relaxed == stepped.edges_relaxed
    assert stepped.mode == "stepped" and fusedr.mode == "fused"


@pytest.mark.parametrize("strategy", ["BS", "WD", "AD"])
def test_fused_bfs_matches_reference(strategy):
    g = GRAPHS["rmat"]
    unweighted = CSRGraph(g.row_ptr, g.col, None, g.num_nodes, g.num_edges,
                          g.max_degree)
    ref = engine.reference_distances(unweighted, 0)
    res = bfs(g, 0, strategy=strategy, mode="fused")
    np.testing.assert_array_equal(res.dist, ref)


def test_fused_empty_graph():
    g = CSRGraph.from_edges(np.array([], np.int64), np.array([], np.int64),
                            None, 3)
    for mode in ("stepped", "fused"):
        res = engine.run(g, 1, engine.make_strategy("WD"), mode=mode)
        assert res.dist[1] == 0 and res.iterations == 0
        assert (np.delete(res.dist, 1) == INF).all()


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_fused_unreachable_and_edgeless_source(strategy):
    """Node 2 has no outgoing edges; nodes 2,3 are unreachable from 0."""
    src = np.array([0, 1])
    dst = np.array([1, 0])
    wt = np.array([1, 1])
    g = CSRGraph.from_edges(src, dst, wt, 4)
    for source in (0, 2):      # reachable pair / edgeless source
        stepped, fusedr = _run_pair(g, strategy, source=source)
        np.testing.assert_array_equal(fusedr.dist, stepped.dist)
        assert fusedr.iterations == stepped.iterations
        assert fusedr.edges_relaxed == stepped.edges_relaxed


# ---------------------------------------------------------------------------
# single-dispatch claim
# ---------------------------------------------------------------------------

def test_one_dispatch_per_traversal_no_recompile():
    g = GRAPHS["rmat"]
    # warm-up: pay the one compilation for this (kernel, shape) pair
    engine.run(g, 0, engine.make_strategy("WD"), mode="fused")
    d0 = fused.DISPATCH_COUNTS["WD"]
    t0 = fused.TRACE_COUNTS["WD"]
    res = engine.run(g, 0, engine.make_strategy("WD"), mode="fused")
    assert res.iterations > 1                       # many frontier rounds…
    assert fused.DISPATCH_COUNTS["WD"] == d0 + 1    # …one device dispatch
    assert fused.TRACE_COUNTS["WD"] == t0           # …zero recompiles


def test_fused_ad_reports_kernel_schedule():
    g = GRAPHS["rmat"]
    strat = engine.make_strategy("AD", small_frontier=8)
    res = engine.run(g, 0, strat, mode="fused")
    assert sum(strat.kernel_counts.values()) == res.iterations
    assert set(strat.kernel_counts) <= {"BS", "WD", "HP"}
    # a tight BS window on a skewed graph must exercise ≥ 2 kernels
    assert len(strat.kernel_counts) >= 2


def test_fused_mode_validation():
    g = GRAPHS["road"]
    with pytest.raises(ValueError, match="mode"):
        engine.run(g, 0, engine.make_strategy("WD"), mode="warp")
    with pytest.raises(ValueError, match="stepped"):
        engine.run(g, 0, engine.make_strategy("WD"), mode="fused",
                   record_degrees=True)
    with pytest.raises(ValueError, match="fused lowering"):
        fused.run_fixed_point(g, g, engine.StrategyBase(), None, None)
    # unchunked EP's duplicate-push worklist has no dense equivalent —
    # silently fusing it would measure the chunked algorithm instead
    strat = engine.make_strategy("EP", chunked=False)
    with pytest.raises(ValueError, match="chunked"):
        engine.run(GRAPHS["rmat"], 0, strat, mode="fused")


# ---------------------------------------------------------------------------
# batched multi-source fused loop
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("gname", ["rmat", "road"])
def test_batch_fused_matches_stepped(gname):
    g = GRAPHS[gname]
    sources = [0, 3, 17, 42]
    stepped = sssp_batch(g, sources)
    fusedb = sssp_batch(g, sources, mode="fused")
    np.testing.assert_array_equal(fusedb.dist, stepped.dist)
    assert fusedb.iterations == stepped.iterations
    assert fusedb.edges_relaxed == stepped.edges_relaxed
    # and both equal K independent single-source runs
    for i, s in enumerate(sources):
        single = engine.run(g, s, engine.make_strategy("WD"))
        np.testing.assert_array_equal(fusedb.dist[i], single.dist)


def test_batch_fused_single_dispatch():
    g = GRAPHS["road"]
    engine.run_batch(g, [0, 5], mode="fused")       # warm-up
    d0 = fused.DISPATCH_COUNTS["batch"]
    t0 = fused.TRACE_COUNTS["batch"]
    res = engine.run_batch(g, [0, 5], mode="fused")
    assert res.iterations > 1
    assert fused.DISPATCH_COUNTS["batch"] == d0 + 1
    assert fused.TRACE_COUNTS["batch"] == t0


def test_batch_mode_validation():
    g = GRAPHS["road"]
    with pytest.raises(ValueError, match="mode"):
        engine.run_batch(g, [0], mode="warp")


# ---------------------------------------------------------------------------
# RunResult timing split (mteps excludes one-off setup)
# ---------------------------------------------------------------------------

def test_mteps_excludes_setup():
    res = engine.RunResult(
        dist=np.zeros(1, np.int32), iterations=1, total_seconds=3.0,
        setup_seconds=1.0, kernel_seconds=1.5, overhead_seconds=1.5,
        edges_relaxed=4_000_000, iter_stats=[], strategy="WD",
        state_bytes=0)
    assert res.traversal_seconds == 2.0
    assert res.mteps == pytest.approx(2.0)
    assert res.mteps_with_setup == pytest.approx(4.0 / 3.0)


def test_mteps_zero_time_guard():
    res = engine.RunResult(
        dist=np.zeros(1, np.int32), iterations=0, total_seconds=0.0,
        setup_seconds=0.0, kernel_seconds=0.0, overhead_seconds=0.0,
        edges_relaxed=0, iter_stats=[], strategy="WD", state_bytes=0)
    assert res.mteps == 0.0 and res.mteps_with_setup == 0.0


@pytest.mark.parametrize("strategy", ["BS", "WD", "HP", "NS", "AD"])
def test_fused_multi_block_batches_match_stepped(monkeypatch, strategy):
    """Batches wider than the largest lane block run as a loop of blocks
    reading a snapshot of the values at the batch's start; shrinking the
    block sizes forces that path (and every switch branch) on a small
    graph, which must stay bit-identical to the stepped driver."""
    import jax

    g = GRAPHS["g500"]
    hub = int(np.argmax(np.diff(np.asarray(g.row_ptr))))
    stepped = engine.run(g, hub, engine.make_strategy(strategy))
    assert stepped.edges_relaxed > 64       # some batch needs the loop
    monkeypatch.setattr(fused, "_BLOCK_SIZES", (4, 16, 64))
    jax.clear_caches()
    try:
        fusedr = engine.run(g, hub, engine.make_strategy(strategy),
                            mode="fused")
    finally:
        jax.clear_caches()
    np.testing.assert_array_equal(fusedr.dist, stepped.dist)
    assert fusedr.iterations == stepped.iterations
    assert fusedr.edges_relaxed == stepped.edges_relaxed
