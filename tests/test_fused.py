"""Tests for the fused single-dispatch engine (``mode="fused"``).

The contract: for every registered strategy, a fused traversal is
bit-identical to the stepped one — same distances, same iteration count,
same relaxed-edge total — while issuing exactly one jit dispatch for the
whole traversal (and recompiling nothing when shapes repeat).
"""

import re

import numpy as np
import pytest

from repro.algos import bfs, sssp_batch
from repro.core import engine, fused, strategies
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


def _run_pair(g, strategy, source=0, **kw):
    stepped = engine.run(g, source, engine.make_strategy(strategy), **kw)
    fusedr = engine.run(g, source, engine.make_strategy(strategy),
                        mode="fused", **kw)
    return stepped, fusedr


def numbers(r):
    """Everything a traversal reports that the edge layout must not
    move."""
    return (np.asarray(r.dist).tolist(), r.iterations, r.edges_relaxed,
            r.relax_batches, r.lanes_run)


# ---------------------------------------------------------------------------
# fused ≡ stepped on the graph zoo, packed ≡ plain edge layout
# ---------------------------------------------------------------------------

PARITY = ([(gname, "shortest_path") for gname in GRAPHS]
          + [("rmat", "widest_path"), ("road", "widest_path")])


@pytest.mark.parametrize("packed", [True, False])
@pytest.mark.parametrize("gname,op", PARITY)
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_fused_matches_stepped(gname, op, strategy, packed):
    # from_edges packs the suite's weights; plain() decodes them
    g = GRAPHS[gname] if packed else GRAPHS[gname].plain()
    assert (g.wt_shift is not None) == packed
    stepped, fusedr = _run_pair(g, strategy, op=op)
    np.testing.assert_array_equal(fusedr.dist, stepped.dist)
    assert fusedr.iterations == stepped.iterations
    assert fusedr.edges_relaxed == stepped.edges_relaxed
    assert stepped.mode == "stepped" and fusedr.mode == "fused"
    if packed:
        other = engine.run(g.plain(), 0, engine.make_strategy(strategy),
                           mode="fused", op=op)
        assert numbers(fusedr) == numbers(other)


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
    assert sum(res.kernel_counts.values()) == res.iterations
    assert set(res.kernel_counts) <= {"BS", "WD", "HP"}
    # a tight BS window on a skewed graph must exercise ≥ 2 kernels
    assert len(res.kernel_counts) >= 2


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

@pytest.mark.parametrize("packed", [True, False])
@pytest.mark.parametrize("gname", ["rmat", "road"])
def test_batch_fused_matches_stepped(gname, packed):
    g = GRAPHS[gname] if packed else GRAPHS[gname].plain()
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
    if packed:
        other = sssp_batch(g.plain(), sources, mode="fused")
        np.testing.assert_array_equal(fusedb.dist, other.dist)
        assert ((fusedb.iterations, fusedb.edges_relaxed)
                == (other.iterations, other.edges_relaxed))


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


# ---------------------------------------------------------------------------
# the loop's own counters and names (RunResult.kernel_counts /
# relax_batches / lanes_run, the jax.named_scope names, the host spans)
# ---------------------------------------------------------------------------

def _host_cost(total, cap):
    """``(blocks, lanes)`` of one relax batch of ``total`` lanes: the
    smallest block of ``_block_sizes`` that holds it, else a loop of the
    largest."""
    sizes = fused._block_sizes(cap, "xla")
    fit = [s for s in sizes if total <= s]
    if fit:
        return 1, fit[0]
    blocks = -(-total // sizes[-1])
    return blocks, blocks * sizes[-1]


def _host_recount(g, stepped, sched):
    """``(relax_batches, lanes_run)`` recounted on the host from a stepped
    run's per-iteration frontier degrees and the kernel each iteration
    ran: BS one batch per edge column, WD one merge-path batch, HP one
    batch per MDT tile while more than ``switch_threshold`` nodes have
    edges left and one for the tail (straight WD at or below it)."""
    n, e = g.num_nodes, g.num_edges
    mdt, threshold = sched.mdt or 1, sched.switch_threshold
    costs = []
    for st in stepped.iter_stats:
        deg = np.asarray(st.frontier_degrees, np.int64)
        kernel = st.kernel or stepped.strategy
        if kernel == "HP" and len(deg) <= threshold:
            kernel = "WD"
        if kernel == "BS":
            costs += [_host_cost(int((deg > d).sum()), n)
                      for d in range(int(deg.max(initial=0)))]
        elif kernel == "WD":
            costs.append(_host_cost(int(deg.sum()), e))
        else:
            cursor = 0
            while True:
                tile = np.clip(deg - cursor, 0, mdt).sum()
                costs.append(_host_cost(int(tile), e))
                cursor += mdt
                if (deg > cursor).sum() <= threshold:
                    break
            costs.append(_host_cost(int(np.maximum(deg - cursor, 0).sum()),
                                    e))
    return sum(b for b, _ in costs), sum(lanes for _, lanes in costs)


TALLIED = [("BS", {}), ("WD", {}), ("HP", {}),
           ("HP", dict(switch_threshold=4, mdt=3)),
           ("AD", dict(small_frontier=8))]


@pytest.mark.parametrize("gname", ["rmat", "road"])
@pytest.mark.parametrize("strategy,kw", TALLIED)
def test_fused_relax_tally_matches_host_recount(gname, strategy, kw):
    g = GRAPHS[gname]
    stepped = engine.run(g, 0, engine.make_strategy(strategy, **kw),
                         record_degrees=True)
    fusedr = engine.run(g, 0, engine.make_strategy(strategy, **kw),
                        mode="fused")
    assert (fusedr.relax_batches, fusedr.lanes_run) == _host_recount(
        g, stepped, fusedr.work_schedule)
    assert fusedr.edges_relaxed <= fusedr.lanes_run
    assert stepped.relax_batches is None and stepped.lanes_run is None


@pytest.mark.parametrize("strategy", ["BS", "WD", "AD"])
def test_fused_relax_tally_counts_block_loops(monkeypatch, strategy):
    """Batches past the largest block count one block per trip of the
    block loop (block sizes shrunk as in the multi-block test above)."""
    import jax

    g = GRAPHS["g500"]
    hub = int(np.argmax(np.diff(np.asarray(g.row_ptr))))
    stepped = engine.run(g, hub, engine.make_strategy(strategy),
                         record_degrees=True)
    monkeypatch.setattr(fused, "_BLOCK_SIZES", (4, 16, 64))
    jax.clear_caches()
    try:
        fusedr = engine.run(g, hub, engine.make_strategy(strategy),
                            mode="fused")
    finally:
        jax.clear_caches()
    want = _host_recount(g, stepped, fusedr.work_schedule)
    assert (fusedr.relax_batches, fusedr.lanes_run) == want
    # WD runs one batch an iteration: more blocks than iterations is a
    # batch that ran as a loop of 64-lane blocks
    assert fusedr.relax_batches > fusedr.iterations


@pytest.mark.parametrize("mode", ["stepped", "fused"])
def test_run_result_kernel_counts(mode):
    g = GRAPHS["rmat"]
    res = engine.run(g, 0, engine.make_strategy("AD", small_frontier=8),
                     mode=mode)
    assert sum(res.kernel_counts.values()) == res.iterations
    assert set(res.kernel_counts) <= {"BS", "WD", "HP"}
    assert len(res.kernel_counts) >= 2
    # the on-device selector picks what the host one picks
    other = engine.run(g, 0, engine.make_strategy("AD", small_frontier=8),
                       mode="fused" if mode == "stepped" else "stepped")
    assert other.kernel_counts == res.kernel_counts
    # a strategy that does not choose reports no choices
    assert engine.run(g, 0, engine.make_strategy("WD"),
                      mode=mode).kernel_counts == {}


def test_uncounted_paths_leave_relax_tally_unset():
    g = GRAPHS["road"]
    delta = engine.run(g, 0, engine.make_strategy("WD"), mode="fused",
                       schedule="delta")
    stepped = engine.run(g, 0, engine.make_strategy("WD"))
    for res in (delta, stepped):
        assert res.relax_batches is None and res.lanes_run is None


def test_fused_ad_lowering_names_its_scopes():
    """The AD loop's HLO carries the program's scope names in its
    ``op_name`` metadata — what a profiler's ``tf_op`` shows."""
    import re

    import jax.numpy as jnp

    from repro.core import operators

    g = GRAPHS["rmat"]
    strat = engine.make_strategy("AD")
    plan = fused._plan(strat, strat.setup(g), g)
    dist = jnp.full((g.num_nodes,), operators.shortest_path.identity,
                    jnp.int32).at[0].set(0)
    mask = jnp.zeros((g.num_nodes,), jnp.bool_).at[0].set(True)
    text = fused._fixed_point.lower(
        g, jnp.zeros((1,), jnp.int32), dist, mask, kernel="AD",
        max_iterations=100, **plan.static).as_text(dialect="hlo",
                                                   debug_info=True)
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    parts = {tuple(p.split("/")) for p in paths}
    for branch in ("BS", "WD", "HP"):
        for scope in ("frontier", "lanemap", "relax"):
            assert any("AD" in q and branch in q and scope in q
                       and q.index("AD") < q.index(branch) < q.index(scope)
                       for q in parts), (branch, scope)
    # AD's statistics and choice sit outside every branch
    assert any("AD" in q and "frontier" in q
               and not {"BS", "WD", "HP"} & set(q) for q in parts)


def _lowered(g, kernel, op="shortest_path", backend="xla"):
    """The fused loop's StableHLO for ``kernel`` on ``g``."""
    import jax.numpy as jnp

    from repro.core import operators

    op = operators.resolve(op)
    strat = engine.make_strategy(kernel)
    plan = fused._plan(strat, strat.setup(g), g)
    dist = jnp.full((g.num_nodes,), op.identity, op.dtype)
    mask = jnp.zeros((g.num_nodes,), jnp.bool_).at[0].set(True)
    return fused._fixed_point.lower(
        g, jnp.zeros((1,), jnp.int32), dist, mask, kernel=kernel,
        max_iterations=100, op=op, backend=backend, **plan.static).as_text()


def edge_gathers(text, e):
    """Gathers in lowered StableHLO whose operand is an ``[e]`` int32
    array: the per-lane reads of ``col`` and the weights."""
    return len(re.findall(
        rf'"stablehlo\.gather".*: \(tensor<{e}xi32>, ', text))


@pytest.mark.parametrize("kernel", ["BS", "WD", "HP", "AD"])
def test_packed_lane_maps_gather_once(kernel):
    """Every lane map of the packed layout reads an edge with ONE gather
    of its word where the plain layout gathers ``col`` and the weight."""
    g = GRAPHS["g500"]
    assert g.num_edges not in (g.num_nodes, g.num_nodes + 1)
    packed = edge_gathers(_lowered(g, kernel), g.num_edges)
    unpacked = edge_gathers(_lowered(g.plain(), kernel), g.num_edges)
    assert packed > 0 and unpacked == 2 * packed


@pytest.mark.parametrize("mode,module,name", [
    ("fused", fused, "_fixed_point"),
    ("stepped", strategies, "wd_relax"),
])
def test_pallas_tables_decoded_before_the_loop(mode, module, name,
                                              monkeypatch):
    """The Pallas kernels hold whole ``col``/weight tables: ``engine.run``
    hands them the plain layout, decoded once in its set-up, so no
    Pallas program decodes packed words inside the loop."""
    g = GRAPHS["rmat"]
    assert g.wt_shift is not None
    seen = []
    inner = getattr(module, name)

    def spy(graph, *args, **kw):
        seen.append(graph.wt_shift)
        return inner(graph, *args, **kw)

    monkeypatch.setattr(module, name, spy)
    got = engine.run(g, 0, engine.make_strategy("WD"), mode=mode,
                     backend="pallas")
    monkeypatch.setattr(module, name, inner)
    want = engine.run(g, 0, engine.make_strategy("WD"), mode=mode)
    assert seen and set(seen) == {None}
    np.testing.assert_array_equal(got.dist, want.dist)
    assert (got.iterations, got.edges_relaxed) == (want.iterations,
                                                   want.edges_relaxed)
    # the loop's lowering: no [E]-wide decode (a shift of the words)
    text = _lowered(g.plain(), "WD", backend="pallas")
    assert not re.search(
        rf"stablehlo\.shift_right_arithmetic.*tensor<{g.num_edges}xi32>",
        text)
    # and a packed graph handed to the Pallas tables is refused
    with pytest.raises(ValueError, match="plain"):
        _lowered(g, "WD", backend="pallas")


def test_engine_run_host_spans(tmp_path):
    """``engine.run`` marks its set-up, the fused call, the wait and the
    readback with host spans on the profiler's clock, in that order."""
    import jax
    from jax.profiler import ProfileData

    g = GRAPHS["rmat"]
    engine.run(g, 0, engine.make_strategy("AD"), mode="fused")   # warm
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine.run(g, 0, engine.make_strategy("AD"), mode="fused")
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    names = ("engine.setup", "engine.dispatch", "engine.wait",
             "engine.readback")
    spans = sorted((ev.start_ns, ev.name) for plane in pd.planes
                   if plane.name.startswith("/host:")
                   for line in plane.lines for ev in line.events
                   if ev.name in names)
    assert [name for _, name in spans] == list(names) + ["engine.readback"]
