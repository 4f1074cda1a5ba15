"""Tests for the sharded multi-device fixed-point engine
(``repro.core.shard`` — docs/sharding.md).

Two layers:

* a **subprocess parity matrix** on 8 forced host devices
  (``XLA_FLAGS=--xla_force_host_platform_device_count=8``, the
  documented CPU recipe) proving the acceptance criterion: a sharded
  fused run is bit-identical — dist, iterations, edges_relaxed — to the
  single-device fused AND stepped paths for every SHARDABLE strategy ×
  built-in operator, with a ``backend="pallas"`` leg running the
  per-shard Pallas kernels + epilogue-fused ghost combine
  (docs/backends.md), plus the batched engine, CC seeding through
  ``engine.fixed_point``, both partition methods, and the
  one-dispatch-per-traversal claim.  The subprocess keeps the 8-device
  override out of this process's jax state (same pattern as
  tests/test_moe_sharded.py), so the matrix runs under plain tier-1 too.
* **in-process tests** for the host-side partitioner (boundaries, local
  CSR reconstruction, ghost maps, balance), the capability gating /
  validation errors, ``shards=1`` on whatever devices are visible, and
  the once-per-edge accounting contract on ``RunResult``.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from repro.core import engine, shard
from repro.core.graph import CSRGraph
from repro.core.strategies import (DEFAULT_CAPABILITIES, SHARDABLE,
                                   strategy_capabilities)
from repro.data import rmat_graph, road_grid_graph

SHARDED_STRATEGIES = ["BS", "WD", "HP", "NS"]

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import json
import numpy as np
import jax.numpy as jnp
from repro.algos import connected_components
from repro.core import engine, fused, operators
from repro.core.graph import CSRGraph
from repro.data import rmat_graph, road_grid_graph

summary = {"cases": 0}


def check(tag, sharded, single, stepped=None):
    assert np.array_equal(sharded.dist, single.dist), f"{tag}: dist"
    assert sharded.iterations == single.iterations, (
        f"{tag}: iterations {sharded.iterations} != {single.iterations}")
    assert sharded.edges_relaxed == single.edges_relaxed, (
        f"{tag}: edges {sharded.edges_relaxed} != {single.edges_relaxed}")
    if stepped is not None:
        assert np.array_equal(sharded.dist, stepped.dist), f"{tag}: vs stepped"
        assert sharded.iterations == stepped.iterations, f"{tag}: it stepped"
        assert sharded.edges_relaxed == stepped.edges_relaxed, (
            f"{tag}: edges stepped")
    summary["cases"] += 1


g = rmat_graph(scale=8, edge_factor=8, weighted=True, seed=7)

# --- the acceptance matrix: every SHARDABLE strategy x built-in operator
for strat in ("BS", "WD", "HP", "NS"):
    for op in ("shortest_path", "min_label", "widest_path"):
        single = engine.run(g, 0, engine.make_strategy(strat),
                            mode="fused", op=op)
        stepped = engine.run(g, 0, engine.make_strategy(strat), op=op)
        sharded = engine.run(g, 0, engine.make_strategy(strat),
                             mode="fused", op=op, shards=8)
        assert sharded.shards == 8
        check(f"{strat}/{op}", sharded, single, stepped)

# reach_count on its documented convergence domain (a level-layered DAG)
rng = np.random.default_rng(0)
layers, start = [], 0
for w in (1, 3, 4, 3, 2):
    layers.append(np.arange(start, start + w)); start += w
src, dst = [], []
for a, b in zip(layers[:-1], layers[1:]):
    for u in a:
        picks = b[rng.random(len(b)) < 0.7]
        if len(picks) == 0:
            picks = b[:1]
        src.extend([u] * len(picks)); dst.extend(picks)
dag = CSRGraph.from_edges(np.array(src), np.array(dst),
                          rng.integers(1, 10, len(src)), start)
for strat in ("BS", "WD", "HP", "NS"):
    single = engine.run(dag, 0, engine.make_strategy(strat),
                        mode="fused", op="reach_count")
    sharded = engine.run(dag, 0, engine.make_strategy(strat),
                         mode="fused", op="reach_count", shards=5)
    check(f"{strat}/reach_count", sharded, single)

# --- HP's large-frontier branch (MDT tile loop + cursor tail): the
# default switch_threshold never trips on these small graphs, so force it
for kw in (dict(switch_threshold=4, mdt=3), dict(switch_threshold=16, mdt=7)):
    stepped = engine.run(g, 0, engine.make_strategy("HP", **kw))
    single = engine.run(g, 0, engine.make_strategy("HP", **kw), mode="fused")
    sharded = engine.run(g, 0, engine.make_strategy("HP", **kw),
                         mode="fused", shards=8)
    check(f"HP-big/{kw['switch_threshold']}", sharded, single, stepped)

# --- BS/NS columns wider than the smallest lane block: a BFS level with
# more walking nodes than 4 shards x 256 lanes, so some shard's column
# runs in a wider block of the switch
wide = rmat_graph(scale=12, edge_factor=8, weighted=False, seed=3)
deg = np.diff(np.asarray(wide.row_ptr))
hub = int(np.argmax(deg))
levels = np.asarray(engine.run(wide, hub, engine.make_strategy("WD"),
                               mode="fused").dist)
assert max(np.sum((levels == lv) & (deg > 0))
           for lv in range(1, 4)) > 4 * 256
for strat in ("BS", "NS"):
    single = engine.run(wide, hub, engine.make_strategy(strat), mode="fused")
    sharded = engine.run(wide, hub, engine.make_strategy(strat),
                         mode="fused", shards=4)
    check(f"{strat}/wide", sharded, single)

# --- pallas backend: per-shard Pallas kernels with the ghost combine
# fused into the kernel epilogue
# (docs/backends.md#sharded-pallas-the-fused-ghost-combine)
for strat in ("BS", "WD", "HP", "NS"):
    single = engine.run(g, 0, engine.make_strategy(strat), mode="fused")
    stepped = engine.run(g, 0, engine.make_strategy(strat))
    sharded = engine.run(g, 0, engine.make_strategy(strat),
                         mode="fused", shards=8, backend="pallas")
    assert sharded.shards == 8 and sharded.backend == "pallas"
    check(f"{strat}/pallas", sharded, single, stepped)

# the non-min monoids through the fused epilogue (max-fold + psum)
wp = engine.run(g, 0, engine.make_strategy("WD"), mode="fused",
                op="widest_path")
wps = engine.run(g, 0, engine.make_strategy("WD"), mode="fused",
                 op="widest_path", shards=4, backend="pallas")
check("WD/widest_path/pallas", wps, wp)
rc = engine.run(dag, 0, engine.make_strategy("WD"), mode="fused",
                op="reach_count")
rcs = engine.run(dag, 0, engine.make_strategy("WD"), mode="fused",
                 op="reach_count", shards=5, backend="pallas")
check("WD/reach_count/pallas", rcs, rc)

# sharded pallas keys its own dispatch/trace counters; repeating the
# shape must not dispatch under (or retrace) the sharded-XLA keys
dp = fused.DISPATCH_COUNTS["shard:pallas:WD"]
tp = fused.TRACE_COUNTS["shard:pallas:WD"]
dx = fused.DISPATCH_COUNTS["shard:WD"]
res = engine.run(g, 0, engine.make_strategy("WD"), mode="fused",
                 shards=8, backend="pallas")
assert res.iterations > 1
assert fused.DISPATCH_COUNTS["shard:pallas:WD"] == dp + 1
assert fused.TRACE_COUNTS["shard:pallas:WD"] == tp, "sharded pallas retraced"
assert fused.DISPATCH_COUNTS["shard:WD"] == dx, "xla counter disturbed"
summary["cases"] += 1

# --- edge accounting: each edge counted once across shards (regression)
single = engine.run(g, 0, engine.make_strategy("WD"), mode="fused")
sharded = engine.run(g, 0, engine.make_strategy("WD"), mode="fused",
                     shards=8)
summary["edges_single"] = single.edges_relaxed
summary["edges_sharded"] = sharded.edges_relaxed

# --- both partition methods agree with each other and the oracle
road = road_grid_graph(side=16, weighted=True, seed=7)
ref = engine.reference_distances(road, 0)
for method in ("degree", "contiguous"):
    res = engine.run(road, 0, engine.make_strategy("HP"), mode="fused",
                     shards=7, partition=method)
    assert np.array_equal(res.dist, ref), f"partition={method}: vs Dijkstra"
    summary["cases"] += 1

# --- batched multi-source: sharded == fused == stepped
sources = [0, 3, 17, 42]
sb = engine.run_batch(road, sources)
fb = engine.run_batch(road, sources, mode="fused")
hb = engine.run_batch(road, sources, mode="fused", shards=8)
assert hb.shards == 8
assert np.array_equal(hb.dist, fb.dist) and np.array_equal(hb.dist, sb.dist)
assert hb.iterations == fb.iterations == sb.iterations
assert hb.edges_relaxed == fb.edges_relaxed == sb.edges_relaxed
summary["cases"] += 1

# --- custom seeding through engine.fixed_point: sharded CC == single
ref_cc = connected_components(road, strategy="WD", mode="fused")
got_cc = connected_components(road, strategy="WD", mode="fused", shards=8)
assert np.array_equal(got_cc, ref_cc), "sharded CC diverged"
summary["cases"] += 1

# --- one dispatch per traversal, zero recompiles when shapes repeat
d0 = fused.DISPATCH_COUNTS["shard:WD"]
t0 = fused.TRACE_COUNTS["shard:WD"]
res = engine.run(g, 0, engine.make_strategy("WD"), mode="fused", shards=8)
assert res.iterations > 1
assert fused.DISPATCH_COUNTS["shard:WD"] == d0 + 1
assert fused.TRACE_COUNTS["shard:WD"] == t0, "sharded WD recompiled"
summary["cases"] += 1

print(json.dumps(summary))
"""


@pytest.fixture(scope="module")
def parity():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SCRIPT], cwd=os.path.join(
        os.path.dirname(__file__), ".."), env=env, capture_output=True,
        text=True, timeout=1800)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
@pytest.mark.multi_device
def test_sharded_bit_parity_matrix(parity):
    """Acceptance: 8-virtual-device sharded runs are bit-identical to the
    single-device paths for every SHARDABLE strategy × built-in op."""
    # 4 strategies × 3 monotone ops + 4 reach_count + 2 HP-big-branch +
    # 2 wide BS/NS columns +
    # 4 pallas strategies + 2 pallas monoids + pallas counters +
    # 2 partition methods + batch + CC + dispatch counting
    assert parity["cases"] >= 30


@pytest.mark.slow
@pytest.mark.multi_device
def test_sharded_edge_accounting_counts_each_edge_once(parity):
    """Regression: mteps' numerator under sharding must equal the
    single-device relaxed-edge total, not S copies of it."""
    assert parity["edges_sharded"] == parity["edges_single"]
    assert parity["edges_sharded"] > 0


# ---------------------------------------------------------------------------
# in-process: host-side partitioner
# ---------------------------------------------------------------------------

RMAT = rmat_graph(scale=8, edge_factor=8, weighted=True, seed=7)


@pytest.mark.parametrize("method", ["degree", "contiguous"])
@pytest.mark.parametrize("num_shards", [1, 3, 8])
def test_partition_reassembles_to_original(method, num_shards):
    sharded, info = shard.partition(RMAT, num_shards, method=method)
    rp = np.asarray(RMAT.row_ptr)
    col = np.asarray(RMAT.col)
    wt = np.asarray(RMAT.wt)
    bounds = info.boundaries
    assert bounds[0] == 0 and bounds[-1] == RMAT.num_nodes
    assert (np.diff(bounds) >= 0).all()
    assert info.nodes.sum() == RMAT.num_nodes
    assert info.edges.sum() == RMAT.num_edges
    srp = np.asarray(sharded.row_ptr)
    scol = np.asarray(sharded.col)
    swt = np.asarray(sharded.wt)
    for s in range(num_shards):
        b0, b1 = int(bounds[s]), int(bounds[s + 1])
        n_local, e_local = b1 - b0, int(rp[b1] - rp[b0])
        # local row_ptr == global slice rebased to 0, padded flat
        np.testing.assert_array_equal(srp[s, : n_local + 1],
                                      rp[b0:b1 + 1] - rp[b0])
        assert (srp[s, n_local + 1:] == e_local).all()
        # local edges == the owned global slice, in order
        np.testing.assert_array_equal(scol[s, :e_local],
                                      col[rp[b0]:rp[b1]])
        np.testing.assert_array_equal(swt[s, :e_local],
                                      wt[rp[b0]:rp[b1]])
        # ghosts: exactly the referenced non-owned destinations
        dsts = np.unique(col[rp[b0]:rp[b1]])
        expect = dsts[(dsts < b0) | (dsts >= b1)]
        np.testing.assert_array_equal(info.ghosts[s], expect)


def test_degree_partition_balances_edges_better_than_contiguous():
    """On a power-law graph, equal node counts put most edges on few
    shards; the degree method cuts the degree prefix sum instead."""
    _, by_degree = shard.partition(RMAT, 8, method="degree")
    _, by_nodes = shard.partition(RMAT, 8, method="contiguous")
    assert by_degree.edge_imbalance <= by_nodes.edge_imbalance
    assert by_degree.edge_imbalance < 1.5


def test_degree_partition_handles_leading_hub():
    """Regression: a hub at node 0 with degree >= E/S must not collapse
    every degree cut to 0 (all nodes on the last shard)."""
    star = CSRGraph.from_edges(np.array([0, 0, 0, 0, 1]),
                               np.array([1, 2, 3, 4, 0]),
                               np.ones(5, np.int64), 5)
    bounds = shard.partition_boundaries(star, 3, "degree")
    assert bounds[0] == 0 and bounds[-1] == 5
    # the hub occupies one shard by itself; the rest is spread, not piled
    _, info = shard.partition(star, 3, method="degree")
    assert info.edges.max() == 4          # the hub's shard
    assert (info.nodes > 0).sum() >= 2    # not everything on one shard


def test_partition_validation():
    with pytest.raises(ValueError, match="num_shards"):
        shard.partition(RMAT, 0)
    with pytest.raises(ValueError, match="method"):
        shard.partition(RMAT, 2, method="metis")


def test_shard_info_halo_fields():
    _, info = shard.partition(RMAT, 4)
    assert info.num_shards == 4
    assert info.halo_total == sum(len(g) for g in info.ghosts)
    assert info.halo_bytes == 4 * info.halo_total
    # cross-shard edges exist on any connected multi-shard partition
    assert info.halo_total > 0
    assert 0.0 < info.cut_share <= 1.0
    # manual recount of the edge cut
    rp = np.asarray(RMAT.row_ptr)
    col = np.asarray(RMAT.col)
    for s in range(4):
        b0, b1 = int(info.boundaries[s]), int(info.boundaries[s + 1])
        span = col[rp[b0]:rp[b1]]
        assert info.cut_edges[s] == int(((span < b0) | (span >= b1)).sum())

    _, one = shard.partition(RMAT, 1)
    assert one.cut_share == 0.0 and one.halo_total == 0


def test_partition_more_shards_than_nodes():
    tiny = CSRGraph.from_edges(np.array([0, 1]), np.array([1, 2]),
                               np.array([1, 1]), 3)
    sharded, info = shard.partition(tiny, 8, method="contiguous")
    assert info.nodes.sum() == 3
    assert sharded.num_shards == 8          # empty shards ride along


# ---------------------------------------------------------------------------
# in-process: capability gating + validation
# ---------------------------------------------------------------------------

def test_shardable_capability_declarations():
    for name in SHARDED_STRATEGIES:
        assert SHARDABLE in strategy_capabilities(name)
    for name in ("EP", "AD"):
        assert SHARDABLE not in strategy_capabilities(name)
    # third-party strategies are single-device until they say otherwise
    assert SHARDABLE not in DEFAULT_CAPABILITIES


def test_run_rejects_non_shardable_strategies():
    for name in ("EP", "AD"):
        with pytest.raises(ValueError, match="shardable"):
            engine.run(RMAT, 0, engine.make_strategy(name), mode="fused",
                       shards=1)


def test_run_rejects_stepped_sharding():
    with pytest.raises(ValueError, match="fused"):
        engine.run(RMAT, 0, engine.make_strategy("WD"), shards=1)
    with pytest.raises(ValueError, match="fused"):
        engine.run_batch(RMAT, [0], shards=1)
    with pytest.raises(ValueError, match="fused"):
        engine.fixed_point(RMAT, engine.make_strategy("WD"),
                           lambda n: (None, None), shards=1)


def test_shard_mesh_overask_mentions_cpu_recipe():
    want = len(jax.devices()) + 1
    with pytest.raises(ValueError, match="host_platform_device_count"):
        shard.shard_mesh(want)


# ---------------------------------------------------------------------------
# in-process: shards=1 runs the full shard_map machinery on any host
# ---------------------------------------------------------------------------

ROAD = road_grid_graph(side=12, weighted=True, seed=7)


@pytest.mark.parametrize("strategy", SHARDED_STRATEGIES)
def test_single_shard_matches_single_device(strategy):
    single = engine.run(ROAD, 0, engine.make_strategy(strategy),
                        mode="fused")
    sharded = engine.run(ROAD, 0, engine.make_strategy(strategy),
                         mode="fused", shards=1)
    np.testing.assert_array_equal(sharded.dist, single.dist)
    assert sharded.iterations == single.iterations
    assert sharded.edges_relaxed == single.edges_relaxed
    assert sharded.shards == 1 and sharded.mode == "fused"


def test_single_shard_batch_matches():
    fb = engine.run_batch(ROAD, [0, 5, 9], mode="fused")
    hb = engine.run_batch(ROAD, [0, 5, 9], mode="fused", shards=1)
    np.testing.assert_array_equal(hb.dist, fb.dist)
    assert hb.iterations == fb.iterations
    assert hb.edges_relaxed == fb.edges_relaxed


def test_sharded_state_bytes_include_partition():
    single = engine.run(ROAD, 0, engine.make_strategy("WD"), mode="fused")
    sharded = engine.run(ROAD, 0, engine.make_strategy("WD"), mode="fused",
                         shards=1)
    assert sharded.state_bytes > single.state_bytes


def test_run_result_shards_default():
    res = engine.RunResult(
        dist=np.zeros(1, np.int32), iterations=1, total_seconds=1.0,
        setup_seconds=0.0, kernel_seconds=1.0, overhead_seconds=0.0,
        edges_relaxed=2_000_000, iter_stats=[], strategy="WD",
        state_bytes=0)
    assert res.shards == 1
    assert res.mteps == pytest.approx(2.0)
