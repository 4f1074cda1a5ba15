"""Convergence-property harness for priority-ordered (delta-stepping)
and asynchronous fixed points (repro.core.priority / repro.core.shard,
docs/scheduling.md).

What a *schedule* is allowed to change and what it must preserve:

* **values are schedule-independent** — for every strategy × idempotent
  operator × schedule (and async_shards on/off), the final value array
  must equal the BSP fixed point bit-for-bit AND the host oracles
  (Dijkstra for shortest_path, max-heap Dijkstra for widest_path, the
  order-free Jacobi sweep for everything);
* **bucket invariants** — a delta epoch settles the minimum live
  bucket; once bucket ``i`` is settled, no later epoch may reactivate
  work into a bucket ``<= i`` (the monotone-rank argument of Meyer &
  Sanders), observed through the per-epoch ``IterStats.bucket`` trail
  of stepped mode;
* **work bounds** — delta-stepping reorders relaxations, it must not
  multiply them: total relaxed edges stay within a small documented
  factor of BSP's, and in the degenerate case (Δ ≥ every finite rank)
  the accounting *equals* BSP's exactly;
* **cap semantics** — ``max_iterations`` caps the schedule's outer unit
  (bucket epochs for delta) identically in stepped and fused mode,
  including under ``engine.fixed_point`` custom multi-source seeding.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import engine, operators, priority, worklist
from repro.core.graph import INF
from repro.core.strategies import (
    PRIORITY_SCHEDULE, strategy_capabilities)
from repro.data import rmat_graph, road_grid_graph

from test_differential import host_fixed_point, single_source_init

DELTA_STRATEGIES = ["BS", "WD", "NS", "HP", "AD"]
MONOTONE_OPS = ["shortest_path", "min_label", "widest_path"]
N_SHARDS = min(len(jax.devices()), 4)

#: the high-diameter input where priority ordering pays off
ROAD = road_grid_graph(side=12, weighted=True, seed=5)
#: the low-diameter skewed input where BSP was already fine
RMAT = rmat_graph(scale=8, edge_factor=6, weighted=True, seed=5)
#: ROAD in the plain edge layout (``from_edges`` packs ROAD's weights
#: beside its heads; the constructor keeps them in their own array)
ROAD_PLAIN = ROAD.plain()

#: documented work bound: delta-stepping may re-relax light edges while
#: closing a bucket, but the light closure touches each bucket's frontier
#: a bounded number of times — empirically well under 2× BSP's total on
#: every suite graph; 3× is the contract tests pin (docs/scheduling.md)
EDGE_BOUND_FACTOR = 3


def _strategy(name):
    return engine.make_strategy(name)


# ---------------------------------------------------------------------------
# convergence matrix: strategy × operator × schedule == BSP == oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("graph_name", ["road", "rmat"])
@pytest.mark.parametrize("op", MONOTONE_OPS)
@pytest.mark.parametrize("strategy", DELTA_STRATEGIES)
def test_delta_matches_bsp_and_oracle(strategy, op, graph_name):
    g = ROAD if graph_name == "road" else RMAT
    opr = operators.resolve(op)
    source = 3
    ref = host_fixed_point(
        g, single_source_init(opr, g.num_nodes, source), op)
    bsp = engine.run(g, source, _strategy(strategy), op=op, mode="fused")
    delta = engine.run(g, source, _strategy(strategy), op=op, mode="fused",
                       schedule="delta")
    np.testing.assert_array_equal(
        delta.dist.astype(np.int64), ref,
        err_msg=f"{strategy}/{op}/{graph_name}: delta vs oracle")
    np.testing.assert_array_equal(delta.dist, bsp.dist)
    assert delta.schedule == "delta"
    assert delta.edges_relaxed <= EDGE_BOUND_FACTOR * bsp.edges_relaxed


def test_delta_matches_dijkstra_oracle():
    """shortest_path against the heap Dijkstra oracle specifically (the
    Jacobi sweep above is order-free but shares the relax formulation;
    Dijkstra is an independent algorithm)."""
    for g in (ROAD, RMAT):
        ref = engine.reference_distances(g, 0)
        r = engine.run(g, 0, _strategy("WD"), mode="fused",
                       schedule="delta")
        np.testing.assert_array_equal(r.dist, ref)


def _delta_numbers(r):
    return (r.dist.tolist(), r.iterations, r.relax_rounds, r.edges_relaxed,
            r.delta)


@pytest.mark.parametrize("packed", [True, False])
@pytest.mark.parametrize("op", MONOTONE_OPS)
def test_delta_stepped_equals_fused(op, packed):
    """Stepped and fused delta are the same schedule: bit-identical
    dist, equal epochs, relax rounds and edge totals — in the packed
    edge layout and the plain one alike (every ROAD edge is light here,
    so the light graph aliases the packed graph)."""
    g = ROAD if packed else ROAD_PLAIN
    stepped = engine.run(g, 0, _strategy("WD"), op=op, schedule="delta")
    fused = engine.run(g, 0, _strategy("WD"), op=op, mode="fused",
                       schedule="delta")
    np.testing.assert_array_equal(stepped.dist, fused.dist)
    assert stepped.iterations == fused.iterations
    assert stepped.relax_rounds == fused.relax_rounds
    assert stepped.edges_relaxed == fused.edges_relaxed
    assert stepped.delta == fused.delta
    assert (g.wt_shift is not None) == packed
    if packed:
        other = engine.run(ROAD_PLAIN, 0, _strategy("WD"), op=op,
                           mode="fused", schedule="delta")
        assert _delta_numbers(fused) == _delta_numbers(other)


def test_delta_pallas_backend_parity():
    """The delta phases reuse the fused step kernels, so the Pallas
    lowering rides along — bit-identical to the XLA path."""
    xla = engine.run(ROAD, 0, _strategy("WD"), mode="fused",
                     schedule="delta")
    pallas = engine.run(ROAD, 0, _strategy("WD"), mode="fused",
                        schedule="delta", backend="pallas")
    np.testing.assert_array_equal(pallas.dist, xla.dist)
    assert pallas.iterations == xla.iterations
    assert pallas.relax_rounds == xla.relax_rounds
    assert pallas.edges_relaxed == xla.edges_relaxed


# ---------------------------------------------------------------------------
# bucket invariants (stepped mode exposes the per-epoch bucket trail)
# ---------------------------------------------------------------------------

def test_bucket_trail_strictly_increases():
    """Settled-bucket monotonicity: epoch t settles the minimum live
    bucket, and light candidates stay in buckets >= current while heavy
    candidates land strictly later — so the per-epoch bucket indices
    must be strictly increasing.  (WD single-source: the all-active NS
    mirror can transiently re-open earlier buckets on *children*, which
    is why the invariant is stated on node-frontier strategies.)"""
    for op in MONOTONE_OPS:
        r = engine.run(ROAD, 0, _strategy("WD"), op=op, schedule="delta")
        buckets = [st.bucket for st in r.iter_stats]
        assert all(b is not None for b in buckets)
        assert all(b2 > b1 for b1, b2 in zip(buckets, buckets[1:])), (
            op, buckets)
        assert buckets[0] == 0      # the source's bucket settles first


def test_bucket_trail_respects_explicit_delta():
    """Halving Δ cannot decrease the number of settled buckets, and
    every settled bucket index stays consistent with the final
    distances: bucket b was settled <=> some node's final rank lands
    in it (reachable-bucket accounting)."""
    wide = engine.run(ROAD, 0, _strategy("WD"), schedule="delta", delta=400)
    narrow = engine.run(ROAD, 0, _strategy("WD"), schedule="delta",
                        delta=200)
    assert narrow.iterations >= wide.iterations
    final = wide.dist[wide.dist < INF]
    settled = {st.bucket for st in wide.iter_stats}
    populated = {int(b) for b in np.unique(final // 400)}
    # every populated bucket was settled by exactly one epoch
    assert populated <= settled


def test_iter_stats_carry_delta_bookkeeping():
    r = engine.run(ROAD, 0, _strategy("WD"), schedule="delta")
    assert r.iterations == len(r.iter_stats)
    assert r.relax_rounds == sum(st.sub_iterations for st in r.iter_stats)
    assert r.edges_relaxed == sum(st.edges_processed for st in r.iter_stats)
    assert all(st.kernel == "delta:WD" for st in r.iter_stats)
    # BSP results leave the bucket field unset
    b = engine.run(ROAD, 0, _strategy("WD"))
    assert all(st.bucket is None for st in b.iter_stats)


# ---------------------------------------------------------------------------
# degenerate Δ: one bucket == plain BSP, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", DELTA_STRATEGIES)
def test_degenerate_delta_reduces_to_bsp(strategy):
    """Δ ≥ every finite rank ⇒ the light subgraph aliases the full graph
    and the single bucket's light closure IS the BSP loop: equal relax
    rounds, equal edge totals, bit-identical dist."""
    bsp = engine.run(ROAD, 0, _strategy(strategy), mode="fused")
    deg = engine.run(ROAD, 0, _strategy(strategy), mode="fused",
                     schedule="delta", delta=2 * int(INF))
    np.testing.assert_array_equal(deg.dist, bsp.dist)
    assert deg.iterations == 1                 # one bucket epoch
    assert deg.relax_rounds == bsp.iterations  # rounds == BSP iterations
    assert deg.edges_relaxed == bsp.edges_relaxed


def test_degenerate_delta_plan_aliases_graph():
    """No heavy edges ⇒ the plan's light graph must alias the phase
    graph (no copy, no reordering) — the structural reason the
    degenerate case is bit-exact."""
    strat = _strategy("WD")
    state = strat.setup(ROAD)
    plan = priority.plan_delta(strat, state, ROAD, delta=2 * int(INF))
    assert not plan.heavy
    assert plan.light.col is ROAD.col
    assert ROAD.wt_shift is not None
    assert plan.light.wt_shift == ROAD.wt_shift
    split = priority.plan_delta(strat, state, ROAD, delta=1)
    assert split.heavy
    # the split graphs keep plain weights
    assert split.light.wt_shift is None and split.heavy_graph.wt_shift is None
    assert (split.light.num_edges + split.heavy_graph.num_edges
            == ROAD.num_edges)


# ---------------------------------------------------------------------------
# max_iterations cap semantics (the latent-issue satellite): the cap
# counts the schedule's outer unit identically in stepped and fused mode,
# including under custom multi-source seeding
# ---------------------------------------------------------------------------

def _two_sources(n_alloc):
    s0, s1 = 0, ROAD.num_nodes - 1
    dist = (jnp.full((n_alloc,), INF, jnp.int32).at[s0].set(0).at[s1].set(0))
    mask = (jnp.zeros((n_alloc,), jnp.bool_)
            .at[s0].set(True).at[s1].set(True))
    return dist, mask


@pytest.mark.parametrize("schedule", ["bsp", "delta"])
def test_fixed_point_cap_parity_multi_source(schedule):
    """engine.fixed_point with custom multi-source seeding must respect
    max_iterations identically across schedules and modes: capped at K,
    both modes stop after exactly K outer units (BSP iterations / delta
    bucket epochs) with the same partial values."""
    # narrow buckets under delta so a 2-epoch cap truncates *values*,
    # not just bookkeeping (a wide Δ can finalize every distance in two
    # epochs and then spend further epochs settling already-exact
    # buckets)
    kw = {"delta": 64} if schedule == "delta" else {}
    full, full_it, _ = engine.fixed_point(
        ROAD, _strategy("WD"), _two_sources, schedule=schedule, **kw)
    assert full_it > 2                        # the cap below really bites
    cap = 2
    stepped, it_s, e_s = engine.fixed_point(
        ROAD, _strategy("WD"), _two_sources, schedule=schedule,
        max_iterations=cap, **kw)
    fused, it_f, e_f = engine.fixed_point(
        ROAD, _strategy("WD"), _two_sources, schedule=schedule,
        max_iterations=cap, mode="fused", **kw)
    assert it_s == it_f == cap
    assert e_s == e_f
    np.testing.assert_array_equal(stepped, fused)
    assert not np.array_equal(stepped, full)   # genuinely truncated


def test_fixed_point_multi_source_delta_equals_bsp():
    """Uncapped, the two schedules land on the same multi-source fixed
    point (min of per-source runs)."""
    bsp, _, _ = engine.fixed_point(ROAD, _strategy("WD"), _two_sources)
    delta, _, _ = engine.fixed_point(ROAD, _strategy("WD"), _two_sources,
                                     schedule="delta")
    np.testing.assert_array_equal(delta, bsp)


def test_run_cap_counts_bucket_epochs():
    """engine.run: a delta run capped at K reports exactly K epochs and
    its relax_rounds exceed K (the cap did NOT count rounds)."""
    full = engine.run(ROAD, 0, _strategy("WD"), mode="fused",
                      schedule="delta")
    assert full.iterations > 2
    capped = engine.run(ROAD, 0, _strategy("WD"), mode="fused",
                        schedule="delta", max_iterations=2)
    capped_stepped = engine.run(ROAD, 0, _strategy("WD"),
                                schedule="delta", max_iterations=2)
    assert capped.iterations == capped_stepped.iterations == 2
    assert capped.relax_rounds == capped_stepped.relax_rounds > 2
    np.testing.assert_array_equal(capped.dist, capped_stepped.dist)


# ---------------------------------------------------------------------------
# async shards: stale reads converge to the same values
# ---------------------------------------------------------------------------

@pytest.mark.multi_device
@pytest.mark.parametrize("op", MONOTONE_OPS)
@pytest.mark.parametrize("strategy", ["BS", "WD", "HP", "NS"])
def test_async_shards_same_fixed_point(strategy, op):
    sync = engine.run(ROAD, 0, _strategy(strategy), op=op, mode="fused",
                      shards=N_SHARDS)
    async_ = engine.run(ROAD, 0, _strategy(strategy), op=op, mode="fused",
                        shards=N_SHARDS, async_shards=True)
    np.testing.assert_array_equal(async_.dist, sync.dist,
                                  err_msg=f"{strategy}/{op}")
    assert async_.async_shards
    # epochs can't exceed lockstep iterations: each epoch drains every
    # shard at least as far as one lockstep step would
    assert async_.iterations <= sync.iterations


@pytest.mark.multi_device
def test_async_shards_fixed_point_seeding():
    """CC-style all-active seeding through engine.fixed_point, async."""
    def all_active(n):
        return (jnp.arange(n, dtype=jnp.int32), jnp.ones((n,), jnp.bool_))

    ref, _, _ = engine.fixed_point(ROAD, _strategy("WD"), all_active,
                                   op="min_label", mode="fused",
                                   shards=N_SHARDS)
    got, it, edges = engine.fixed_point(ROAD, _strategy("WD"), all_active,
                                        op="min_label", mode="fused",
                                        shards=N_SHARDS, async_shards=True)
    np.testing.assert_array_equal(got, ref)
    assert it > 0 and edges > 0


# ---------------------------------------------------------------------------
# batched delta
# ---------------------------------------------------------------------------

def test_batch_delta_matches_per_source_runs():
    sources = [0, 7, ROAD.num_nodes // 2, ROAD.num_nodes - 1]
    batch = engine.run_batch(ROAD, sources, mode="fused", schedule="delta")
    assert batch.schedule == "delta" and batch.delta >= 1
    for i, s in enumerate(sources):
        single = engine.run(ROAD, s, _strategy("WD"), mode="fused",
                            schedule="delta")
        np.testing.assert_array_equal(batch.dist[i], single.dist,
                                      err_msg=f"row {i} (source {s})")
    bsp = engine.run_batch(ROAD, sources, mode="fused")
    np.testing.assert_array_equal(batch.dist, bsp.dist)


def test_batch_delta_requires_fused():
    with pytest.raises(ValueError, match="fused"):
        engine.run_batch(ROAD, [0, 1], mode="stepped", schedule="delta")


# ---------------------------------------------------------------------------
# knob surfacing, capability gating, worklist helpers
# ---------------------------------------------------------------------------

def test_auto_delta_surfaced_on_result():
    r = engine.run(ROAD, 0, _strategy("WD"), mode="fused",
                   schedule="delta")
    assert r.delta == priority.auto_delta(ROAD)
    explicit = engine.run(ROAD, 0, _strategy("WD"), mode="fused",
                          schedule="delta", delta=123)
    assert explicit.delta == 123
    bsp = engine.run(ROAD, 0, _strategy("WD"), mode="fused")
    assert bsp.delta is None and bsp.schedule == "bsp"
    assert bsp.relax_rounds == bsp.iterations


def test_auto_delta_unweighted_default():
    g = road_grid_graph(side=6, weighted=False, seed=0)
    assert priority.auto_delta(g) == priority.DELTA_WEIGHT_MULTIPLIER


def test_priority_schedule_capability_declarations():
    for name in DELTA_STRATEGIES:
        assert PRIORITY_SCHEDULE in strategy_capabilities(name), name
    assert PRIORITY_SCHEDULE not in strategy_capabilities("EP")


def test_schedule_gating_errors():
    g, wd = ROAD, _strategy("WD")
    with pytest.raises(ValueError, match="priority_schedule"):
        engine.run(g, 0, _strategy("EP"), schedule="delta")
    with pytest.raises(ValueError, match="idempotent"):
        engine.run(g, 0, wd, schedule="delta", op="reach_count")
    with pytest.raises(ValueError, match="single-device"):
        engine.run(g, 0, wd, mode="fused", shards=1, schedule="delta")
    with pytest.raises(ValueError, match="shards"):
        engine.run(g, 0, wd, async_shards=True)
    with pytest.raises(ValueError, match="stale"):
        engine.run(g, 0, wd, mode="fused", shards=1, op="reach_count",
                   async_shards=True)
    with pytest.raises(ValueError, match="delta="):
        engine.run(g, 0, wd, delta=5)
    with pytest.raises(ValueError, match="schedule"):
        engine.run(g, 0, wd, schedule="lifo")
    with pytest.raises(ValueError, match="delta must be >= 1"):
        engine.run(g, 0, wd, schedule="delta", delta=0)
    with pytest.raises(ValueError, match="record_degrees"):
        engine.run(g, 0, wd, schedule="delta", record_degrees=True)
    with pytest.raises(ValueError, match="WD"):
        plan = priority.plan_delta(_strategy("BS"),
                                   _strategy("BS").setup(g), g)
        priority.run_batch_fixed_point(
            plan, jnp.zeros((1, g.num_nodes), jnp.int32),
            jnp.zeros((1, g.num_nodes), jnp.bool_))


def test_worklist_bucket_helpers():
    vals = jnp.asarray([0, 5, 9, 10, INF], jnp.int32)
    np.testing.assert_array_equal(
        worklist.bucket_index(vals, jnp.int32(5)), [0, 1, 1, 2, INF // 5])
    # descending rank (max monoids): INF ranks lowest
    np.testing.assert_array_equal(
        worklist.bucket_index(vals, jnp.int32(5), descending=True),
        [INF // 5, (INF - 5) // 5, (INF - 9) // 5, (INF - 10) // 5, 0])
    mask = jnp.asarray([False, True, False, True, False])
    b = worklist.bucket_index(vals, jnp.int32(5))
    assert int(worklist.min_live_bucket(mask, b)) == 1
    none = jnp.zeros((5,), jnp.bool_)
    assert int(worklist.min_live_bucket(none, b)) == worklist.NO_BUCKET
    # negative values clip into bucket 0 (defensive: identity-below-zero)
    np.testing.assert_array_equal(
        worklist.bucket_rank(jnp.asarray([-3, 2], jnp.int32)), [0, 2])


def test_weight_additive_declarations():
    assert operators.shortest_path.weight_additive
    assert not operators.min_label.weight_additive
    assert not operators.widest_path.weight_additive
    assert not operators.reach_count.weight_additive
    # non-additive monotone ops run delta with an all-light split
    strat = _strategy("WD")
    plan = priority.plan_delta(strat, strat.setup(ROAD), ROAD,
                               op=operators.widest_path, delta=1)
    assert not plan.heavy


# ---------------------------------------------------------------------------
# auto-delta clamping and Schedule-carried delta policy
# ---------------------------------------------------------------------------

def _zero_weight(g):
    from repro.core.graph import CSRGraph
    wt = np.zeros((g.num_edges,), np.int32)
    return CSRGraph(g.row_ptr, g.col, jnp.asarray(wt), g.num_nodes,
                    g.num_edges, g.max_degree)


@pytest.mark.parametrize("strategy", ["BS", "WD"])
def test_delta_bfs_parity_on_unweighted_graph(strategy):
    # regression for the Δ≥1 clamp: unit weights give Δ = multiplier,
    # and the delta run must still land on exact BFS levels
    g = road_grid_graph(side=10, weighted=False, seed=4)
    bsp = engine.run(g, 0, _strategy(strategy), mode="fused")
    delta = engine.run(g, 0, _strategy(strategy), mode="fused",
                       schedule="delta")
    np.testing.assert_array_equal(np.asarray(delta.dist),
                                  np.asarray(bsp.dist))
    assert delta.delta == priority.DELTA_WEIGHT_MULTIPLIER


@pytest.mark.parametrize("strategy", ["BS", "WD"])
def test_delta_bfs_parity_on_zero_weight_graph(strategy):
    # the pathological input the clamp exists for: a zero-mean weight
    # array would yield Δ=0 and a division by zero in bucket_index;
    # clamped to Δ=1 the run settles everything reachable at distance 0
    g = _zero_weight(road_grid_graph(side=8, weighted=True, seed=4))
    assert priority.auto_delta(g) == 1
    bsp = engine.run(g, 0, _strategy(strategy), mode="fused")
    delta = engine.run(g, 0, _strategy(strategy), mode="fused",
                       schedule="delta")
    np.testing.assert_array_equal(np.asarray(delta.dist),
                                  np.asarray(bsp.dist))
    assert delta.delta == 1


def test_auto_delta_multiplier_clamps():
    # multiplier is itself clamped to >= 1, so even an absurd caller
    # value cannot produce Δ=0
    assert priority.auto_delta(ROAD, multiplier=0) >= 1
    assert priority.auto_delta(ROAD, multiplier=-3) >= 1
    g0 = _zero_weight(ROAD)
    assert priority.auto_delta(g0, multiplier=100) == 1


def test_schedule_object_carries_delta_policy():
    from repro.core.schedule import Schedule
    pinned = engine.run(
        ROAD, 0, engine.make_strategy("WD", schedule=Schedule(delta=7)),
        mode="fused", schedule="delta")
    assert pinned.delta == 7
    doubled = engine.run(
        ROAD, 0,
        engine.make_strategy("WD", schedule=Schedule(delta_multiplier=2)),
        mode="fused", schedule="delta")
    assert doubled.delta == priority.auto_delta(ROAD, multiplier=2)
    # the engine-level kwarg still wins over the schedule's policy
    explicit = engine.run(
        ROAD, 0, engine.make_strategy("WD", schedule=Schedule(delta=7)),
        mode="fused", schedule="delta", delta=9)
    assert explicit.delta == 9
    # and whichever won, the fixed point is the same
    base = engine.run(ROAD, 0, _strategy("WD"), mode="fused")
    for r in (pinned, doubled, explicit):
        np.testing.assert_array_equal(np.asarray(r.dist),
                                      np.asarray(base.dist))
