"""Backend parity matrix: ``backend="pallas"`` ≡ ``backend="xla"``.

The contract of the Pallas kernel layer (repro.kernels.relax,
docs/backends.md): for every strategy × built-in operator × execution
mode, switching the relax backend changes *nothing observable* — ``dist``,
``iterations`` and ``edges_relaxed`` are bit-identical — and switching
back costs nothing (the XLA jit cache entry survives, asserted from the
per-backend trace counters).

Pallas runs in interpret mode on CPU (the default), so this suite
exercises the exact kernel code path CI ships.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.algos import connected_components, widest_path
from repro.algos.widest import reference_widest
from repro.core import engine, fused
from repro.core.graph import CSRGraph
from repro.core.strategies import (
    BACKENDS, PALLAS_BACKEND, StrategyBase, strategy_capabilities)
from repro.data import rmat_graph, road_grid_graph

ALL_STRATEGIES = ["BS", "EP", "WD", "NS", "HP", "AD"]
MONOTONE_OPS = ["shortest_path", "min_label", "widest_path"]
MODES = ["stepped", "fused"]

#: small on purpose: interpret-mode Pallas serializes the grid on CPU,
#: and backend parity is scale-independent (the chunk schedule — not the
#: graph size — is what must match)
RMAT = rmat_graph(scale=7, edge_factor=6, weighted=True, seed=7)
ROAD = road_grid_graph(side=10, weighted=True, seed=7)


def _layered_dag(seed=0):
    """Level-layered DAG — reach_count's documented convergence domain."""
    rng = np.random.default_rng(seed)
    layers, start = [], 0
    for w in (1, 3, 4, 3, 2):
        layers.append(np.arange(start, start + w))
        start += w
    src, dst = [], []
    for a, b in zip(layers[:-1], layers[1:]):
        for u in a:
            picks = b[rng.random(len(b)) < 0.7]
            if len(picks) == 0:
                picks = b[:1]
            src.extend([u] * len(picks))
            dst.extend(picks)
    return CSRGraph.from_edges(np.array(src), np.array(dst),
                               rng.integers(1, 10, len(src)), start)


DAG = _layered_dag()


def _assert_parity(tag, xla, pallas):
    np.testing.assert_array_equal(
        pallas.dist, xla.dist, err_msg=f"{tag}: dist diverged")
    assert pallas.iterations == xla.iterations, (
        f"{tag}: iterations {pallas.iterations} != {xla.iterations}")
    assert pallas.edges_relaxed == xla.edges_relaxed, (
        f"{tag}: edges {pallas.edges_relaxed} != {xla.edges_relaxed}")
    assert xla.backend == "xla" and pallas.backend == "pallas"


# ---------------------------------------------------------------------------
# the acceptance matrix: strategy × operator × mode × backend
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("opname", MONOTONE_OPS)
@pytest.mark.parametrize("strategy", ALL_STRATEGIES)
def test_backend_parity_matrix(strategy, opname, mode):
    runs = {}
    for backend in BACKENDS:
        runs[backend] = engine.run(
            RMAT, 0, engine.make_strategy(strategy), mode=mode, op=opname,
            backend=backend)
    _assert_parity(f"{strategy}/{opname}/{mode}", runs["xla"],
                   runs["pallas"])
    assert runs["pallas"].edges_relaxed > 0


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("strategy", ALL_STRATEGIES)
def test_backend_parity_reach_count_dag(strategy, mode):
    """The additive monoid on its convergence domain: int32 sums fold
    associatively, so kernel tile order cannot show through."""
    runs = {}
    for backend in BACKENDS:
        runs[backend] = engine.run(
            DAG, 0, engine.make_strategy(strategy), mode=mode,
            op="reach_count", backend=backend)
    _assert_parity(f"{strategy}/reach_count/{mode}", runs["xla"],
                   runs["pallas"])


@pytest.mark.parametrize("mode", MODES)
def test_backend_parity_hp_big_branch(mode):
    """HP's large-frontier branch (MDT tile loop + cursor-aware WD tail)
    never trips at the default threshold on a small graph — force it."""
    kw = dict(switch_threshold=4, mdt=3)
    xla = engine.run(RMAT, 0, engine.make_strategy("HP", **kw), mode=mode)
    pallas = engine.run(RMAT, 0, engine.make_strategy("HP", **kw),
                        mode=mode, backend="pallas")
    _assert_parity(f"HP-big/{mode}", xla, pallas)


@pytest.mark.parametrize("mode", MODES)
def test_backend_parity_ad_kernel_schedule(mode):
    """AD must pick the same kernel sequence under both backends (the
    selector consumes frontier statistics, which parity preserves)."""
    sx = engine.make_strategy("AD", small_frontier=8)
    sp = engine.make_strategy("AD", small_frontier=8)
    xla = engine.run(RMAT, 0, sx, mode=mode)
    pallas = engine.run(RMAT, 0, sp, mode=mode, backend="pallas")
    _assert_parity(f"AD/{mode}", xla, pallas)
    assert xla.kernel_counts == pallas.kernel_counts
    assert len(xla.kernel_counts) >= 2     # the schedule actually switched


def test_backend_parity_unchunked_ep_push():
    """Unchunked EP consumes the *per-lane* improve flags for its
    duplicate-push worklist — the Pallas kernel's third output."""
    xla = engine.run(RMAT, 0, engine.make_strategy("EP", chunked=False))
    pallas = engine.run(RMAT, 0, engine.make_strategy("EP", chunked=False),
                        backend="pallas")
    _assert_parity("EP-unchunked", xla, pallas)


# ---------------------------------------------------------------------------
# batched engine + custom seeding + oracle spot checks
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
def test_backend_parity_batch(mode):
    sources = [0, 3, 17]
    xla = engine.run_batch(ROAD, sources, mode=mode)
    pallas = engine.run_batch(ROAD, sources, mode=mode, backend="pallas")
    np.testing.assert_array_equal(pallas.dist, xla.dist)
    assert pallas.iterations == xla.iterations
    assert pallas.edges_relaxed == xla.edges_relaxed
    assert pallas.backend == "pallas"


def test_backend_parity_cc_seeding():
    """engine.fixed_point custom seeding (every node active) through the
    pallas backend."""
    for mode in MODES:
        ref = connected_components(ROAD, strategy="WD", mode=mode)
        got = connected_components(ROAD, strategy="WD", mode=mode,
                                   backend="pallas")
        np.testing.assert_array_equal(got, ref)


def test_backend_pallas_matches_dijkstra_oracles():
    """Not just backend-vs-backend: the pallas path must equal the host
    oracles outright."""
    ref = engine.reference_distances(ROAD, 0)
    res = engine.run(ROAD, 0, engine.make_strategy("WD"), mode="fused",
                     backend="pallas")
    np.testing.assert_array_equal(res.dist, ref)
    wref = reference_widest(ROAD, 0)
    wres = widest_path(ROAD, 0, strategy="BS", backend="pallas")
    np.testing.assert_array_equal(wres.dist, wref)


# ---------------------------------------------------------------------------
# trace accounting: backend switches must not recompile the XLA path
# ---------------------------------------------------------------------------

def test_backend_switch_does_not_recompile_xla_path():
    g = ROAD
    # warm both backends for this (kernel, shape, op) signature
    engine.run(g, 0, engine.make_strategy("WD"), mode="fused")
    engine.run(g, 0, engine.make_strategy("WD"), mode="fused",
               backend="pallas")
    t_xla = fused.TRACE_COUNTS["WD"]
    t_pallas = fused.TRACE_COUNTS["pallas:WD"]
    assert t_pallas >= 1                   # pallas compiled separately...
    # ...and alternating backends reuses both cache entries
    r1 = engine.run(g, 0, engine.make_strategy("WD"), mode="fused",
                    backend="pallas")
    r2 = engine.run(g, 0, engine.make_strategy("WD"), mode="fused")
    r3 = engine.run(g, 0, engine.make_strategy("WD"), mode="fused",
                    backend="pallas")
    assert fused.TRACE_COUNTS["WD"] == t_xla, "backend switch recompiled XLA"
    assert fused.TRACE_COUNTS["pallas:WD"] == t_pallas, "pallas recompiled"
    assert r1.iterations == r2.iterations == r3.iterations > 1


def test_backend_pallas_single_dispatch():
    """The fused one-dispatch-per-traversal claim holds per backend."""
    engine.run(ROAD, 0, engine.make_strategy("BS"), mode="fused",
               backend="pallas")                        # warm-up
    d0 = fused.DISPATCH_COUNTS["pallas:BS"]
    res = engine.run(ROAD, 0, engine.make_strategy("BS"), mode="fused",
                     backend="pallas")
    assert res.iterations > 1
    assert fused.DISPATCH_COUNTS["pallas:BS"] == d0 + 1


# ---------------------------------------------------------------------------
# gating + validation
# ---------------------------------------------------------------------------

def test_builtin_strategies_declare_pallas_backend():
    for name in ALL_STRATEGIES:
        assert PALLAS_BACKEND in strategy_capabilities(name), name


def test_default_capabilities_exclude_pallas_backend():
    """A plain third-party StrategyBase subclass is XLA-only until it
    declares otherwise — the registry gate engine.run enforces."""

    class HostOnly(StrategyBase):
        name = "host-only-test"

    assert PALLAS_BACKEND not in HostOnly.capabilities
    with pytest.raises(ValueError, match="pallas_backend"):
        engine.run(RMAT, 0, HostOnly(), backend="pallas")


def test_pre_backend_strategy_still_runs_on_xla_path():
    """Regression: a third-party strategy written against the
    pre-backend ``iterate`` signature (no ``backend`` kwarg) must keep
    running unchanged under the default backend — the gate's whole
    point is that XLA-only strategies need no code change."""
    from repro.core.strategies import bs_relax
    from repro.core.worklist import bucket, compact_mask

    class OldSignature(StrategyBase):
        name = "old-signature-test"

        def iterate(self, g, dist, updated_mask, count, *,
                    op, record_degrees=False):      # no backend kwarg
            cap = bucket(count)
            frontier = compact_mask(updated_mask, cap)
            dist, new_mask = bs_relax(g, dist, frontier, cap=cap, op=op)
            from repro.core.strategies import IterStats
            return dist, new_mask, IterStats(frontier_size=int(count),
                                             edges_processed=0)

    res = engine.run(ROAD, 0, OldSignature())       # must not TypeError
    ref = engine.run(ROAD, 0, engine.make_strategy("BS"))
    np.testing.assert_array_equal(res.dist, ref.dist)
    # and engine.fixed_point's stepped loop takes the same path
    labels, _, _ = engine.fixed_point(
        ROAD, OldSignature(),
        lambda n: (jnp.arange(n, dtype=jnp.int32),
                   jnp.ones((n,), jnp.bool_)),
        op="min_label")
    ref_labels = connected_components(ROAD, strategy="BS")
    np.testing.assert_array_equal(labels, ref_labels)


def test_backend_validation_errors():
    with pytest.raises(ValueError, match="backend"):
        engine.run(RMAT, 0, engine.make_strategy("WD"), backend="cuda")
    with pytest.raises(ValueError, match="backend"):
        engine.run_batch(RMAT, [0], backend="warp")


def test_pallas_composes_with_shards():
    """Regression for the old gate: ``backend="pallas"`` + ``shards=``
    used to raise 'single-device'; the per-shard Pallas lowering with
    the epilogue-fused ghost combine now runs and stays bit-identical
    (docs/backends.md#sharded-pallas-the-fused-ghost-combine).  The
    8-device matrix lives in tests/test_sharded.py; this in-process
    check covers whatever width the host has (>= 1)."""
    single = engine.run(ROAD, 0, engine.make_strategy("WD"), mode="fused",
                        backend="pallas")
    sharded = engine.run(ROAD, 0, engine.make_strategy("WD"), mode="fused",
                         shards=1, backend="pallas")
    np.testing.assert_array_equal(sharded.dist, single.dist)
    assert sharded.iterations == single.iterations
    assert sharded.edges_relaxed == single.edges_relaxed
    assert sharded.backend == "pallas" and sharded.shards == 1

    bs = engine.run_batch(ROAD, [0, 5], mode="fused", backend="pallas")
    bh = engine.run_batch(ROAD, [0, 5], mode="fused", shards=1,
                          backend="pallas")
    np.testing.assert_array_equal(bh.dist, bs.dist)
    assert bh.iterations == bs.iterations
    assert bh.edges_relaxed == bs.edges_relaxed
    assert bh.backend == "pallas"


def test_backend_recorded_on_results():
    res = engine.run(ROAD, 0, engine.make_strategy("WD"))
    assert res.backend == "xla"
    res = engine.run(ROAD, 0, engine.make_strategy("WD"), backend="pallas")
    assert res.backend == "pallas"
