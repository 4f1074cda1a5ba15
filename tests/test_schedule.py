"""Tests for the first-class Schedule layer and the measured cost model.

Four contracts:

* **golden parity** — the default :class:`~repro.core.schedule.Schedule`
  IS the pre-extraction constants: every strategy × mode reproduces the
  pre-refactor ``(iterations, edges_relaxed, crc32(dist))`` signatures
  captured before the extraction, bit for bit;
* **serialization** — schedules round-trip losslessly through
  dict/JSON (the costmodel calibration cache keys on the JSON form);
* **overrides** — historical constructor kwargs
  (``make_strategy("HP", switch_threshold=4, mdt=3)``) compose with and
  take precedence over a supplied ``schedule=``;
* **cost model v2** — the measured per-kernel model calibrates, caches,
  refines online, picks only feasible Pallas block shapes, and its
  host/device selectors agree (AD stepped ≡ AD fused under a measured
  model).
"""

import math
import os
import zlib

import numpy as np
import pytest

from repro.core import costmodel, engine, fused
from repro.core.graph import CSRGraph, INF
from repro.core.schedule import (DEFAULT_SCHEDULE, LANE, SCHEDULE_FIELDS,
                                 Schedule, default_schedule,
                                 resolve_overrides)
from repro.core.strategies import STRATEGIES, choose_kernel, make_strategy
from repro.data import rmat_graph, road_grid_graph
from repro.kernels import relax

ALL = ["BS", "EP", "WD", "NS", "HP", "AD"]


def graphs():
    return {
        "rmat": rmat_graph(scale=7, edge_factor=6, weighted=True, seed=7),
        "road": road_grid_graph(side=24, weighted=True, seed=3),
    }


GRAPHS = graphs()

#: pre-refactor signatures, captured on the constants the default
#: Schedule now carries: (iterations, edges_relaxed, crc32(dist bytes)).
#: Identical for stepped and fused (the repo-wide parity contract).
GOLDEN = {
    ("rmat", "BS"): (7, 1219, 2243746589),
    ("rmat", "EP"): (9, 1375, 2243746589),
    ("rmat", "WD"): (9, 1375, 2243746589),
    ("rmat", "NS"): (9, 1350, 2243746589),
    ("rmat", "HP"): (9, 1375, 2243746589),
    ("rmat", "AD"): (7, 1229, 2243746589),
    ("road", "BS"): (37, 5337, 1508505819),
    ("road", "EP"): (37, 6422, 1508505819),
    ("road", "WD"): (37, 6422, 1508505819),
    ("road", "NS"): (37, 5299, 1508505819),
    ("road", "HP"): (37, 6422, 1508505819),
    ("road", "AD"): (37, 5337, 1508505819),
}


def _sig(res):
    return (res.iterations, res.edges_relaxed,
            zlib.crc32(np.asarray(res.dist).tobytes()))


# ---------------------------------------------------------------------------
# golden parity: default Schedule == pre-extraction constants
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("gname", sorted(GRAPHS))
@pytest.mark.parametrize("strategy", ALL)
@pytest.mark.parametrize("mode", ["stepped", "fused"])
def test_default_schedule_matches_pre_refactor_goldens(gname, strategy,
                                                       mode):
    res = engine.run(GRAPHS[gname], 0, make_strategy(strategy), mode=mode)
    assert _sig(res) == GOLDEN[(gname, strategy)]


@pytest.mark.parametrize("strategy", ["BS", "WD", "NS", "HP", "AD"])
def test_explicit_default_schedule_is_a_noop(strategy):
    g = GRAPHS["rmat"]
    implicit = engine.run(g, 0, make_strategy(strategy))
    explicit = engine.run(g, 0, make_strategy(
        strategy, schedule=Schedule()))
    assert _sig(implicit) == _sig(explicit)


def test_run_result_reports_resolved_work_schedule():
    g = GRAPHS["rmat"]
    res = engine.run(g, 0, make_strategy("HP"))
    assert isinstance(res.work_schedule, Schedule)
    # HP resolves MDT at setup — the reported schedule is concrete
    assert res.work_schedule.mdt is not None
    # the work-ordering string is a separate axis and keeps its name
    assert res.schedule == "bsp"


def test_non_default_min_bucket_is_bit_identical():
    g = GRAPHS["rmat"]
    base = engine.run(g, 0, make_strategy("WD"))
    wide = engine.run(g, 0, make_strategy(
        "WD", schedule=Schedule(min_bucket=1024)))
    assert _sig(base) == _sig(wide)


@pytest.mark.parametrize("mode", ["stepped", "fused"])
def test_non_default_tile_shape_is_bit_identical_on_pallas(mode):
    g = road_grid_graph(side=16, weighted=True, seed=5)
    base = engine.run(g, 0, make_strategy("WD"), mode=mode,
                      backend="pallas")
    tiled = engine.run(g, 0, make_strategy(
        "WD", schedule=Schedule(tile_c=256, chunk=256)), mode=mode,
        backend="pallas")
    assert _sig(base) == _sig(tiled)


def test_equal_schedules_share_one_compiled_executable():
    g = road_grid_graph(side=12, weighted=True, seed=2)
    s1 = make_strategy("WD", schedule=Schedule(min_bucket=512))
    s2 = make_strategy("WD", schedule=Schedule(min_bucket=512))
    assert s1.schedule == s2.schedule
    assert hash(s1.schedule) == hash(s2.schedule)
    engine.run(g, 0, s1, mode="fused")
    before = fused._fixed_point._cache_size()
    engine.run(g, 0, s2, mode="fused")
    assert fused._fixed_point._cache_size() == before


# ---------------------------------------------------------------------------
# serialization and validation
# ---------------------------------------------------------------------------

def test_every_registered_strategy_schedule_round_trips():
    for name in sorted(STRATEGIES):
        sched = default_schedule(name)
        via_json = Schedule.from_json(sched.to_json())
        via_dict = Schedule.from_dict(sched.to_dict())
        assert via_json == sched and hash(via_json) == hash(sched)
        assert via_dict == sched


def test_modified_schedules_round_trip():
    for sched in (Schedule(mdt=3, delta=16),
                  Schedule(min_bucket=1024, tile_c=256, chunk=512),
                  Schedule(imbalance_threshold=3.7,
                           hp_edges_threshold=1 << 12)):
        assert Schedule.from_json(sched.to_json()) == sched


def test_imbalance_threshold_canonicalizes_to_float32():
    s = Schedule(imbalance_threshold=3.7)
    assert s.imbalance_threshold == float(np.float32(3.7))
    # canonical form survives the round trip unchanged
    assert Schedule.from_json(s.to_json()).imbalance_threshold == \
        s.imbalance_threshold


def test_from_dict_rejects_unknown_fields():
    with pytest.raises(ValueError, match="unknown Schedule fields"):
        Schedule.from_dict({"chunk_size": 256})


@pytest.mark.parametrize("bad", [
    dict(min_bucket=0), dict(min_bucket=300), dict(mdt=0), dict(delta=0),
    dict(tile_c=100), dict(chunk=64), dict(switch_threshold=-1),
    dict(min_bucket=True),
])
def test_invalid_schedules_are_rejected(bad):
    with pytest.raises(ValueError):
        Schedule(**bad)


def test_schedule_fields_cover_the_dataclass():
    assert set(SCHEDULE_FIELDS) == set(Schedule().to_dict())
    assert Schedule().tile == Schedule().tile_r * Schedule().tile_c
    assert LANE == relax.LANE if hasattr(relax, "LANE") else True


def test_resolved_makes_mdt_concrete():
    degrees = np.array([1, 1, 2, 40, 3], np.int32)
    auto = Schedule().resolved(degrees)
    assert auto.mdt is not None and auto.mdt >= 1
    pinned = Schedule(mdt=7).resolved(degrees)
    assert pinned.mdt == 7


# ---------------------------------------------------------------------------
# constructor-kwarg precedence
# ---------------------------------------------------------------------------

def test_historical_kwargs_still_work():
    hp = make_strategy("HP", switch_threshold=4, mdt=3)
    assert hp.schedule.switch_threshold == 4
    assert hp.schedule.mdt == 3


def test_explicit_kwarg_beats_supplied_schedule():
    sched = Schedule(switch_threshold=64, mdt=5)
    hp = make_strategy("HP", switch_threshold=4, schedule=sched)
    assert hp.schedule.switch_threshold == 4     # kwarg wins
    assert hp.schedule.mdt == 5                  # schedule preserved
    ns = make_strategy("NS", histogram_bins=7,
                       schedule=Schedule(histogram_bins=20))
    assert ns.schedule.histogram_bins == 7
    assert ns.histogram_bins == 7


def test_resolve_overrides_none_kwargs_are_transparent():
    sched = Schedule(switch_threshold=64)
    assert resolve_overrides("HP", sched, switch_threshold=None) is sched
    assert resolve_overrides("HP", None) == default_schedule("HP")


# ---------------------------------------------------------------------------
# heuristic hardening (degenerate frontiers)
# ---------------------------------------------------------------------------

def _isolated_graph(n=5):
    empty = np.array([], np.int64)
    return CSRGraph.from_edges(empty, empty, None, n)


def test_choose_kernel_degenerate_frontier_is_bs():
    assert choose_kernel(0, 0, 0, float("nan"), mdt=1) == "BS"
    assert choose_kernel(5, 0, 0, 0.0, mdt=1) == "BS"
    assert choose_kernel(0, 10, 3, 1.0, mdt=1) == "BS"


def test_choose_kernel_nonfinite_imbalance_is_clamped():
    # inf/NaN ratios (max_degree / zero-mean in float32) must behave as
    # "maximally skewed", never silently fail every comparison
    for imb in (float("inf"), float("nan")):
        pick = choose_kernel(4096, 1 << 16, 1 << 12, imb, mdt=4)
        assert pick == choose_kernel(4096, 1 << 16, 1 << 12, float("inf"),
                                     mdt=4)
        assert pick in ("BS", "WD", "HP")


@pytest.mark.parametrize("mode", ["stepped", "fused"])
def test_ad_on_all_isolated_nodes(mode):
    # regression: every node isolated — degree_sum == 0 on the very
    # first frontier, imbalance is 0/0; the run must settle the source
    # only, relax nothing, and never crash in the selector
    g = _isolated_graph()
    res = engine.run(g, 0, make_strategy("AD"), mode=mode)
    dist = np.asarray(res.dist)
    assert dist[0] == 0 and res.edges_relaxed == 0
    # every other node stays at the unreached sentinel (int32 INF here:
    # the edgeless graph is unweighted)
    assert np.all(dist[1:] == INF)


def test_ad_on_all_isolated_nodes_with_cost_model():
    g = _isolated_graph()
    model = costmodel.CostModel.fresh()
    res = engine.run(g, 0, make_strategy("AD", cost_model=model))
    assert res.edges_relaxed == 0
    assert model.choose(0, 0) == "BS"


# ---------------------------------------------------------------------------
# cost model v2
# ---------------------------------------------------------------------------

def _small_graph():
    return rmat_graph(scale=6, edge_factor=5, weighted=True, seed=11)


def test_costmodel_calibrate_and_cache(tmp_path):
    g = _small_graph()
    model, hit = costmodel.calibrate(g, cache_dir=str(tmp_path),
                                     repeats=1)
    assert not hit
    assert np.isfinite(model.coeffs).all()
    again, hit2 = costmodel.calibrate(g, cache_dir=str(tmp_path),
                                      repeats=1)
    assert hit2
    np.testing.assert_array_equal(model.coeffs, again.coeffs)
    # a different schedule keys a different cache entry
    _, hit3 = costmodel.calibrate(
        g, sched=Schedule(min_bucket=1024), cache_dir=str(tmp_path),
        repeats=1)
    assert not hit3


def test_costmodel_cache_is_keyed_on_the_device(tmp_path, monkeypatch):
    """A calibration timed on one device is never loaded on another:
    platform and device kind are part of the signature and the file
    name."""
    import jax
    g = _small_graph()
    sig = costmodel.graph_signature(g, "xla")
    dev = jax.devices()[0]
    assert (sig["platform"], sig["device_kind"]) == (dev.platform,
                                                     dev.device_kind)
    tpu = dict(sig, platform="tpu", device_kind="TPU v5 lite")
    path = costmodel.cache_path(str(tmp_path), tpu)
    assert path != costmodel.cache_path(str(tmp_path), sig)
    assert os.path.basename(path).startswith("costmodel-tpu-TPU-v5-lite-")
    # a calibration made here, planted under the chip's file name, is
    # re-measured rather than loaded there: its signature names this
    # device
    model, _ = costmodel.calibrate(g, cache_dir=str(tmp_path), repeats=1)
    model.save(path)
    monkeypatch.setattr(costmodel, "graph_signature", lambda *a, **k: tpu)
    _, hit = costmodel.calibrate(g, cache_dir=str(tmp_path), repeats=1)
    assert not hit


def test_costmodel_rejects_foreign_cache_payload():
    d = costmodel.CostModel.fresh().to_dict()
    d["version"] = 1
    with pytest.raises(ValueError):
        costmodel.CostModel.from_dict(d)


def test_costmodel_choose_is_predict_argmin():
    model = costmodel.CostModel.fresh()
    # seed each kernel with a distinct constant cost: WD cheapest
    for k, t in (("BS", 3e-3), ("WD", 1e-3), ("HP", 2e-3)):
        for _ in range(4):
            model.observe(k, 1000, 100, t)
    assert model.choose(100, 1000) == "WD"
    pred = model.predict(100, 1000)
    assert costmodel.KERNELS[int(np.argmin(pred))] == "WD"
    # degenerate frontiers bypass the argmin entirely
    assert model.choose(0, 0) == "BS"


def test_costmodel_observe_refines_recursively():
    model = costmodel.CostModel.fresh()
    rng = np.random.default_rng(3)
    for _ in range(32):
        ds = int(rng.integers(1, 1 << 14))
        cnt = int(rng.integers(1, 1 << 10))
        model.observe("BS", ds, cnt, 1e-6 + 2e-9 * ds + 5e-8 * cnt)
    a, b, c = model.coeffs[costmodel.KERNELS.index("BS")]
    assert b == pytest.approx(2e-9, rel=0.05)
    assert c == pytest.approx(5e-8, rel=0.05)
    # non-finite / negative samples are ignored, not fitted
    before = model.coeffs.copy()
    model.observe("BS", 10, 10, float("nan"))
    model.observe("BS", 10, 10, -1.0)
    np.testing.assert_array_equal(model.coeffs, before)


def test_kernel_order_matches_fused_switch_branches():
    assert costmodel.KERNELS == fused._AD_KERNEL_ORDER


@pytest.mark.parametrize("mode", ["stepped", "fused"])
def test_measured_ad_parity_and_kernel_lockstep(mode, tmp_path):
    g = _small_graph()
    model, _ = costmodel.calibrate(g, cache_dir=str(tmp_path), repeats=1)
    fixed = engine.run(g, 0, make_strategy("AD"), mode=mode)
    measured = engine.run(g, 0, make_strategy("AD", cost_model=model),
                          mode=mode)
    # measured selection may take a different path but must land on the
    # same fixed point
    np.testing.assert_array_equal(np.asarray(fixed.dist),
                                  np.asarray(measured.dist))


def test_measured_ad_host_device_selectors_agree(tmp_path):
    g = _small_graph()
    model, _ = costmodel.calibrate(g, cache_dir=str(tmp_path), repeats=1)
    stepped = engine.run(g, 0, make_strategy("AD", cost_model=model))
    fusedr = engine.run(g, 0, make_strategy("AD", cost_model=model),
                        mode="fused")
    assert _sig(stepped) == _sig(fusedr)
    # the stepped run's per-iteration picks are the model's argmin —
    # which is exactly what the device branch evaluates
    for st in stepped.iter_stats:
        count = int(st.frontier_size)
        degree_sum = int(st.edges_processed)
        assert st.kernel == model.choose(count, degree_sum)


def test_online_refinement_observes_real_iterations(tmp_path):
    g = _small_graph()
    model, _ = costmodel.calibrate(g, cache_dir=str(tmp_path), repeats=1)
    before = model.xtx.copy()
    engine.run(g, 0, make_strategy("AD", cost_model=model, online=True))
    assert not np.array_equal(model.xtx, before)


def test_pallas_block_candidates_respect_vmem_budget():
    g = _small_graph()
    cands = costmodel.pallas_block_candidates(g)
    assert cands, "no feasible Pallas block schedule for a tiny graph?"
    n = g.num_nodes
    for sched in cands:
        for kernel, kw in (("lanes", dict(n=n)),
                           ("wd", dict(n=n, f=n, e=g.num_edges))):
            blocks = relax.kernel_vmem_blocks(
                kernel, tile_r=sched.tile_r, tile_c=sched.tile_c,
                chunk=sched.chunk, **kw)
            assert sum(blocks.values()) <= relax.VMEM_BUDGET_BYTES
    # candidates are real schedules: bit-parity holds for any of them
    first = cands[0]
    base = engine.run(g, 0, make_strategy("WD"), backend="pallas")
    cand = engine.run(g, 0, make_strategy("WD", schedule=first),
                      backend="pallas")
    assert _sig(base) == _sig(cand)
