"""The ``tf_op`` reader and the reduction by program scope, on the trace
recorded on one v5e (``bench/testdata``, a program without scopes) and
on synthetic events."""

from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import harness, scopes, tracing, xplane

TRACE_DIR = Path(__file__).resolve().parents[1] / "testdata" / "trace_v5e_bfs"
SPANS = harness.load_loop("closed_one_client").HOST_SPANS


@pytest.fixture(scope="module")
def recorded():
    path = scopes.trace_file(str(TRACE_DIR))
    pd = tracing.load(str(TRACE_DIR))
    ops = xplane.tf_ops(path)
    return (pd, ops, tracing.reduce(pd, SPANS),
            scopes.reduce(pd, ops, SPANS))


def test_reader_maps_fusion_to_its_name_stack(recorded):
    _, ops, _, _ = recorded
    device = ops["/device:TPU:0"]
    (name,) = [n for n in device if n.startswith("%fusion.79 ")]
    assert device[name] == \
        "jit(_fixed_point)/while/body/cond/branch_0_fun/gather:"
    assert xplane.name_stack(device[name]) == [
        "jit(_fixed_point)", "while", "body", "cond", "branch_0_fun",
        "gather"]
    assert len(device) == 36
    # keyed by the names ProfileData gives the same events
    pd, _, _, _ = recorded
    events = tracing._device_ops(pd)["/device:TPU:0"]
    assert name in {n for _, _, n in events}


def test_reduction_keeps_every_existing_number(recorded):
    _, _, base, r = recorded
    for key in ("busy_s", "window_s", "idle_share", "devices"):
        assert r[key] == base[key]
    assert [t for _, t in r["device_ops"]] == \
        [t for _, t in base["device_ops"]]
    assert [n.split(" @")[0] for n, _ in r["device_ops"]] == \
        [n for n, _ in base["device_ops"]]
    assert [t for _, t in r["idle_gaps"]] == \
        [t for _, t in base["idle_gaps"]]
    # no program span in this trace: the same names as before
    assert [n for n, _ in r["idle_gaps"]] == \
        [n for n, _ in base["idle_gaps"]]


def test_program_without_scopes_reads_other(recorded):
    _, _, base, r = recorded
    assert set(r["scopes"]) == set(scopes.SCOPES) | {scopes.OTHER}
    assert all(v == 0.0 for k, v in r["scopes"].items()
               if k != scopes.OTHER)
    assert 0 < r["scopes"][scopes.OTHER] <= base["busy_s"] * (1 + 1e-9)
    assert all(" @" not in n for n, _ in r["device_ops"])
    assert r["kernels"][scopes.OTHER] == r["scopes"][scopes.OTHER]


@pytest.mark.parametrize("tf_op,path", [
    ("jit(_fixed_point)/while/body/AD/cond/branch_1_fun/WD/lanemap/"
     "gather:", ["AD", "WD", "lanemap"]),
    ("jit(_fixed_point)/while/body/AD/frontier/reduce_sum:",
     ["AD", "frontier"]),
    ("jit(_fixed_point)/while/body/WD/relax/scatter-min:Scatter",
     ["WD", "relax"]),
    ("jit(_fixed_point)/while/body/cond/branch_0_fun/gather:", []),
    ("", []),
])
def test_scope_path(tf_op, path):
    assert scopes.scope_path(tf_op) == path


def _event(start, end, name):
    return NS(start_ns=start, duration_ns=end - start, name=name)


def _fake_trace():
    host = NS(name="/host:CPU", lines=[NS(name="spans", events=[
        _event(0, 100, tracing.WINDOW_SPAN), _event(10, 90, "query"),
        _event(12, 18, "engine.setup"), _event(19, 31, "engine.dispatch"),
        _event(69, 81, "engine.readback"), _event(92, 96, "tally")])])
    fusion = ("%fusion.{} = s32[4]{{0}} fusion(s32[4]{{0}} %p), "
              "kind=kLoop, calls=%c")
    device = NS(name="/device:TPU:0", lines=[NS(name=tracing.OPS_LINE,
                                                events=[
        _event(0, 15, fusion.format(1)), _event(30, 70, fusion.format(2)),
        _event(80, 90, fusion.format(3)), _event(95, 100,
                                                 fusion.format(4))])])
    ops = {"/device:TPU:0": {
        fusion.format(1): "jit(_fixed_point)/while/body/AD/frontier/x:",
        fusion.format(2): "jit(_fixed_point)/while/body/AD/cond/"
                          "branch_1_fun/WD/relax/scatter-min:",
        fusion.format(3): "jit(_fixed_point)/while/body/AD/cond/"
                          "branch_1_fun/WD/lanemap/gather:"}}
    return NS(planes=[host, device]), ops


def test_synthetic_scopes_and_spans():
    pd, ops = _fake_trace()
    r = scopes.reduce(pd, ops, SPANS)
    assert r["scopes"]["frontier"] == pytest.approx(15e-9)
    assert r["scopes"]["relax"] == pytest.approx(40e-9)
    assert r["scopes"]["lanemap"] == pytest.approx(10e-9)
    assert r["scopes"][scopes.OTHER] == pytest.approx(5e-9)
    assert r["kernels"]["AD"] == pytest.approx(15e-9)
    assert r["kernels"]["WD"] == pytest.approx(50e-9)
    assert r["kernels"][scopes.OTHER] == pytest.approx(5e-9)
    assert r["busy_s"] == pytest.approx(70e-9)
    names = dict(r["device_ops"])
    assert names["fusion.2 s32[4] (fusion Loop) @AD/WD/relax"] == \
        pytest.approx(40e-9)
    assert "fusion.4 s32[4] (fusion Loop)" in names
    # gaps: 15-30 (middle 22.5, in engine.dispatch), 70-80 (readback),
    # 90-95 (tally)
    assert r["idle_gaps"] == [["engine.dispatch", pytest.approx(15e-9)],
                              ["engine.readback", pytest.approx(10e-9)],
                              ["tally", pytest.approx(5e-9)]]
    # the existing reduction names the same gaps by the harness's spans
    assert [n for n, _ in tracing.reduce(pd, SPANS)["idle_gaps"]] == \
        ["query", "query", "tally"]
