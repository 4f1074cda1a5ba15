"""The trace reduction, on synthetic events and on a trace recorded on
one v5e (``bench/testdata``: one BFS of a scale-7 Kronecker graph,
wrapped in the harness's spans)."""

from pathlib import Path

import pytest

from bench import harness, tracing

TRACE_DIR = Path(__file__).resolve().parents[1] / "testdata" / "trace_v5e_bfs"
SPANS = harness.load_loop("closed_one_client").HOST_SPANS


def test_union_merges_overlaps():
    assert tracing.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [[0, 3],
                                                               [5, 9]]


def test_self_times_subtract_enclosed_ops():
    events = [(0, 100, "loop"), (10, 30, "cond"), (12, 20, "fusion"),
              (40, 50, "fusion"), (200, 210, "copy")]
    assert tracing.self_times(events) == [
        ("loop", 70), ("cond", 12), ("fusion", 8), ("fusion", 10),
        ("copy", 10)]


def test_op_names():
    assert tracing.op_name("%while.131 = (s32[]{:T(128)}, s32[4]{0}) "
                           "while((s32[]{:T(128)}) %t), body=%b") == \
        "while.131 tuple (while)"
    assert tracing.op_name("%dynamic_slice.1 = s32[4096]{0:T(1024)} "
                           "dynamic-slice(s32[4097]{0} %a)") == \
        "dynamic_slice.1 s32[4096] (dynamic-slice)"
    assert tracing.op_name("%fusion.113 = s32[1048576]{0:T(1024)S(1)} "
                           "fusion(s32[4]{0} %p), kind=kLoop, "
                           "calls=%fused_computation.9") == \
        "fusion.113 s32[1048576] (fusion Loop)"
    assert tracing.op_name("jit_subtract(1413)") == "jit_subtract(1413)"


@pytest.fixture(scope="module")
def recorded():
    pd = tracing.load(str(TRACE_DIR))
    return pd, tracing.reduce(pd, SPANS)


def test_recorded_trace_numbers(recorded):
    pd, r = recorded
    assert r["devices"] == 1
    # as first read from this file; a change to the reduction shows here
    assert r["busy_s"] == pytest.approx(0.002141982, rel=1e-9)
    assert r["window_s"] == pytest.approx(0.021963549, rel=1e-9)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["idle_share"] == pytest.approx(1 - r["busy_s"] / r["window_s"])
    # an independent count: a sweep over the op line's start and end
    # points, counting the window time in which at least one op is open
    spans = tracing._host_spans(pd, SPANS)
    w0, w1 = [(s, e) for s, e, n in spans if n == tracing.WINDOW_SPAN][0]
    ops = tracing._device_ops(pd)["/device:TPU:0"]
    marks = sorted([(min(max(s, w0), w1), 1) for s, e, _ in ops]
                   + [(min(max(e, w0), w1), -1) for s, e, _ in ops])
    covered, depth, last = 0.0, 0, w0
    for t, step in marks:
        if depth > 0:
            covered += t - last
        depth += step
        last = t
    assert r["busy_s"] == pytest.approx(covered / 1e9, rel=1e-6)


def test_recorded_trace_breakdown(recorded):
    _, r = recorded
    ops = r["device_ops"]
    assert 1 <= len(ops) <= tracing.TOP
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    assert all(not name.startswith("%") and len(name) < 120
               for name, _ in ops)
    # self times never exceed the busy time they are part of
    assert sum(t for _, t in ops) <= r["busy_s"] * (1 + 1e-9)
    gaps = r["idle_gaps"]
    assert 1 <= len(gaps) <= tracing.TOP
    assert {name for name, _ in gaps} <= {"query", "tally", "host"}
    assert "query" in {name for name, _ in gaps}
    assert sum(t for _, t in gaps) <= r["window_s"] - r["busy_s"] + 1e-9
