"""Reduce a profiler trace of traced queries to device numbers.

The harness wraps the traced sub-window in one host span,
:data:`WINDOW_SPAN`, and the loop names its host work with spans of its
own, the loop module's ``HOST_SPANS`` (``query`` and ``tally`` for the
closed loop).  From the ``.xplane.pb`` that
``jax.profiler`` writes, :func:`reduce` takes

* ``busy_s``: the union of the intervals in which a device operation
  ran (the ``XLA Ops`` line of each TPU plane), clipped to the window
  and averaged over the chips that ran any;
* ``window_s``: the length of the window span;
* ``idle_share``: ``1 - busy_s / window_s``;
* ``device_ops``: the ten operations with the most device self time
  (an enclosing loop or conditional less the operations inside it),
  named by :func:`op_name`;
* ``idle_gaps``: the ten longest gaps between busy intervals, each
  named by the innermost of the loop's host spans that covers its
  middle (``host`` where none does).

Only ``jax.profiler.ProfileData`` is used to read the file.
"""

from __future__ import annotations

import re
from pathlib import Path

WINDOW_SPAN = "traced_window"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
TOP = 10


def load(log_dir: str):
    """The ``ProfileData`` of the one trace under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    return ProfileData.from_file(str(paths[0]))


def union(intervals) -> list:
    """Sorted, merged ``[start, end]`` pairs of ``intervals``."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _host_spans(pd, host_spans) -> list:
    """``(start_ns, end_ns, name)`` of the window span and of the host
    spans named ``host_spans``."""
    names = set(host_spans) | {WINDOW_SPAN}
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name))
    return out


def _device_ops(pd) -> dict:
    """``{plane name: [(start_ns, end_ns, op name)]}`` of TPU planes."""
    out = {}
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        ops = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
               for line in plane.lines if line.name == OPS_LINE
               for ev in line.events]
        if ops:
            out[plane.name] = ops
    return out


_OPCODE = re.compile(r"[\]})] ([a-z][a-z0-9_-]*)\(")


_LAYOUT = re.compile(r"\{[^{}]*\}")
_KIND = re.compile(r"kind=k(\w+)")


def op_name(text: str) -> str:
    """A short name for an HLO instruction's text: the instruction, its
    result's shape without layout (``tuple`` for a tuple) and its opcode,
    with a fusion's kind, as ``"fusion.12 s32[1048576] (fusion Loop)"``.
    Names that are not instructions are kept as they are."""
    head, eq, rest = text.partition(" = ")
    if not eq:
        return text
    m = _OPCODE.search(rest)
    if not m:
        return head.lstrip("%")
    shape = rest[:m.start() + 1].strip()
    shape = "tuple" if shape.startswith("(") else _LAYOUT.sub("", shape)
    kind = _KIND.search(rest[m.end():])
    op = m.group(1) + (f" {kind.group(1)}" if kind else "")
    return f"{head.lstrip('%')} {shape} ({op})"


def self_times(events) -> list:
    """``[(name, self ns)]`` of ``(start, end, name)`` events on one line,
    where an operation (a loop, a conditional) encloses the operations it
    runs: each gets its duration less that of the events directly inside
    it, so no time is counted twice."""
    out = []
    stack: list = []                  # [index into out, end]
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            parent = stack[-1][0]
            out[parent][1] -= min(e, stack[-1][1]) - s
        out.append([name, e - s])
        stack.append((len(out) - 1, e))
    return [(name, t) for name, t in out]


def _label(t: float, spans) -> str:
    """Name of the shortest host span covering ``t``."""
    best = None
    for s, e, name in spans:
        if name != WINDOW_SPAN and s <= t <= e and (
                best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "host"


def reduce(pd, host_spans) -> dict:
    """The numbers above, from a trace holding one :data:`WINDOW_SPAN`;
    idle gaps are named by the spans ``host_spans``."""
    spans = _host_spans(pd, host_spans)
    windows = [(s, e) for s, e, name in spans if name == WINDOW_SPAN]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN!r} span, found "
                           f"{len(windows)}")
    w0, w1 = windows[0]
    devices = _device_ops(pd)
    if not devices:
        raise RuntimeError("the trace holds no TPU operation")
    busy = {}
    op_time: dict = {}
    for plane, ops in devices.items():
        clipped = [(max(s, w0), min(e, w1), name) for s, e, name in ops
                   if e > w0 and s < w1]
        busy[plane] = union((s, e) for s, e, _ in clipped)
        for name, t in self_times(clipped):
            key = op_name(name)
            op_time[key] = op_time.get(key, 0.0) + t
    ndev = len(devices)
    busy_ns = sum(e - s for ivs in busy.values() for s, e in ivs) / ndev
    window_ns = w1 - w0
    first = busy[sorted(busy)[0]]
    edges = [w0] + [x for s, e in first for x in (s, e)] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": busy_ns / 1e9,
        "window_s": window_ns / 1e9,
        "idle_share": 1.0 - busy_ns / window_ns if window_ns > 0 else None,
        "device_ops": [[name, t / ndev / 1e9] for name, t in ops],
        "idle_gaps": [[_label((s + e) / 2, spans), (e - s) / 1e9]
                      for s, e in gaps[:TOP]],
        "devices": ndev,
    }
