"""Device time by program scope, and the program's host spans, in a
profiler trace of traced queries.

The fused loop names its work with ``jax.named_scope``
(``repro.core.fused``): inside a kernel's scope (``AD``, ``BS``, ``WD``,
``HP``, ``EP``, ``NS``), ``frontier`` for the ``[N]``-wide passes over the
frontier, ``lanemap`` for mapping lanes to edges, ``relax`` for the relax
of one block.  ``engine.run`` marks its host work with the spans
:data:`PROGRAM_SPANS`.  :func:`reduce` takes a trace that
:func:`bench.tracing.reduce` reads and adds

* ``scopes``: device self time in seconds per scope, each operation
  going to the innermost of :data:`SCOPES` in its ``tf_op`` name stack
  (:mod:`bench.xplane`), or to ``other``;
* ``kernels``: the same time split by the innermost kernel scope (AD's
  branch, or ``AD`` for its own choice), or ``other``;
* ``device_ops``: :func:`bench.tracing.reduce`'s entries, in the same
  order with the same times, each name followed by ``@`` and the scopes
  in its name stack (``fusion.12 s32[1048576] (fusion Loop) @AD/WD/
  lanemap``) where it has any;
* ``idle_gaps``: the same gaps, each named by the innermost host span
  covering its middle, the loop's or the program's.

A trace of a program without scopes has all its time under ``other``.

    python3 bench/scopes.py <trace directory> [<loop>]

prints the reduction of the one trace under the directory as JSON,
naming idle gaps by the ``HOST_SPANS`` of ``bench/loops/<loop>.py``
(``closed_one_client`` by default).
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

if __name__ == "__main__":
    sys.path[:0] = [str(Path(__file__).resolve().parent.parent)]

from bench import tracing, xplane  # noqa: E402

#: names of the program's scopes, kernels first, then the inner scopes
KERNELS = ("AD", "BS", "WD", "HP", "EP", "NS")
SCOPES = KERNELS + ("frontier", "lanemap", "relax")
OTHER = "other"
#: the host spans of ``repro.core.engine.run`` and the fused call
PROGRAM_SPANS = ("engine.setup", "engine.dispatch", "engine.wait",
                 "engine.readback")


def scope_path(tf_op: str) -> list:
    """The program's scope names in a ``tf_op`` name stack, outer
    first."""
    return [part for part in xplane.name_stack(tf_op) if part in SCOPES]


def trace_file(log_dir: str) -> Path:
    paths = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def _spans(pd, names) -> list:
    """``(start_ns, end_ns, name)`` of the host events named ``names``."""
    return [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name in names]


def reduce(pd, ops: dict, host_spans) -> dict:
    """:func:`bench.tracing.reduce` of ``pd`` with the keys above;
    ``ops`` is :func:`bench.xplane.tf_ops` of the same file and
    ``host_spans`` the loop's span names."""
    base = tracing.reduce(pd, host_spans)
    spans = _spans(pd, set(host_spans) | {tracing.WINDOW_SPAN}
                   | set(PROGRAM_SPANS))
    (w0, w1), = [(s, e) for s, e, name in spans
                 if name == tracing.WINDOW_SPAN]
    devices = tracing._device_ops(pd)
    scopes = dict.fromkeys(SCOPES + (OTHER,), 0.0)
    kernels = dict.fromkeys(KERNELS + (OTHER,), 0.0)
    paths: dict = {}                      # short name -> (ns, scope path)
    busy = {}
    for plane, events in devices.items():
        clipped = [(max(s, w0), min(e, w1), name) for s, e, name in events
                   if e > w0 and s < w1]
        busy[plane] = tracing.union((s, e) for s, e, _ in clipped)
        stacks = ops.get(plane, {})
        for name, t in tracing.self_times(clipped):
            path = scope_path(stacks.get(name, ""))
            scopes[path[-1] if path else OTHER] += t
            outer = [p for p in path if p in KERNELS]
            kernels[outer[-1] if outer else OTHER] += t
            key = tracing.op_name(name)
            best = paths.get(key)
            if best is None or t > best[0]:
                paths[key] = (t, "/".join(path))
    ndev = len(devices)
    first = busy[sorted(busy)[0]]
    edges = [w0] + [x for s, e in first for x in (s, e)] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = dict(base)
    out["scopes"] = {k: v / ndev / 1e9 for k, v in scopes.items()}
    out["kernels"] = {k: v / ndev / 1e9 for k, v in kernels.items()}
    out["device_ops"] = [
        [f"{name} @{paths[name][1]}" if paths.get(name, (0, ""))[1]
         else name, t] for name, t in base["device_ops"]]
    out["idle_gaps"] = [[tracing._label((s + e) / 2, spans), (e - s) / 1e9]
                        for s, e in gaps[:tracing.TOP]]
    return out


def reduce_dir(log_dir: str, host_spans) -> dict:
    """:func:`reduce` of the one trace under ``log_dir``."""
    path = trace_file(log_dir)
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(str(path)), xplane.tf_ops(path),
                  host_spans)


if __name__ == "__main__":
    if len(sys.argv) not in (2, 3):
        raise SystemExit("usage: python3 bench/scopes.py <trace directory> "
                         "[<loop>]")
    from bench import harness
    loop = harness.load_loop(sys.argv[2] if len(sys.argv) == 3
                             else harness.DEFAULT_LOOP)
    print(json.dumps(reduce_dir(sys.argv[1],
                                getattr(loop, "HOST_SPANS", ()))))
