"""The control of the comparison that decides ``correct``.

The configurations state exact int32 distances.  A control is the plain
reference put in the program's place with that guarantee broken, run
through the whole harness; the run must then come out not correct.

``bf16``    Bellman-Ford over the benchmark's arcs with every distance
            held in bfloat16, the step below int32 that would tempt a
            later change to halve the bytes of the distance vector
            (each sum rounds to bfloat16, as a bf16 relax would).  It
            departs from exact only above 256: the road grid's
            distances run to the thousands.
``fp8``     the same with float8 (e4m3) distances, a quarter of the
            bytes: exact only up to 16, so it departs on Graph500 SSSP,
            whose distances stay below 256, where bfloat16 is exact.
``short``   the exact reference stopped one round before its fixed
            point: nodes at the largest finite distance stay unreached.
            It stands in where no lower precision departs from exact:
            Graph500 BFS levels stay below 16, exact in float8 and int8.

    python3 bench/control.py --workload <cell> --kind fp8 \\
        --seeds 1,2,3 --seconds 20

runs the cell once per seed with the control in the program's place
and prints each run's checks and one JSON line per seed.  The
benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

if __name__ == "__main__":
    _ROOT = Path(__file__).resolve().parent.parent
    sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from bench import harness, reference  # noqa: E402


def _csr(src, dst, wt, n):
    order = np.argsort(src, kind="stable")
    row_ptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=row_ptr[1:])
    w = (np.ones(len(src), np.float32) if wt is None
         else np.asarray(wt, np.float32)[order])
    return row_ptr, np.asarray(dst)[order], w


def _low(kind: str):
    import ml_dtypes
    return {"bf16": ml_dtypes.bfloat16, "fp8": ml_dtypes.float8_e4m3fn}[kind]


def lowp_distances(row_ptr, col, w, n, source, dtype) -> np.ndarray:
    """Frontier Bellman-Ford from ``source`` with distances rounded to
    ``dtype`` after every add; int32 out, UNREACHED where unreached."""
    d = np.full(n, np.inf, np.float32)
    d[source] = 0.0
    frontier = np.array([source], np.int64)
    while frontier.size:
        lo, hi = row_ptr[frontier], row_ptr[frontier + 1]
        cnt = hi - lo
        idx = np.repeat(lo - np.cumsum(cnt) + cnt, cnt) + np.arange(cnt.sum())
        cand = (np.repeat(d[frontier], cnt) + w[idx]).astype(dtype).astype(
            np.float32)
        v = col[idx]
        better = cand < d[v]
        v, cand = v[better], cand[better]
        np.minimum.at(d, v, cand)
        frontier = np.unique(v)
    out = np.full(n, reference.UNREACHED, np.int64)
    fin = np.isfinite(d)
    out[fin] = d[fin]
    return out.astype(np.int32)


def factory(kind: str):
    """An ``entry_factory`` for :func:`bench.harness.run_workload`."""
    def make(src, dst, wt, n):
        if kind in ("bf16", "fp8"):
            csr, dtype = _csr(src, dst, wt, n), _low(kind)

            def dist_of(root):
                return lowp_distances(*csr, n, root, dtype)
        elif kind == "short":
            ref = reference.Reference(src, dst, wt, n)

            def dist_of(root):
                d = ref.distances(root).copy()
                fin = d < reference.UNREACHED
                d[fin & (d == d[fin].max())] = reference.UNREACHED
                return d
        else:
            raise ValueError(f"unknown control {kind!r}")

        def entry(graph, root, **_):
            return SimpleNamespace(dist=dist_of(int(root)), iterations=0,
                                   edges_relaxed=0)
        return entry
    return make


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description="Run a cell with the control "
                                 "in the program's place")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--kind", choices=("bf16", "fp8", "short"),
                    required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    c = harness.cell(args.workload)
    dev = harness.require_chips(c.workload["chips"])
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run_workload(args.workload, seed, args.seconds, False,
                                   t_start=t_start, device=dev,
                                   entry_factory=factory(args.kind))
        print(json.dumps({"seed": seed, "kind": args.kind,
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
