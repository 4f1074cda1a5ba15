"""Fig. 16 (extension): XLA vs Pallas relax-kernel backend, per strategy.

``backend="pallas"`` (docs/backends.md) routes every relax through the
fused scatter-combine kernels of ``repro.kernels.relax`` instead of the
XLA gather/scatter HLO pipeline.  This module measures both backends in
fused mode per strategy per graph family and reports MTEPS side by side
plus the pallas/xla ratio.

Every run is **parity-asserted** first: distances, iteration counts and
relaxed-edge totals must be bit-identical across backends (the
docs/backends.md contract) before any timing is recorded — a benchmark
that silently measured a diverging kernel would be worse than useless.

Caveat for reading the numbers on CPU: Pallas runs in **interpret
mode** here (the CI-testable path), which serializes the kernel grid in
the XLA emulator — the ratio column then measures interpret overhead,
not TPU kernel quality.  On a real TPU backend the same entry points
compile through Mosaic.  Graphs are sized below the main suite for the
same reason (grid serialization is O(lanes), and the parity signal is
scale-independent).

Every row carries a ``shards`` field.  The single-device matrix above
runs in-process (``shards=1``); a second **sharded** section re-runs WD
at ``shards=8`` (at most the visible devices) for both backends,
parity-asserted against the single-device run, so the artifact exposes
the full backend × shards axis the parity contract covers
(docs/backends.md).  On an accelerator it runs in this process; on the
CPU in a child process with 8 virtual devices
(:func:`benchmarks.common.sharded_rows`).
"""

from __future__ import annotations

import numpy as np

from benchmarks.common import (csv_line, fmt_rate, run_strategy,
                               safe_mteps, save_result, sharded_rows)
from repro.core import engine
from repro.data import rmat_graph, road_grid_graph

#: one power-law, one bounded-degree family (paper suite), scaled to the
#: interpret-mode budget — see module docstring
FIG16_GRAPHS = {
    "rmat": lambda: rmat_graph(scale=9, edge_factor=8, weighted=True,
                               seed=7),
    "road": lambda: road_grid_graph(side=24, weighted=True, seed=7),
}
#: the CSR strategies with Pallas relax lowerings exercised here (EP/NS
#: add memory/morph axes fig9-11 already cover; AD composes the other
#: three and reports its kernel schedule)
FIG16_STRATEGIES = ["BS", "WD", "HP", "AD"]
#: shard width for the sharded section (docs/backends.md
#: #sharded-pallas-the-fused-ghost-combine); WD only — fig15 owns the
#: shard-count sweep, this section prices the backend axis at width
FIG16_SHARDS = 8

def sharded_measure(max_shards: int) -> list:
    """WD backend pair at ``min(FIG16_SHARDS, max_shards)`` shards,
    parity-asserted against the single-device run; same row schema plus
    ``shards``."""
    shards = min(FIG16_SHARDS, max_shards)
    rows = []
    for gname, make in FIG16_GRAPHS.items():
        g = make()
        source = int(np.argmax(np.asarray(g.degrees)))
        base = engine.run(g, source, engine.make_strategy("WD"),
                          mode="fused")
        runs = {}
        for backend in ("xla", "pallas"):
            best = None
            for i in range(2):                 # warm-up (compile) + timed
                res = engine.run(g, source, engine.make_strategy("WD"),
                                 mode="fused", shards=shards,
                                 backend=backend)
                best = res if i else None
            tag = f"{gname}/{backend}"
            assert np.array_equal(best.dist, base.dist), tag
            assert best.iterations == base.iterations, tag
            assert best.edges_relaxed == base.edges_relaxed, tag
            runs[backend] = best
        xla, pallas = runs["xla"], runs["pallas"]
        rows.append({
            "graph": gname, "strategy": "WD", "shards": shards,
            "iterations": xla.iterations,
            "edges_relaxed": xla.edges_relaxed,
            "xla_s": xla.traversal_seconds,
            "pallas_s": pallas.traversal_seconds,
            "mteps_xla": safe_mteps(xla),
            "mteps_pallas": safe_mteps(pallas),
            "pallas_over_xla": (
                pallas.traversal_seconds / xla.traversal_seconds
                if xla.traversal_seconds > 0 else 0.0),
            "parity": "bit-identical",
        })
    return rows


def run(verbose: bool = True):
    rows = []
    for gname, make in FIG16_GRAPHS.items():
        g = make()
        for s in FIG16_STRATEGIES:
            xla = run_strategy(g, s, mode="fused", backend="xla",
                               repeats=1)
            pallas = run_strategy(g, s, mode="fused", backend="pallas",
                                  repeats=1)
            np.testing.assert_array_equal(
                pallas.dist, xla.dist,
                err_msg=f"pallas dist diverged for {s} on {gname}")
            assert pallas.iterations == xla.iterations, (
                f"pallas iterations diverged for {s} on {gname}")
            assert pallas.edges_relaxed == xla.edges_relaxed, (
                f"pallas edge total diverged for {s} on {gname}")
            rows.append({
                "graph": gname, "strategy": s, "shards": 1,
                "iterations": xla.iterations,
                "edges_relaxed": xla.edges_relaxed,
                "xla_s": xla.traversal_seconds,
                "pallas_s": pallas.traversal_seconds,
                "mteps_xla": safe_mteps(xla),
                "mteps_pallas": safe_mteps(pallas),
                "pallas_over_xla": (
                    pallas.traversal_seconds / xla.traversal_seconds
                    if xla.traversal_seconds > 0 else 0.0),
                "parity": "bit-identical",
            })

    rows.extend(sharded_rows("benchmarks.fig16_pallas",
                             "sharded_measure"))

    save_result("fig16_pallas", {"rows": rows})
    lines = []
    for r in rows:
        derived = (f"mteps_xla={fmt_rate(r['mteps_xla'])};"
                   f"mteps_pallas={fmt_rate(r['mteps_pallas'])};"
                   f"pallas_over_xla={r['pallas_over_xla']:.2f}x;"
                   f"parity={r['parity']}")
        lines.append(csv_line(
            f"fig16_pallas/{r['graph']}/{r['strategy']}"
            f"/shards{r['shards']}",
            r["pallas_s"] * 1e6, derived))
    if verbose:
        print("\n".join(lines))
    return lines


if __name__ == "__main__":
    run()
