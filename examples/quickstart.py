"""Quickstart: the paper in one script.

Runs SSSP on a skewed RMAT graph under all five load-balancing strategies
(BS/EP/WD/NS/HP) plus the adaptive AD selector, validates every result
against a host Dijkstra oracle, and prints the per-strategy
time/memory/balance trade-off table (paper Figs. 7/9 in miniature).

Times include jit compilation (no warm-up), and strategies sharing
kernels benefit from earlier rows' compile cache — AD, which runs last,
reuses BS/WD/HP kernels.  For warmed, best-of-N timings use the
benchmark suite (see docs/benchmarks.md).

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np  # noqa: F811

from repro import compile_cache
from repro.core import balance, engine
from repro.core.graph import graph_stats
from repro.data import rmat_graph


def main():
    import numpy as np
    compile_cache.enable()
    g = rmat_graph(scale=13, edge_factor=8, weighted=True, seed=1)
    print(f"graph: {graph_stats(g)}")
    print(f"whole-graph node imbalance: {balance.graph_imbalance(g)}\n")
    source = int(np.argmax(np.asarray(g.degrees)))   # giant component
    ref = engine.reference_distances(g, source)

    header = (f"{'strategy':>8} {'total_ms':>9} {'kernel_ms':>10} "
              f"{'overhead_ms':>12} {'iters':>6} {'MTEPS':>7} "
              f"{'state_MB':>9} {'correct':>8}")
    print(header)
    for name in ["BS", "EP", "WD", "NS", "HP", "AD"]:
        strat = engine.make_strategy(name)
        res = engine.run(g, source, strat)
        ok = bool(np.array_equal(res.dist, ref))
        print(f"{name:>8} {res.total_seconds*1e3:9.1f} "
              f"{res.kernel_seconds*1e3:10.1f} "
              f"{res.overhead_seconds*1e3:12.1f} {res.iterations:6d} "
              f"{res.mteps:7.2f} {res.state_bytes/2**20:9.2f} {ok!s:>8}")
        assert ok, f"{name} diverged from Dijkstra"
    print("\nall strategies agree with the Dijkstra oracle ✓")

    # the same traversal as ONE device dispatch (docs/architecture.md):
    # no per-iteration host round-trips, bit-identical distances
    warm = engine.run(g, source, engine.make_strategy("AD"), mode="fused")
    res = engine.run(g, source, engine.make_strategy("AD"), mode="fused")
    assert np.array_equal(res.dist, ref) and np.array_equal(warm.dist, ref)
    print(f"\nfused AD (single dispatch, warmed): "
          f"{res.total_seconds*1e3:.1f} ms, {res.mteps:.2f} MTEPS, "
          f"kernels={res.iterations} iterations in 1 dispatch")

    # the same schedules under different SEMANTICS (docs/operators.md):
    # swap the edge operator, keep the strategy — no new kernels
    from repro.algos import connected_components, reference_widest, widest_path
    wide = widest_path(g, source, strategy="HP")
    assert np.array_equal(wide.dist, reference_widest(g, source))
    labels = connected_components(g, strategy="WD", mode="fused")
    print(f"\noperators on the same machinery: widest_path[HP] max width "
          f"{int(np.max(wide.dist[wide.dist < np.max(wide.dist)])):d} "
          f"(oracle ✓), min_label CC[WD,fused] found "
          f"{len(np.unique(labels))} components")


if __name__ == "__main__":
    main()
