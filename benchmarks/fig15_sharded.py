"""Fig. 15 (extension): sharded fixed-point engine — MTEPS and
communication share vs shard count (docs/sharding.md).

The paper rules edge-based balancing out for large graphs on memory
grounds (§I); the production answer is to partition the graph across
devices.  This module measures the sharded fused engine
(``engine.run(..., mode="fused", shards=S)``) on the rmat (power-law)
and road (bounded-degree) families over S ∈ {1, 2, 4, 8} and reports:

* measured MTEPS per shard count (``RunResult.mteps`` — the edge total
  counts each relaxed edge exactly once across shards);
* the partition's **edge-cut share** (``ShardInfo.cut_share``): the
  fraction of relax traffic that crosses a shard boundary, i.e. the
  communication a sparse ghost exchange would pay — rmat's permuted
  power-law edges cut heavily, road's grid locality cuts lightly,
  reproducing the classic partitioning contrast;
* the per-combine halo volume (``ShardInfo.halo_bytes``) and the dense
  replica-exchange volume the current combine actually moves
  (``S · N · 4`` bytes), so the sparse-vs-dense exchange gap is visible
  in the table;
* a parity assertion: every sharded run must be bit-identical (dist,
  iterations, edges) to the single-device fused run;
* the **backend axis** (docs/backends.md): every row carries a
  ``backend`` field, and ``backend="pallas"`` rows re-run the same
  sharded traversal through the per-shard Pallas kernels with the
  epilogue-fused ghost combine, parity-asserted against the *same*
  single-device base.  Pallas rows run in interpret mode on CPU (grid
  serialized in the emulator), so they use a reduced shard set — their
  absolute times price emulation, not TPU kernel quality.

On an accelerator the sweep runs in this process over the visible
devices, up to their count.  On the CPU the shards are *virtual* host
devices carved out of one CPU, in a child process whose environment
sets ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(:func:`benchmarks.common.sharded_rows`), so MTEPS vs S there shows the
*overhead* trend (combine cost, padding) rather than real multi-device
speedup — the same caveat as every CPU-scaled figure in this suite.
"""

from __future__ import annotations

import numpy as np

from benchmarks.common import (csv_line, fmt_rate, safe_mteps, save_result,
                               sharded_rows)
from repro.core import engine, shard
from repro.data import rmat_graph, road_grid_graph

SHARD_COUNTS = [1, 2, 4, 8]
#: interpret-mode Pallas serializes the kernel grid, so the pallas leg
#: prices the endpoints of the shard axis rather than the full sweep
PALLAS_SHARD_COUNTS = [1, 8]

GRAPHS = {
    "rmat": lambda: rmat_graph(scale=10, edge_factor=8, weighted=True,
                               seed=7),
    "road": lambda: road_grid_graph(side=48, weighted=True, seed=7),
}


def measure(max_shards: int) -> list:
    """The sweep's rows, over the shard counts up to ``max_shards``
    (the devices this process sees)."""
    rows = []
    for gname, make in GRAPHS.items():
        g = make()
        source = int(np.argmax(np.asarray(g.degrees)))
        base = None
        for backend in ("xla", "pallas"):
            counts = SHARD_COUNTS if backend == "xla" else PALLAS_SHARD_COUNTS
            for s_count in (c for c in counts if c <= max_shards):
                _, info = shard.partition(g, s_count, method="degree")
                best = None
                for i in range(3):                 # warm-up + best-of-2
                    res = engine.run(g, source, engine.make_strategy("WD"),
                                     mode="fused", shards=s_count,
                                     backend=backend)
                    if i and (best is None
                              or res.traversal_seconds
                              < best.traversal_seconds):
                        best = res
                if base is None:
                    base = best
                tag = f"{gname}/{backend}/{s_count}"
                assert np.array_equal(best.dist, base.dist), tag
                assert best.iterations == base.iterations, tag
                assert best.edges_relaxed == base.edges_relaxed, tag
                rows.append({
                    "graph": gname, "backend": backend, "shards": s_count,
                    "iterations": best.iterations,
                    "edges_relaxed": best.edges_relaxed,
                    "traversal_s": best.traversal_seconds,
                    "setup_s": best.setup_seconds,
                    "mteps": safe_mteps(best),
                    "cut_share": info.cut_share,
                    "halo_bytes": info.halo_bytes,
                    "replica_exchange_bytes": 4 * g.num_nodes * s_count,
                    "edge_imbalance": info.edge_imbalance,
                })
    return rows


def run(verbose: bool = True):
    payload = {"rows": sharded_rows("benchmarks.fig15_sharded", "measure")}
    save_result("fig15_sharded", payload)
    lines = []
    for r in payload["rows"]:
        derived = (f"mteps={fmt_rate(r['mteps'])};"
                   f"cut_share={r['cut_share']:.3f};"
                   f"halo_kb={r['halo_bytes'] / 1024:.1f};"
                   f"edge_imbalance={r['edge_imbalance']:.2f}")
        lines.append(csv_line(
            f"fig15_sharded/{r['graph']}/{r['backend']}"
            f"/shards{r['shards']}",
            r["traversal_s"] * 1e6, derived))
    if verbose:
        print("\n".join(lines))
    return lines


if __name__ == "__main__":
    run()
