"""Graph-query serving demo: the production tier in ~40 lines.

A thin driver over :mod:`repro.serve` (docs/serving.md): load a resident
graph, optionally pin landmark sources, push an open-loop stream of
BFS/SSSP queries with deadlines through the admission queue, and let the
deadline-aware continuous batcher re-bucket K and dispatch fused
``run_batch`` executables.  Every number printed at the end comes from
``GraphServer.stats()`` — the same metric dict the tests and
``benchmarks/fig18_serving.py`` consume.

    PYTHONPATH=src python examples/serve_graph_queries.py \
        --queries 12 --max-batch 4 --graph rmat --algo sssp
"""

import argparse

import numpy as np

from repro import compile_cache
from repro.data import make_graph
from repro.serve import GraphServer, Request


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--queries", type=int, default=12)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-queue", type=int, default=64)
    ap.add_argument("--deadline", type=float, default=30.0,
                    help="per-request deadline, seconds from submit")
    ap.add_argument("--landmarks", type=int, default=2,
                    help="hot sources pinned in the distance cache")
    ap.add_argument("--burst", type=int, default=4,
                    help="arrivals per batcher turn (open-loop burstiness)")
    ap.add_argument("--graph", default="rmat",
                    help="name from repro.data.GRAPH_SUITE")
    ap.add_argument("--algo", choices=["sssp", "bfs"], default="sssp")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    compile_cache.enable()

    g = make_graph(args.graph, weighted=(args.algo == "sssp"))
    rng = np.random.default_rng(args.seed)
    # draw sources from the high-degree end so queries land in the giant
    # component (Graph500 practice); repeats exercise the distance cache
    order = np.argsort(np.asarray(g.degrees))[::-1]
    pool = order[: max(g.num_nodes // 10, 1)]
    sources = rng.choice(pool, size=args.queries)

    srv = GraphServer(max_queue=args.max_queue, max_batch=args.max_batch)
    srv.load_graph(args.graph, g)
    if args.landmarks:
        srv.warm(args.graph, pool[: args.landmarks])

    done = []
    for start in range(0, len(sources), args.burst):
        for src in sources[start:start + args.burst]:   # arrival burst
            resp = srv.submit(Request(
                source=int(src), graph=args.graph,
                deadline=srv.clock() + args.deadline))
            if resp is not None:              # cache hit or reject
                done.append(resp)
        done.extend(srv.step())               # continuous batching
    done.extend(srv.drain())

    for r in done:
        if r.ok:
            reached = int((r.dist < np.iinfo(np.int32).max // 2).sum())
            print(f"query {r.request.id:3d}: source={r.request.source:6d} "
                  f"reached={reached:6d} lanes={r.batch_lanes} "
                  f"{'cache-hit' if r.cached else 'traversed'} "
                  f"latency={r.latency * 1e3:7.1f}ms")
        else:
            print(f"query {r.request.id:3d}: source={r.request.source:6d} "
                  f"REJECTED ({r.reason})")

    s = srv.stats()
    print(f"\n{s['submitted']} submitted, {s.get('completed', 0)} served "
          f"({s.get('result_cache_hits', 0)} cache hits), "
          f"{s.get('rejected_total', 0)} rejected; "
          f"{s.get('batches', 0)} batches at "
          f"occupancy={s['batch_occupancy'] or 0:.2f}; "
          f"p50={s['latency_p50'] * 1e3:.1f}ms "
          f"p99={s['latency_p99'] * 1e3:.1f}ms")


if __name__ == "__main__":
    main()
