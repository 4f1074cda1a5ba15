"""Closed loop of one client: queries back to back through the mix's
one entry, each from the next key of its root stream after the
warm-up's, until the first completion at or after ``ctx.seconds`` (or
the stream runs out).  With ``--trace 1`` the first
:data:`TRACED_QUERIES` whole queries are profiled.

A query's host time is spanned ``query`` (the call to its distance row
on the host) and ``tally`` (counting the edges it reached); the trace's
idle gaps are named by them.  ``loop_args``: none."""

import time

import jax
import numpy as np

from bench.harness import LoopResult, Query, reached_edges

HOST_SPANS = ("query", "tally")
#: whole queries at the start of the window that ``--trace 1`` profiles
TRACED_QUERIES = 1


def run(ctx) -> LoopResult:
    (weighted, g), = ctx.graphs.items()
    entry, kwargs, degrees = ctx.entries[weighted], ctx.kwargs, ctx.degrees
    keys = iter(ctx.keys(ctx.traffic["roots"])[ctx.warmup_keys:].tolist())
    n_traced = TRACED_QUERIES if ctx.trace else 0
    queries: list = []

    w0 = time.perf_counter()
    while True:
        i = len(queries)
        if i == 0 and n_traced:
            ctx.trace_on()
        root_v = next(keys, None)
        if root_v is None:           # every search key used: close early
            break
        q0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("query"):
            r = entry(g, root_v, **kwargs)
            dist = np.asarray(r.dist)
        q1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("tally"):
            reached, edges = reached_edges(dist, degrees)
        q = Query(root=root_v, ms=(q1 - q0) * 1e3, reached=reached,
                  edges=edges, relaxed=int(r.edges_relaxed),
                  iterations=int(r.iterations), weighted=weighted,
                  t_submit=q0 - w0, t_done=q1 - w0)
        queries.append(q)
        ctx.offer(q, dist)
        if n_traced and i + 1 == n_traced:
            ctx.trace_off()
        if time.perf_counter() - w0 >= ctx.seconds:
            break
    w1 = time.perf_counter()
    ctx.trace_off()
    return LoopResult(queries=queries, start=w0, window_s=w1 - w0,
                      traced=queries[:n_traced], attempted=len(queries),
                      failed=0)
