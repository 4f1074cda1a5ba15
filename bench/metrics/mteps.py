"""Traversed edges per second (Graph500 TEPS), in millions.

The numerator is counted from the graph: for each completed window
query, the CSR edges leaving the nodes it reached (sum of their
degrees), never the engine's ``edges_relaxed``.  The denominator is the
whole window, first query's call to the last query's completion."""


def read(run):
    if run.window_s <= 0 or not run.queries:
        return None
    return sum(q.edges for q in run.queries) / run.window_s / 1e6
