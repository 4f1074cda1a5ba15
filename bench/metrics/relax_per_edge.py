"""Edges the engine relaxed per edge of the reached set: the sum of
``RunResult.edges_relaxed`` over the window's queries over the sum of
the ``mteps`` edge counts.  Above 1, work is redone (SSSP re-relaxes
an edge each time its tail improves)."""


def read(run):
    edges = sum(q.edges for q in run.queries)
    if edges == 0:
        return None
    return sum(q.relaxed for q in run.queries) / edges
