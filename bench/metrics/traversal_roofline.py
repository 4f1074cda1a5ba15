"""Share of the HBM roofline that the traced traversals reach, in %.

The traversal is memory-bound: it does a compare and an add per edge,
so the least time the chip could take is the bytes it has to move over
the peak HBM bandwidth (bench/peaks.json).  :func:`min_bytes` counts
the least bytes a traversal moves, whatever implements it: per node
reached, its ``row_ptr`` entry and its distance (4 bytes each); per
edge leaving a reached node, its ``col`` entry, its weight when the
traversal is weighted, and the distance at its head (4 bytes each).
The share is that least time over the device busy time of the traced
queries (bench.tracing)."""

WORD = 4


def min_bytes(reached: int, edges: int, weighted: bool) -> int:
    per_edge = WORD * (3 if weighted else 2)
    return reached * 2 * WORD + edges * per_edge


def read(run):
    if run.trace is None or run.peaks is None or run.trace["busy_s"] <= 0:
        return None
    weighted = bool(run.traffic["weighted"])
    total = sum(min_bytes(q.reached, q.edges, weighted) for q in run.traced)
    if total == 0:
        return None
    least_s = total / run.peaks["hbm_bytes_per_s"]
    return least_s / run.trace["busy_s"] * 100.0
