"""Seconds in the program's ``CSRGraph.from_edges`` on the benchmark's
arcs, to the arrays ready on the device (the set-up layer)."""


def read(run):
    return run.csr_build_s
