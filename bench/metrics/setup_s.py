"""Seconds from the start of the process to the start of the window:
imports, graph generation, CSR build, compile or cache load, warm-up."""


def read(run):
    return run.setup_s
