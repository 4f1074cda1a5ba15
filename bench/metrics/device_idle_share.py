"""Share of the traced window in which no operation ran on the device,
in %: ``1 - busy / window`` (bench.tracing)."""


def read(run):
    if run.trace is None or run.trace["idle_share"] is None:
        return None
    return run.trace["idle_share"] * 100.0
