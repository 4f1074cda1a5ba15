"""Device busy time per fixed-point iteration, in us: the busy time of
the traced queries (bench.tracing) over their ``RunResult.iterations``."""


def read(run):
    if run.trace is None:
        return None
    iters = sum(q.iterations for q in run.traced)
    if iters == 0 or run.trace["busy_s"] <= 0:
        return None
    return run.trace["busy_s"] / iters * 1e6
