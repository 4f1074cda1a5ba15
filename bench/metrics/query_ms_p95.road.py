"""95th percentile of the per-query time, in ms, in the road cell: the
call into the entry to the distance row on the host, over every query
of the window (numpy's linear interpolation).  A window holds about
nine road queries, not the hundreds an end-to-end tail wants, so it is
a per-layer reading here, near the window's slowest query."""

import numpy as np


def read(run):
    if not run.queries:
        return None
    return float(np.percentile([q.ms for q in run.queries], 95))
