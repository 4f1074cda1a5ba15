"""Graph500's search keys: the nodes of degree >= 1 in a uniform order,
so no key repeats inside a run.  The order is drawn over the
structure's nodes from the configuration's ``graph_seed`` and mapped
through the seed's ``labels``: every seed runs the same structural keys
in the same order, so ``--seed`` does not change which roots a window
holds."""

import numpy as np


def keys(degrees: np.ndarray, labels: np.ndarray, graph_seed: int,
         traffic: dict) -> np.ndarray:
    rng = np.random.default_rng([graph_seed, 0x5EA4C4])
    return labels[rng.permutation(np.flatnonzero(degrees[labels] > 0))]
