"""Shared benchmark plumbing.

The paper's graphs are scaled to CPU budgets (DESIGN.md §8): same degree
*distribution shapes* (power-law RMAT / Graph500 Kronecker, uniform ER,
bounded-degree road grids), reduced node counts.  The strategies react to
distribution shape, not absolute size, so the paper's relative orderings
are reproducible at this scale — EXPERIMENTS.md §Claims records each.

EP's GPU-memory wall (4.66 GB on the paper's K20c) is scaled
proportionally: the budget is set so the Graph500-class graphs' COO
representation exceeds it while every CSR representation fits — the same
relationship the paper's hardware imposed.
"""

from __future__ import annotations

import json
import math
import os

import numpy as np

from repro.core import engine
from repro.data import make_graph

RESULTS_DIR = os.environ.get("REPRO_BENCH_DIR", "experiments/bench")

#: scaled analogue of the 4.66 GB device memory (see module docstring):
#: every CSR fits, every Graph500-class COO (weighted or not) does not
EP_MEMORY_BUDGET = int(3.5 * 2 ** 20)

BENCH_GRAPHS = ["rmat", "road", "er", "graph500_a", "graph500_b",
                "graph500_c"]

_GRAPH_CACHE: dict = {}


def get_graph(name: str, weighted: bool):
    key = (name, weighted)
    if key not in _GRAPH_CACHE:
        _GRAPH_CACHE[key] = make_graph(name, weighted=weighted)
    return _GRAPH_CACHE[key]


def run_strategy(graph, strategy_name: str, *, source: int | None = None,
                 repeats: int = 2, record_degrees: bool = False,
                 mode: str = "stepped", op: str = "shortest_path",
                 backend: str = "xla", schedule: str = "bsp",
                 delta: int | None = None,
                 **kwargs) -> engine.RunResult:
    """Warm-up run (jit compile) + best-of-N timed runs.

    The warm-up run is never a best-of candidate (its timings carry
    compilation), and candidates are ranked by ``traversal_seconds`` —
    the same setup-free clock ``RunResult.mteps`` reports — so one-off
    strategy prep (NS morph, EP COO conversion) doesn't pick the winner.

    ``op`` selects the edge operator (docs/operators.md) — the relax
    semantics under the strategy's schedule; ``backend`` the relax
    kernel lowering (docs/backends.md); ``schedule``/``delta`` the work
    ordering — ``"delta"`` settles distance buckets in priority order
    (docs/scheduling.md).

    Default source = highest-outdegree node (inside the giant component —
    Graph500 practice; node 0 of a label-permuted Kronecker graph may
    reach almost nothing)."""
    if source is None:
        source = int(np.argmax(np.asarray(graph.degrees)))
    if strategy_name == "EP":
        kwargs.setdefault("memory_budget_bytes", EP_MEMORY_BUDGET)
    best = None
    for i in range(repeats + 1):
        strat = engine.make_strategy(strategy_name, **kwargs)
        res = engine.run(graph, source, strat,
                         record_degrees=record_degrees, mode=mode, op=op,
                         backend=backend, schedule=schedule, delta=delta)
        if i == 0:
            continue                      # warm-up: compile time pollutes
        if best is None or res.traversal_seconds < best.traversal_seconds:
            best = res
    return best


#: traversal clocks below this resolution are timer noise: a rate
#: computed from them is an artefact of the clock, not the kernel
MTEPS_MIN_SECONDS = 1e-7

def safe_mteps(res, *, min_seconds: float = MTEPS_MIN_SECONDS):
    """``res.mteps``, or ``None`` when the rate would be meaningless.

    ``RunResult.mteps`` guards the exact-zero clock, but a
    sub-resolution traversal time (a one-iteration run on a tiny graph,
    or a timer that under-reports) still divides real edges by noise and
    prints an absurd rate into the JSON a later figure regression would
    ratchet on.  ``None`` keeps the row — status, iterations and edge
    counts stay usable — while marking the rate itself absent; the CSV
    writers render it ``n/a`` (:func:`fmt_rate`) and the JSON writers
    store a null.

    Accepts anything with ``edges_relaxed`` and a traversal clock —
    ``RunResult`` (``traversal_seconds``) or ``BatchRunResult``
    (``total_seconds``; the batch result has no setup/traversal split)."""
    seconds = getattr(res, "traversal_seconds", None)
    if seconds is None:
        seconds = res.total_seconds
    seconds = float(seconds)
    edges = int(res.edges_relaxed)
    if not math.isfinite(seconds) or seconds < min_seconds or edges <= 0:
        return None
    return edges / seconds / 1e6


def fmt_rate(value, spec: str = ".2f") -> str:
    """Format a possibly-``None`` rate for the derived CSV field."""
    return "n/a" if value is None else format(value, spec)


def save_result(name: str, payload) -> None:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, f"{name}.json"), "w") as f:
        json.dump(payload, f, indent=1, default=str)


#: virtual host devices the sharded sections run on without an accelerator
CPU_SHARD_DEVICES = 8


def sharded_rows(module: str, func: str) -> list:
    """Rows of the sharded measurement ``module.func(max_shards=...)``.

    On an accelerator it runs in this process over the visible devices
    (a chip belongs to one process, so a child could not take it).  On
    the CPU it runs in a child process whose environment asks XLA for
    :data:`CPU_SHARD_DEVICES` virtual devices: that flag must be set
    before JAX starts, so it is never set in this process."""
    import importlib
    import subprocess
    import sys

    import jax
    if jax.default_backend() != "cpu":
        return getattr(importlib.import_module(module), func)(
            max_shards=jax.device_count())
    env = dict(os.environ, PYTHONPATH="src", XLA_FLAGS=(
        f"--xla_force_host_platform_device_count={CPU_SHARD_DEVICES}"))
    code = (f"import json; from {module} import {func}; "
            f"print(json.dumps({func}(max_shards={CPU_SHARD_DEVICES})))")
    root = os.path.join(os.path.dirname(__file__), "..")
    out = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                         capture_output=True, text=True, timeout=1800)
    if out.returncode != 0:
        raise RuntimeError(f"{module} sharded child failed:\n"
                           f"{out.stderr[-3000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def csv_line(name: str, us_per_call, derived: str = "") -> str:
    if us_per_call is None:
        us_per_call = float("nan")
    return f"{name},{us_per_call:.1f},{derived}"
