"""Distributed Graph500 SSSP: 1-D node partitioning over the data axis,
WD-balanced local expansion, bucketed all_to_all frontier exchange
(repro.core.dist) — the paper's load balancing composed with a
multi-device runtime.

On an accelerator it runs over the visible devices.  On the CPU it
re-runs itself in a child process whose environment asks XLA for 8
virtual devices (the flag must be set before JAX starts).

    python examples/graph500_distributed.py
"""

import os
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402

from repro import compile_cache  # noqa: E402
from repro.core import engine  # noqa: E402
from repro.core.dist import distributed_sssp  # noqa: E402
from repro.core.graph import graph_stats  # noqa: E402
from repro.data import graph500_graph  # noqa: E402


#: virtual host devices of the CPU recipe
CPU_DEVICES = 8


def main():
    compile_cache.enable()
    if jax.default_backend() == "cpu" and jax.device_count() < CPU_DEVICES:
        env = dict(os.environ, XLA_FLAGS=(
            f"--xla_force_host_platform_device_count={CPU_DEVICES}"))
        sys.exit(subprocess.run([sys.executable, *sys.argv],
                                env=env).returncode)
    g = graph500_graph(scale=13, edge_factor=16, weighted=True, seed=9)
    print("graph:", graph_stats(g))
    mesh = jax.make_mesh((jax.device_count(),), ("data",))
    print(f"mesh: {jax.device_count()} devices over axis 'data'")

    t0 = time.perf_counter()
    dist = distributed_sssp(g, 0, mesh)
    dt = time.perf_counter() - t0
    ref = engine.reference_distances(g, 0)
    ok = np.array_equal(dist, ref)
    reached = int((dist < np.iinfo(np.int32).max // 2).sum())
    print(f"distributed SSSP: {dt:.2f}s, {reached}/{g.num_nodes} reached, "
          f"correct={ok}")
    assert ok


if __name__ == "__main__":
    main()
