"""Measured cost model for adaptive kernel selection (AD v2).

The fixed arXiv:1911.09135 decision tree in
:func:`repro.core.strategies.choose_kernel` encodes *someone else's*
hardware: its thresholds (``small_frontier=512``, imbalance 4.0, 2^15
edges) were tuned on a GPU and carried over verbatim.  This module
replaces guessed thresholds with **measured** per-kernel cost models:

1. **Calibration** (:func:`calibrate`): microbenchmark each fused step
   kernel (BS / WD / HP — :data:`repro.core.fused._AD_KERNEL_ORDER`) on
   synthetic frontier masks of the target graph at several densities,
   then least-squares fit the per-iteration wall time as

       ``t(kernel) = a + b · degree_sum + c · frontier_count``

   — one affine model per kernel, the minimal family that separates a
   dispatch floor (``a``), per-edge throughput (``b``) and per-node
   overhead (``c``).  Results persist as JSON keyed by the graph's
   shape signature, so a second run on the same topology is a cache hit
   (reusable across processes; ``python -m repro.core.costmodel`` prints
   ``cache: hit|miss`` for CI smoke checks).
2. **Selection**: :meth:`CostModel.choose` picks ``argmin`` of the
   predicted costs — mirrored bit-for-bit on device by
   ``repro.core.fused._ad_step`` when the coefficients ride along as a
   ``[3, 3]`` float32 array (same float32 op order: ``a + b·es + c·cn``
   then ``argmin``; degenerate frontiers still take BS on both sides).
3. **Online refinement** (:meth:`CostModel.observe`): stepped-mode AD
   with ``online=True`` feeds per-iteration wall times back through
   recursive ridge-regularized normal equations, so the model tracks
   the live machine instead of the calibration snapshot.
4. **Block-size feasibility** (:func:`pallas_block_candidates`): Pallas
   ``tile_r``/``tile_c``/``chunk`` candidates are pre-filtered through
   the :func:`repro.kernels.relax.kernel_vmem_blocks` footprint oracle
   (PR 8's static budget check) before anything is timed — an
   infeasible schedule is rejected by arithmetic, not by OOM.

The calibrated model rides into the fused AD path via
``make_strategy("AD", cost_model=model)`` (see
``repro.core.fused._plan``); docs/schedules.md walks the workflow.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import time
import zlib
from typing import Optional

import jax
import numpy as np

from repro.core.graph import CSRGraph
from repro.core.schedule import DEFAULT_SCHEDULE, Schedule

#: kernel order of the coefficient rows — MUST match
#: ``repro.core.fused._AD_KERNEL_ORDER`` (the lax.switch branch order);
#: spelled out here to avoid an import cycle, cross-checked in tests.
KERNELS = ("BS", "WD", "HP")

#: bump when the model family or the benchmark protocol changes —
#: part of the cache key, so stale calibrations re-run instead of
#: silently mispredicting
VERSION = 2

#: frontier densities the calibration sweeps.  Two mask families per
#: density (prefix + strided) decorrelate ``degree_sum`` from ``count``
#: enough for the 3-parameter fit; see :func:`_calibration_masks`.
DENSITIES = (0.02, 0.1, 0.3, 0.7, 1.0)

#: ridge regularizer of the (recursive) normal equations — small enough
#: to never bias a well-conditioned fit, large enough to keep the
#: near-collinear (degree_sum, count) pair from blowing up
RIDGE = 1e-9


def _features(degree_sum, count) -> np.ndarray:
    """The regression row ``[1, degree_sum, count]`` (float64 host side;
    the *prediction* path is float32 to match the device selector)."""
    return np.array([1.0, float(degree_sum), float(count)], np.float64)


@dataclasses.dataclass
class CostModel:
    """Per-kernel affine iteration-cost models, ``argmin``-selected.

    ``coeffs[k]`` is ``(a, b, c)`` for ``KERNELS[k]``: predicted seconds
    ``a + b·degree_sum + c·count``.  ``xtx``/``xty`` carry the normal
    equations so :meth:`observe` can refine recursively without storing
    samples."""

    coeffs: np.ndarray                     # [3, 3] float64
    xtx: Optional[np.ndarray] = None       # [3, 3, 3] float64
    xty: Optional[np.ndarray] = None       # [3, 3] float64
    calibrated_on: Optional[dict] = None   # graph signature of the fit

    def __post_init__(self):
        self.coeffs = np.asarray(self.coeffs, np.float64).reshape(
            (len(KERNELS), 3))
        if self.xtx is None:
            self.xtx = np.tile(np.eye(3) * RIDGE, (len(KERNELS), 1, 1))
        if self.xty is None:
            self.xty = np.zeros((len(KERNELS), 3), np.float64)

    @classmethod
    def fresh(cls) -> "CostModel":
        """An uncalibrated model: all-zero coefficients predict 0 s for
        every kernel, ties resolve to ``KERNELS[0]`` (BS), and
        :meth:`observe` refines from there — the pure-online starting
        point when no calibration cache is wanted."""
        return cls(coeffs=np.zeros((len(KERNELS), 3), np.float64))

    # -- selection (host mirror of fused._ad_step's measured branch) ----

    def coeff_array(self) -> np.ndarray:
        """The ``[3, 3]`` float32 array the fused selector consumes."""
        return self.coeffs.astype(np.float32)

    def predict(self, count: int, degree_sum: int) -> np.ndarray:
        """Predicted per-kernel seconds, float32 — the same op order as
        the device side (``a + b·es + c·cn`` elementwise, no fma)."""
        c = self.coeff_array()
        es = np.float32(degree_sum)
        cn = np.float32(count)
        return c[:, 0] + c[:, 1] * es + c[:, 2] * cn

    def choose(self, count: int, degree_sum: int) -> str:
        """Cheapest kernel for one frontier.  Degenerate frontiers (no
        edges / empty mask) take BS, exactly as the fixed tree and the
        device selector do."""
        if degree_sum == 0 or count == 0:
            return "BS"
        return KERNELS[int(np.argmin(self.predict(count, degree_sum)))]

    # -- online refinement ----------------------------------------------

    def observe(self, kernel: str, degree_sum: int, count: int,
                seconds: float) -> None:
        """Fold one measured iteration into the model (recursive ridge
        normal equations — O(1) memory, no sample buffer)."""
        if kernel not in KERNELS or not np.isfinite(seconds) or seconds < 0:
            return
        k = KERNELS.index(kernel)
        x = _features(degree_sum, count)
        self.xtx[k] += np.outer(x, x)
        self.xty[k] += x * float(seconds)
        self.coeffs[k] = np.linalg.solve(self.xtx[k], self.xty[k])

    # -- persistence ----------------------------------------------------

    def to_dict(self) -> dict:
        return {
            "version": VERSION,
            "kernels": list(KERNELS),
            "coeffs": self.coeffs.tolist(),
            "xtx": self.xtx.tolist(),
            "xty": self.xty.tolist(),
            "calibrated_on": self.calibrated_on,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "CostModel":
        if d.get("version") != VERSION or tuple(d.get("kernels", ())) != \
                KERNELS:
            raise ValueError("incompatible cost-model cache")
        return cls(coeffs=np.asarray(d["coeffs"], np.float64),
                   xtx=np.asarray(d["xtx"], np.float64),
                   xty=np.asarray(d["xty"], np.float64),
                   calibrated_on=d.get("calibrated_on"))

    def save(self, path: str) -> None:
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as fh:
            json.dump(self.to_dict(), fh)
        os.replace(tmp, path)

    @classmethod
    def load(cls, path: str) -> "CostModel":
        with open(path) as fh:
            return cls.from_dict(json.load(fh))


# ---------------------------------------------------------------------------
# calibration: microbenchmark the fused step kernels
# ---------------------------------------------------------------------------

def graph_signature(graph: CSRGraph, backend: str,
                    sched: Schedule = DEFAULT_SCHEDULE) -> dict:
    """What a calibration is valid for: the device it was timed on
    (platform and kind, as JAX reports them) + topology shape + backend
    + schedule + protocol version.  Weights and exact wiring do not
    enter — the step kernels' cost is shape-driven; the device does, so
    a CPU calibration is never loaded on a TPU."""
    dev = jax.devices()[0]
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "n": int(graph.num_nodes),
        "e": int(graph.num_edges),
        "max_degree": int(graph.max_degree),
        "backend": backend,
        "schedule": sched.to_json(),
        "version": VERSION,
    }


def cache_path(cache_dir: str, sig: dict) -> str:
    # zlib.crc32, not hash(): str hashes are salted per process, and the
    # whole point of the cache is cross-process reuse
    sched_key = zlib.crc32(sig["schedule"].encode())
    kind = re.sub(r"[^A-Za-z0-9]+", "-", sig["device_kind"]).strip("-")
    key = (f"{sig['platform']}-{kind}-{sig['n']}n-{sig['e']}e-"
           f"{sig['max_degree']}d-{sig['backend']}-{sched_key:08x}-"
           f"v{sig['version']}")
    return os.path.join(cache_dir, f"costmodel-{key}.json")


def _calibration_masks(n: int, degrees: np.ndarray):
    """Deterministic frontier masks spanning the (count, degree_sum)
    plane.  Two families per density — a node-id *prefix* and an evenly
    *strided* selection — land different degree sums for similar counts
    (hubs cluster at low ids in RMAT generators), which is what keeps
    the 3-column design matrix well-conditioned."""
    masks = []
    for rho in DENSITIES:
        k = max(1, int(round(rho * n)))
        prefix = np.zeros(n, bool)
        prefix[:k] = True
        masks.append(prefix)
        if k < n:
            strided = np.zeros(n, bool)
            strided[np.linspace(0, n - 1, k).astype(np.int64)] = True
            masks.append(strided)
    return masks


def _time_call(fn, repeats: int) -> float:
    """Min-of-``repeats`` wall time of a blocking call (the usual
    microbenchmark discipline: min discards scheduler noise)."""
    import jax
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def measure(graph: CSRGraph, *, backend: str = "xla",
            sched: Schedule = DEFAULT_SCHEDULE, repeats: int = 3):
    """Microbenchmark the three fused step kernels on ``graph``.

    Returns ``(rows, times)``: design-matrix rows ``[1, degree_sum,
    count]`` and per-kernel second columns.  One compile per kernel —
    every mask shares the graph's static ``[N]`` mask shape, so only the
    first call traces."""
    import jax
    import jax.numpy as jnp

    from repro.core import fused, node_split

    if backend == "pallas":
        graph = graph.plain()        # whole weight tables (engine.run)
    degrees = np.asarray(graph.degrees)
    resolved = sched.resolved(degrees)
    dist0 = np.full(graph.num_nodes, np.iinfo(np.int32).max, np.int32)
    dist0[: max(1, graph.num_nodes // 64)] = 0   # mixed settled/unsettled
    dist0 = jnp.asarray(dist0)

    steps = {
        "BS": jax.jit(lambda d, m: fused._bs_step(
            graph, d, m, backend=backend, sched=resolved)[:3]),
        "WD": jax.jit(lambda d, m: fused._wd_step(
            graph, d, m, backend=backend, sched=resolved)[:3]),
        "HP": jax.jit(lambda d, m: fused._hp_step(
            graph, d, m, backend=backend, sched=resolved)[:3]),
    }
    assert tuple(steps) == KERNELS

    rows, times = [], []
    for mask_np in _calibration_masks(graph.num_nodes, degrees):
        mask = jnp.asarray(mask_np)
        count = int(mask_np.sum())
        degree_sum = int(degrees[mask_np].sum())
        row = _features(degree_sum, count)
        col = []
        for name in KERNELS:
            fn = steps[name]
            fn(dist0, mask)                       # warm-up / compile
            col.append(_time_call(lambda: fn(dist0, mask), repeats))
        rows.append(row)
        times.append(col)
    return np.asarray(rows), np.asarray(times)


def fit(rows: np.ndarray, times: np.ndarray,
        calibrated_on: Optional[dict] = None) -> CostModel:
    """Ridge-regularized least squares per kernel, with the normal
    equations retained so :meth:`CostModel.observe` continues the same
    fit online."""
    xtx = np.tile(np.eye(3) * RIDGE, (len(KERNELS), 1, 1))
    xty = np.zeros((len(KERNELS), 3), np.float64)
    for row, col in zip(rows, times):
        outer = np.outer(row, row)
        for k in range(len(KERNELS)):
            xtx[k] += outer
            xty[k] += row * float(col[k])
    coeffs = np.stack([np.linalg.solve(xtx[k], xty[k])
                       for k in range(len(KERNELS))])
    return CostModel(coeffs=coeffs, xtx=xtx, xty=xty,
                     calibrated_on=calibrated_on)


def calibrate(graph: CSRGraph, *, backend: str = "xla",
              sched: Schedule = DEFAULT_SCHEDULE,
              cache_dir: Optional[str] = None, force: bool = False,
              repeats: int = 3):
    """Calibrated :class:`CostModel` for one graph, cache-aware.

    Returns ``(model, cache_hit)``.  With ``cache_dir`` set, a prior
    calibration for the same :func:`graph_signature` loads instead of
    re-benchmarking (persisted, reusable across runs — the ISSUE's
    "per-schedule microbenchmark calibration at setup"); ``force=True``
    re-measures and overwrites."""
    sig = graph_signature(graph, backend, sched)
    path = cache_path(cache_dir, sig) if cache_dir else None
    if path and not force and os.path.exists(path):
        try:
            model = CostModel.load(path)
            if model.calibrated_on == sig:
                return model, True
        except (ValueError, OSError, KeyError):
            pass                      # stale/corrupt cache ⇒ re-measure
    rows, times = measure(graph, backend=backend, sched=sched,
                          repeats=repeats)
    model = fit(rows, times, calibrated_on=sig)
    if path:
        os.makedirs(cache_dir, exist_ok=True)
        model.save(path)
    return model, False


# ---------------------------------------------------------------------------
# Pallas block-size candidates, VMEM-feasibility filtered
# ---------------------------------------------------------------------------

#: candidate Pallas block shapes the autotuner considers (tile_r fixed at
#: the VPU sublane count; tile_c/chunk swept in lane-width multiples)
TILE_R_CANDIDATES = (8,)
TILE_C_CANDIDATES = (128, 256)
CHUNK_CANDIDATES = (128, 256, 512)


def pallas_block_candidates(graph: CSRGraph, *,
                            base: Schedule = DEFAULT_SCHEDULE,
                            itemsize: int = 4):
    """Feasible Pallas block-shape schedules for ``graph``, largest
    first.

    Every (tile_r, tile_c, chunk) candidate is costed through the
    :func:`repro.kernels.relax.kernel_vmem_blocks` footprint model for
    BOTH kernel families (lanes + wd at full-graph worst case) and kept
    only when the total fits ``relax.VMEM_BUDGET_BYTES`` — the PR 8
    static oracle as a pre-filter, so nothing infeasible is ever timed
    or launched."""
    from repro.kernels import relax

    n, e = int(graph.num_nodes), int(graph.num_edges)
    out = []
    for tile_r in TILE_R_CANDIDATES:
        for tile_c in TILE_C_CANDIDATES:
            for chunk in CHUNK_CANDIDATES:
                lanes = sum(relax.kernel_vmem_blocks(
                    "lanes", n=n, itemsize=itemsize, tile_r=tile_r,
                    tile_c=tile_c, chunk=chunk).values())
                wd = sum(relax.kernel_vmem_blocks(
                    "wd", n=n, f=n, e=e, itemsize=itemsize, tile_r=tile_r,
                    tile_c=tile_c, chunk=chunk).values())
                if max(lanes, wd) <= relax.VMEM_BUDGET_BYTES:
                    out.append(base.replace(tile_r=tile_r, tile_c=tile_c,
                                            chunk=chunk))
    out.sort(key=lambda s: (s.tile, s.chunk), reverse=True)
    return out


# ---------------------------------------------------------------------------
# CLI — calibration-cache smoke entry point (CI runs it twice and greps
# "cache: miss" then "cache: hit")
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="calibrate the AD v2 cost model and report cache state")
    ap.add_argument("--cache", required=True, help="calibration cache dir")
    ap.add_argument("--graph", default="rmat", choices=("rmat", "road"))
    ap.add_argument("--scale", type=int, default=7)
    ap.add_argument("--backend", default="xla")
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args(argv)

    from repro.data import rmat_graph, road_grid_graph
    if args.graph == "rmat":
        g = rmat_graph(scale=args.scale, edge_factor=6, weighted=True,
                       seed=7)
    else:
        g = road_grid_graph(side=1 << max(1, args.scale // 2),
                            weighted=True, seed=7)
    model, hit = calibrate(g, backend=args.backend, cache_dir=args.cache,
                           force=args.force, repeats=args.repeats)
    print(f"cache: {'hit' if hit else 'miss'}")
    for name, (a, b, c) in zip(KERNELS, model.coeffs):
        print(f"{name}: a={a:.3e} b={b:.3e} c={c:.3e}")
    feasible = pallas_block_candidates(g)
    print(f"feasible pallas block schedules: {len(feasible)}")
    return 0


if __name__ == "__main__":          # pragma: no cover - exercised by CI
    raise SystemExit(main())
