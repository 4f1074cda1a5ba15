"""One run of one cell of the chip benchmark.

:func:`main` is ``bench/run.py``: it refuses a machine without the
chips the cell asks for, turns on JAX's persistent compilation cache
(the program's ``repro.compile_cache``), and hands over to
:func:`run_workload`, which

1. generates the cell's graph from ``--seed`` with the benchmark's own
   generator (``bench/gen/<generator>.py``, named by the configuration);
   the weighted structure comes from the configuration, and ``--seed``
   draws the node labels;
2. builds the program's CSR (``CSRGraph.from_edges``), the set-up layer;
3. warms up with one traversal from a root outside the window's list;
4. runs a closed loop, one client, back to back through the traffic
   mix's public entry (``repro.algos.sssp`` / ``bfs``) from the
   configuration's stream of search keys, and closes the window at the
   first completion at or after ``--seconds``;
5. with ``--trace 1``, profiles the first whole query of the window and
   reduces the device trace (:mod:`bench.tracing`);
6. compares a seeded sample of the window's distance rows with the
   plain reference (:mod:`bench.reference`) once the window has closed;
7. prints one JSON line: ``correct``, ``attempted``, ``failed``, the
   cell's metrics (each read by ``bench/metrics/<name>.py``),
   ``device``, and last ``checks``, every number compared beside its
   limit.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own that this module finds by name; adding one edits
nothing here.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import importlib.util
import json
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Callable, Optional

import numpy as np

from bench import reference, tracing

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


# -- the manifest and the files it names ------------------------------------

def manifest() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def load_module(path: Path):
    """Import the Python file ``path`` under a name of its own."""
    name = "bench._loaded." + path.stem.replace(".", "_") + "_" + \
        path.parent.name
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with the files it names."""
    workload: dict
    config: dict              # the configuration file's contents
    traffic: dict             # bench/traffic/<traffic>.json
    end_to_end: list          # manifest metrics this cell reports
    per_layer: list


def _reports(metric: dict, name: str) -> bool:
    return "workloads" not in metric or name in metric["workloads"]


def cell(name: str) -> Cell:
    m = manifest()
    by_name = {w["name"]: w for w in m["workloads"]}
    if name not in by_name:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(by_name)})")
    w = by_name[name]
    conf = {c["name"]: c for c in m["configs"]}[w["config"]]
    return Cell(
        workload=w,
        config=load_json(ROOT / conf["file"]),
        traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[x for x in m["end_to_end"] if _reports(x, name)],
        per_layer=[x for x in m["per_layer"] if _reports(x, name)])


def reader(metric: str) -> Callable:
    """``read`` of ``bench/metrics/<metric>.py``."""
    return load_module(BENCH / "metrics" / f"{metric}.py").read


def peaks(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"bench: device kind {kind!r} is not in "
                         f"bench/peaks.json ({sorted(table)})")
    return table[kind]


# -- the device ----------------------------------------------------------------

def device_summary() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chips(chips: int) -> dict:
    """The device summary, or exit non-zero when JAX finds no TPU or
    fewer than ``chips`` of them.  Never falls back to the CPU."""
    dev = device_summary()
    if dev["platform"] != "tpu":
        raise SystemExit(f"bench: JAX found {dev['platform']!r}, not a TPU; "
                         f"nothing was run")
    if dev["count"] < chips:
        raise SystemExit(f"bench: the cell asks for {chips} chips, JAX "
                         f"finds {dev['count']}")
    return dev


def peak_bytes() -> Optional[int]:
    import jax
    vals = [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.local_devices()]
    vals = [v for v in vals if v is not None]
    return max(vals) if vals else None


# -- traffic -------------------------------------------------------------------

#: traversals before the window, from a root outside the window's list
WARMUP_QUERIES = 1
#: whole queries at the start of the window that ``--trace 1`` profiles
TRACED_QUERIES = 1
#: distance rows of the window compared with the reference
CHECK_SAMPLE = 16


def search_keys(degrees: np.ndarray, labels: np.ndarray,
                graph_seed: int) -> np.ndarray:
    """Graph500's search keys: the nodes of degree >= 1 in a uniform
    order, so no key repeats inside a run.  The order is drawn over the
    structure's nodes from the configuration's ``graph_seed`` and mapped
    through the seed's ``labels``: every seed runs the same structural
    keys in the same order, so ``--seed`` does not change which roots a
    window holds."""
    rng = np.random.default_rng([graph_seed, 0x5EA4C4])
    return labels[rng.permutation(np.flatnonzero(degrees[labels] > 0))]


ROOT_STREAMS = {"graph500_search_keys": search_keys}


def resolve_entry(spec: str) -> Callable:
    """``"package.module:function"`` → the function."""
    mod, _, attr = spec.partition(":")
    return getattr(importlib.import_module(mod), attr)


@dataclasses.dataclass
class Query:
    root: int
    ms: float                 # call to dist on the host
    reached: int              # nodes with a finite distance
    edges: int                # CSR edges leaving them (TEPS numerator)
    relaxed: int              # the engine's own RunResult.edges_relaxed
    iterations: int


@dataclasses.dataclass
class Run:
    """What a metric reader reads (``bench/metrics/<name>.py``)."""
    config: dict
    traffic: dict
    queries: list             # [Query] of the window, in order
    window_s: float
    setup_s: float
    csr_build_s: float
    window_compiles: int
    peak_bytes: Optional[int]
    peaks: Optional[dict]     # bench/peaks.json entry of the device
    trace: Optional[dict]     # bench.tracing.reduce() of the traced queries
    traced: list              # [Query] inside the traced sub-window


class CompileLog:
    """Times of JAX's backend compiles and persistent-cache loads, read
    from JAX's own monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.times: list = []

    def __call__(self, event: str, duration: float, **_: Any) -> None:
        if event == self.EVENT:
            self.times.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.times)


def run_workload(name: str, seed: int, seconds: float, trace: bool, *,
                 t_start: Optional[float] = None, device: Optional[dict] = None,
                 config_override: Optional[dict] = None,
                 entry_factory: Optional[Callable] = None) -> dict:
    """One run of cell ``name``; returns the result line as a dict.

    ``config_override`` replaces keys of the configuration (the tests'
    small sizes); ``entry_factory(src, dst, wt, n)``, when given,
    returns a callable put in the program's place (the control and the
    planted faults of ``bench/tests``)."""
    from jax import monitoring

    t_start = time.perf_counter() if t_start is None else t_start
    c = cell(name)
    cfg = dict(c.config, **(config_override or {}))
    log = CompileLog()
    monitoring.register_event_duration_secs_listener(log)
    try:
        return _run(c, cfg, seed, seconds, trace, t_start, device,
                    entry_factory, log)
    finally:
        monitoring.unregister_event_duration_listener(log)


def reached_edges(dist: np.ndarray, degrees: np.ndarray) -> tuple:
    """``(nodes reached, CSR edges leaving them)`` of a distance row:
    Graph500's traversed-edge count, taken from the graph and never from
    the engine's own tally of relaxations."""
    reached = dist < reference.UNREACHED
    return int(reached.sum()), int(degrees @ reached)


def _run(c: Cell, cfg: dict, seed: int, seconds: float, trace: bool,
         t_start: float, device: Optional[dict],
         entry_factory: Optional[Callable], log: CompileLog) -> dict:
    import jax

    from repro.core.graph import CSRGraph

    traffic = c.traffic
    gen = load_module(BENCH / "gen" / f"{cfg['generator']}.py")
    t0 = time.perf_counter()
    src, dst, wt, n, labels = gen.generate(cfg, seed)
    _note(f"generate_s={time.perf_counter() - t0:.3f} nodes={n} "
          f"arcs={len(src)}")
    degrees = np.bincount(src, minlength=n).astype(np.int64)
    weighted = bool(traffic["weighted"])

    t0 = time.perf_counter()
    g = CSRGraph.from_edges(src, dst, wt if weighted else None, n)
    jax.block_until_ready([g.row_ptr, g.col, g.wt])
    csr_build_s = time.perf_counter() - t0
    _note(f"csr_build_s={csr_build_s:.3f}")

    if entry_factory is None:
        entry = resolve_entry(traffic["entry"])
    else:
        entry = entry_factory(src, dst, wt if weighted else None, n)
    kwargs = traffic.get("kwargs", {})
    keys = iter(ROOT_STREAMS[traffic["roots"]](
        degrees, labels, cfg["graph_seed"]).tolist())

    t0 = time.perf_counter()
    for _ in range(WARMUP_QUERIES):
        reached_edges(np.asarray(entry(g, next(keys), **kwargs).dist),
                      degrees)
    _note(f"warmup_s={time.perf_counter() - t0:.3f} "
          f"compiles={len(log.times)}")

    sample_k = CHECK_SAMPLE
    rng = np.random.default_rng([seed, 0xC4EC])
    sample: list = []                          # reservoir of (Query, dist)
    queries: list = []
    n_traced = TRACED_QUERIES if trace else 0
    trace_dir = tempfile.TemporaryDirectory() if trace else None
    traced_span = None
    setup_s = time.perf_counter() - t_start
    _note(f"setup_s={setup_s:.3f}")

    w0 = time.perf_counter()
    while True:
        i = len(queries)
        if i == 0 and n_traced:
            jax.profiler.start_trace(trace_dir.name,
                                     profiler_options=_profile_options())
            traced_span = jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN)
            traced_span.__enter__()
        root_v = next(keys, None)
        if root_v is None:           # every search key used: close early
            break
        q0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("query"):
            r = entry(g, root_v, **kwargs)
            dist = np.asarray(r.dist)
        q1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("tally"):
            reached, edges = reached_edges(dist, degrees)
        q = Query(root=root_v, ms=(q1 - q0) * 1e3, reached=reached,
                  edges=edges, relaxed=int(r.edges_relaxed),
                  iterations=int(r.iterations))
        queries.append(q)
        if len(sample) < sample_k:
            sample.append((q, dist))
        else:
            j = int(rng.integers(0, i + 1))
            if j < sample_k:
                sample[j] = (q, dist)
        if n_traced and i + 1 == n_traced:
            traced_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        if time.perf_counter() - w0 >= seconds:
            break
    w1 = time.perf_counter()
    if n_traced and len(queries) < n_traced:
        traced_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    window_compiles = log.between(w0, w1)
    peak = peak_bytes()
    del g, entry

    summary = None
    if trace:
        summary = tracing.reduce(tracing.load(trace_dir.name))
        trace_dir.cleanup()

    _note(f"window_s={w1 - w0:.3f} queries={len(queries)} "
          f"query_ms={[round(q.ms, 1) for q in queries]}")

    # the plain reference, once the window has closed
    t0 = time.perf_counter()
    ref = reference.Reference(src, dst, wt if weighted else None, n)
    mismatches = []
    for q, dist in sample:
        want = ref.distances(q.root)
        mismatches.append(int(np.count_nonzero(dist != want))
                          if dist.shape == want.shape else n)
    dist_mismatch = int(sum(mismatches))
    _note(f"reference_s={time.perf_counter() - t0:.3f}")
    failed = int(sum(m > 0 for m in mismatches))
    checks = {"dist_mismatch": {"value": dist_mismatch, "limit": 0},
              "queries_compared": {"value": len(sample), "limit": 1}}
    correct = dist_mismatch <= 0 and len(sample) >= 1

    dev = device or device_summary()
    run = Run(config=cfg, traffic=traffic, queries=queries,
              window_s=w1 - w0, setup_s=setup_s, csr_build_s=csr_build_s,
              window_compiles=window_compiles, peak_bytes=peak,
              peaks=peaks(dev["kind"]) if trace else None,
              trace=summary, traced=queries[:n_traced])
    metrics = {}
    for m in (c.per_layer if trace else c.end_to_end):
        value = reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device_line = dict(dev, memory_peak_bytes=peak)
    out = {"correct": bool(correct), "attempted": len(queries),
           "failed": failed, "metrics": metrics, "device": device_line}
    if summary is not None:
        device_line["busy_s"] = summary["busy_s"]
        device_line["window_s"] = summary["window_s"]
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    out["checks"] = checks
    return out


def _note(msg: str) -> None:
    """A progress line on standard error, for the reader of a run's log."""
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def print_result(out: dict) -> None:
    for k, v in out["checks"].items():
        rel = "<=" if k == "dist_mismatch" else ">="
        print(f"check {k}={v['value']} limit {rel} {v['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="One run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    from repro import compile_cache
    c = cell(args.workload)
    dev = require_chips(c.workload["chips"])
    peaks(dev["kind"])
    compile_cache.enable()
    # every program in the cache, however short its compile, so that a
    # second run of a cell finds all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out = run_workload(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=t_start, device=dev)
    print_result(out)
    return 0
