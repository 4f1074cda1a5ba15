"""One run of one cell of the chip benchmark.

:func:`main` is ``bench/run.py``: it refuses a machine without the
chips the cell asks for, turns on JAX's persistent compilation cache
(the program's ``repro.compile_cache``), and hands over to
:func:`run_workload`, which

1. generates the cell's graph from ``--seed`` with the benchmark's own
   generator (``bench/gen/<generator>.py``, named by the configuration);
   the weighted structure comes from the configuration, and ``--seed``
   draws the node labels;
2. builds the program's CSR (``CSRGraph.from_edges``), the set-up layer,
   once for each kind of query the traffic mix runs (weighted,
   unweighted or both);
3. warms up: the loop's own ``warmup(ctx)``, or :func:`warm_up`, one
   traversal of each kind from the first key of the mix's root stream;
4. hands the window to the traffic mix's loop
   (``bench/loops/<loop>.py``, ``closed_one_client`` by default), which
   drives the system under test through a :class:`Ctx` and returns a
   :class:`LoopResult`;
5. with ``--trace 1``, reduces the device trace of the sub-window the
   loop profiled (:mod:`bench.tracing`), naming idle gaps by the loop's
   ``HOST_SPANS``;
6. compares the sample of the window's distance rows that the loop
   offered (:meth:`Ctx.offer`) with the plain reference
   (:mod:`bench.reference`) of each row's kind, once the window has
   closed;
7. prints one JSON line: ``correct``, ``attempted``, ``failed``, the
   cell's metrics (each read by ``bench/metrics/<name>.py``),
   ``device``, and last ``checks``, every number compared beside its
   limit.

Everything that belongs to one configuration, traffic mix, loop, root
stream or metric is a file of its own that this module finds by name;
adding one edits nothing here.
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

#: keys at the head of the mix's root stream that :func:`warm_up` uses
WARMUP_QUERIES = 1
#: distance rows of the window compared with the reference
CHECK_SAMPLE = 16
#: the loop of a traffic mix that names none
DEFAULT_LOOP = "closed_one_client"


def kinds(traffic: dict) -> tuple:
    """The kinds of query a mix runs, as ``weighted`` flags: its
    ``"weighted"`` is ``true``, ``false`` or ``"both"``."""
    w = traffic["weighted"]
    if w == "both":
        return (True, False)
    if isinstance(w, bool):
        return (w,)
    raise ValueError(f"traffic {traffic.get('name')!r}: \"weighted\" is "
                     f"true, false or \"both\", not {w!r}")


def entry_spec(traffic: dict, weighted: bool) -> str:
    """The entry of a mix for one kind of query: ``"entry"`` is one
    ``"module:function"`` for every kind, or an object keyed
    ``"weighted"`` / ``"unweighted"``."""
    e = traffic["entry"]
    if isinstance(e, dict):
        return e["weighted" if weighted else "unweighted"]
    return e


def load_loop(name: str):
    """``bench/loops/<name>.py``."""
    return load_module(BENCH / "loops" / f"{name}.py")


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
    weighted: bool = False    # its kind, and so its reference
    t_submit: float = 0.0     # seconds from the window's start
    t_done: float = 0.0
    cached: bool = False      # answered from a cache, with no traversal


@dataclasses.dataclass
class LoopResult:
    """What a loop's ``run(ctx)`` returns.  Every request of the window
    it counts in ``attempted`` is completed (in ``queries``), failed, or
    still queued when the window closes."""
    queries: list             # [Query] completed in the window, in order
    start: float              # time.perf_counter() at the window's start
    window_s: float
    traced: list              # [Query] inside the traced sub-window
    attempted: int
    failed: int               # requests refused or lost by the loop
    counters: dict = dataclasses.field(default_factory=dict)


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
    counters: dict            # LoopResult.counters


class Ctx:
    """What a loop (``bench/loops/<loop>.py``) is handed: the system
    under test, the run's parameters, and the harness's hooks.

    ``graphs`` and ``entries`` are keyed by the ``weighted`` flag of
    each kind the mix runs (:func:`kinds`); ``entries[w](graphs[w],
    root, **kwargs)`` is one traversal, returning a ``RunResult``."""

    def __init__(self, *, graphs: dict, entries: dict, kwargs: dict,
                 traffic: dict, cfg: dict, degrees: np.ndarray,
                 labels: np.ndarray, seed: int, seconds: float,
                 trace: bool):
        self.graphs = graphs
        self.entries = entries
        self.kwargs = kwargs
        self.traffic = traffic
        self.cfg = cfg
        self.degrees = degrees
        self.labels = labels
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        #: keys at the head of the mix's root stream that the warm-up
        #: used; a loop that relies on :func:`warm_up` starts after them
        self.warmup_keys = 0
        self.sample: list = []               # reservoir of (Query, dist)
        self._offered = 0
        self._rng = np.random.default_rng([seed, 0xC4EC])
        self._keys: dict = {}
        self._trace_dir = tempfile.TemporaryDirectory() if trace else None
        self._span = None
        self._traced = False

    def keys(self, stream: str) -> np.ndarray:
        """``keys(degrees, labels, graph_seed, traffic)`` of
        ``bench/roots/<stream>.py``, drawn once per run."""
        if stream not in self._keys:
            mod = load_module(BENCH / "roots" / f"{stream}.py")
            self._keys[stream] = mod.keys(self.degrees, self.labels,
                                          self.cfg["graph_seed"],
                                          self.traffic)
        return self._keys[stream]

    def offer(self, query: Query, dist: np.ndarray) -> None:
        """Offer a completed query's distance row to the sample that is
        compared with the reference: a reservoir of
        :data:`CHECK_SAMPLE` rows, drawn from the seed."""
        i = self._offered
        self._offered += 1
        if len(self.sample) < CHECK_SAMPLE:
            self.sample.append((query, dist))
        else:
            j = int(self._rng.integers(0, i + 1))
            if j < CHECK_SAMPLE:
                self.sample[j] = (query, dist)

    def trace_on(self) -> None:
        """With ``--trace 1``, start the profiler and the
        :data:`bench.tracing.WINDOW_SPAN` span; once per run."""
        if not self.trace or self._traced:
            return
        import jax
        jax.profiler.start_trace(self._trace_dir.name,
                                 profiler_options=_profile_options())
        self._span = jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN)
        self._span.__enter__()
        self._traced = True

    def trace_off(self) -> None:
        """Close the span and stop the profiler, if they run."""
        if self._span is None:
            return
        import jax
        self._span.__exit__(None, None, None)
        self._span = None
        jax.profiler.stop_trace()


def warm_up(ctx: Ctx) -> None:
    """The warm-up of a loop that defines none: one traversal of each
    kind from each of the first :data:`WARMUP_QUERIES` keys of the mix's
    root stream, which the window then skips (``ctx.warmup_keys``)."""
    for root in ctx.keys(ctx.traffic["roots"])[:WARMUP_QUERIES].tolist():
        for w, g in ctx.graphs.items():
            reached_edges(np.asarray(
                ctx.entries[w](g, root, **ctx.kwargs).dist), ctx.degrees)
    ctx.warmup_keys = WARMUP_QUERIES


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
    ks = kinds(traffic)

    t0 = time.perf_counter()
    graphs = {}
    for w in ks:
        g = CSRGraph.from_edges(src, dst, wt if w else None, n)
        jax.block_until_ready([g.row_ptr, g.col, g.wt])
        graphs[w] = g
    csr_build_s = time.perf_counter() - t0
    _note(f"csr_build_s={csr_build_s:.3f}")

    if entry_factory is None:
        entries = {w: resolve_entry(entry_spec(traffic, w)) for w in ks}
    else:
        entries = {w: entry_factory(src, dst, wt if w else None, n)
                   for w in ks}
    loop = load_loop(traffic.get("loop", DEFAULT_LOOP))
    ctx = Ctx(graphs=graphs, entries=entries,
              kwargs=traffic.get("kwargs", {}), traffic=traffic, cfg=cfg,
              degrees=degrees, labels=labels, seed=seed, seconds=seconds,
              trace=trace)

    t0 = time.perf_counter()
    getattr(loop, "warmup", warm_up)(ctx)
    _note(f"warmup_s={time.perf_counter() - t0:.3f} "
          f"compiles={len(log.times)}")
    setup_s = time.perf_counter() - t_start
    _note(f"setup_s={setup_s:.3f}")

    res = loop.run(ctx)
    ctx.trace_off()
    window_compiles = log.between(res.start, res.start + res.window_s)
    peak = peak_bytes()
    graphs.clear()
    entries.clear()
    del g

    summary = None
    if trace:
        summary = tracing.reduce(tracing.load(ctx._trace_dir.name),
                                 getattr(loop, "HOST_SPANS", ()))
        ctx._trace_dir.cleanup()

    queued = res.attempted - len(res.queries) - res.failed
    _note(f"window_s={res.window_s:.3f} queries={len(res.queries)} "
          f"queued={queued} "
          f"query_ms={[round(q.ms, 1) for q in res.queries]}")

    # the plain reference of each row's kind, once the window has closed
    t0 = time.perf_counter()
    refs: dict = {}
    mismatches = []
    for q, dist in ctx.sample:
        if q.weighted not in refs:
            refs[q.weighted] = reference.Reference(
                src, dst, wt if q.weighted else None, n)
        want = refs[q.weighted].distances(q.root)
        mismatches.append(int(np.count_nonzero(dist != want))
                          if dist.shape == want.shape else n)
    dist_mismatch = int(sum(mismatches))
    _note(f"reference_s={time.perf_counter() - t0:.3f}")
    failed = res.failed + int(sum(m > 0 for m in mismatches))
    checks = {"dist_mismatch": {"value": dist_mismatch, "limit": 0},
              "queries_compared": {"value": len(ctx.sample), "limit": 1}}
    correct = dist_mismatch <= 0 and len(ctx.sample) >= 1

    dev = device or device_summary()
    run = Run(config=cfg, traffic=traffic, queries=res.queries,
              window_s=res.window_s, setup_s=setup_s,
              csr_build_s=csr_build_s, window_compiles=window_compiles,
              peak_bytes=peak,
              peaks=peaks(dev["kind"]) if trace else None,
              trace=summary, traced=res.traced, counters=res.counters)
    metrics = {}
    for m in (c.per_layer if trace else c.end_to_end):
        value = reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device_line = dict(dev, memory_peak_bytes=peak)
    out = {"correct": bool(correct), "attempted": res.attempted,
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
