"""Traffic loops and root streams found by name.

The closed loop of one client runs what the harness ran before its loop
became a file of its own, query for query (pinned below from the
harness as it was).  An open loop, a skewed root stream and a mix of
BFS and SSSP queries are added to a copy of the benchmark as new files
only, run through ``run_workload``, and come out correct; a BFS query
answered with an SSSP row comes out not correct."""

import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
SMALL = {"g500-s20-sssp": {"scale": 8}, "g500-s20-bfs": {"scale": 8},
         "road-fla-sssp": {"side": 20}}
SEED = 2**34 + 11

# What the closed loop ran at the SMALL sizes on SEED, with a window
# long enough to use every search key: read from the harness before the
# loop moved out of it.  Digests are sha256 (first 24 hex digits) of
# the JSON list of roots, of the JSON list of [edges, relaxed,
# iterations] per query, and of the int32 distance rows in order.
PINNED = {
    "g500-s20-bfs": dict(
        attempted=234, warmup_root=202, first_roots=[93, 203, 174, 108],
        roots="82c009f45b602b2ce390cbbd", counts="bc41dabce237fd626f31442d",
        dist="3f378b937aae5a1527f23ec3", edges=968292, relaxed=968292,
        iterations=1109),
    "g500-s20-sssp": dict(
        attempted=234, warmup_root=202, first_roots=[93, 203, 174, 108],
        roots="82c009f45b602b2ce390cbbd", counts="db6cea68ea30d74555700bd3",
        dist="14569bfe69851f4c46352262", edges=968292, relaxed=2459530,
        iterations=2134),
    "road-fla-sssp": dict(
        attempted=399, warmup_root=394, first_roots=[154, 248, 219, 132],
        roots="b67301d7618d5b952d440892", counts="646b9766462eb567c87d725d",
        dist="f718bd8b1583f0aefa3790e4", edges=622440, relaxed=1247508,
        iterations=10617),
}


def _digest(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()[:24]


@pytest.mark.parametrize("cell", sorted(PINNED))
def test_closed_loop_runs_what_the_harness_ran(cell, monkeypatch):
    """Same warm-up root, roots in the same order, ``attempted``,
    per-query counts and distance rows as before the loop moved."""
    runs, calls = [], []
    monkeypatch.setattr(harness, "reader",
                        lambda name: lambda run: runs.append(run))

    def factory(src, dst, wt, n):
        entry = harness.resolve_entry(harness.cell(cell).traffic["entry"])

        def call(graph, root, **kw):
            r = entry(graph, root, **kw)
            calls.append((root, np.asarray(r.dist, np.int32).copy()))
            return r
        return call

    out = harness.run_workload(cell, SEED, 1e9, False,
                               config_override=SMALL[cell],
                               entry_factory=factory)
    assert out["correct"] is True
    qs = runs[0].queries
    roots = [q.root for q in qs]
    assert roots == [r for r, _ in calls[1:]]
    counts = [[q.edges, q.relaxed, q.iterations] for q in qs]
    want = PINNED[cell]
    got = dict(
        attempted=out["attempted"], warmup_root=calls[0][0],
        first_roots=roots[:4], roots=_digest(json.dumps(roots).encode()),
        counts=_digest(json.dumps(counts).encode()),
        dist=_digest(b"".join(d.tobytes() for _, d in calls[1:])),
        edges=sum(q.edges for q in qs), relaxed=sum(q.relaxed for q in qs),
        iterations=sum(q.iterations for q in qs))
    assert got == want
    assert all(q.weighted is harness.kinds(harness.cell(cell).traffic)[0]
               and not q.cached and 0 <= q.t_submit <= q.t_done
               for q in qs)


def test_kinds_and_entries():
    assert harness.kinds({"weighted": True}) == (True,)
    assert harness.kinds({"weighted": False}) == (False,)
    assert harness.kinds({"weighted": "both"}) == (True, False)
    with pytest.raises(ValueError):
        harness.kinds({"name": "x", "weighted": "yes"})
    both = {"entry": {"weighted": "a:sssp", "unweighted": "a:bfs"}}
    assert [harness.entry_spec(both, w) for w in (True, False)] == \
        ["a:sssp", "a:bfs"]
    assert harness.entry_spec({"entry": "a:bfs"}, True) == "a:bfs"


# -- a new loop, root stream and mixed mix, as new files only ----------------

OPEN_LOOP = '''"""Open loop on the wall clock: requests fall due at a fixed rate, in
the order of the mix's root stream, each BFS or SSSP as drawn from the
seed; one server takes them in order of arrival and answers a request
it has answered before from its cache, with no traversal."""

import time

import numpy as np

from bench.harness import LoopResult, Query, reached_edges

HOST_SPANS = ("serve",)


def warmup(ctx):
    root = int(ctx.keys(ctx.traffic["roots"])[0])
    for w, g in ctx.graphs.items():
        ctx.entries[w](g, root, **ctx.kwargs)


def run(ctx):
    args = ctx.traffic["loop_args"]
    roots = ctx.keys(ctx.traffic["roots"]).tolist()
    rng = np.random.default_rng([ctx.seed, 0x09E1])
    kind = (rng.random(len(roots)) < args["weighted_share"]).tolist()
    gap = 1.0 / args["rate_per_s"]
    cache, queries = {}, []
    w0 = time.perf_counter()
    i = 0
    while i < len(roots) and time.perf_counter() - w0 < ctx.seconds:
        due = i * gap
        wait = due - (time.perf_counter() - w0)
        if wait > 0:
            time.sleep(wait)
        root, w = roots[i], kind[i]
        i += 1
        cached = (root, w) in cache
        if cached:
            dist, relaxed, iterations = cache[(root, w)], 0, 0
        else:
            r = ctx.entries[w](ctx.graphs[w], root, **ctx.kwargs)
            dist = np.asarray(r.dist)
            relaxed, iterations = int(r.edges_relaxed), int(r.iterations)
            cache[(root, w)] = dist
        done = time.perf_counter() - w0
        reached, edges = reached_edges(dist, ctx.degrees)
        q = Query(root=root, ms=(done - due) * 1e3, reached=reached,
                  edges=0 if cached else edges, relaxed=relaxed,
                  iterations=iterations, weighted=w, t_submit=due,
                  t_done=done, cached=cached)
        queries.append(q)
        ctx.offer(q, dist)
    window_s = time.perf_counter() - w0
    due_by_close = min(len(roots), int(window_s / gap) + 1)
    return LoopResult(queries=queries, start=w0, window_s=window_s,
                      traced=[], attempted=max(due_by_close, len(queries)),
                      failed=0,
                      counters={"cache_hits": sum(q.cached for q in queries)})
'''

ZIPF_STREAM = '''"""Sources drawn with Zipf skew over the search keys, repeats allowed:
rank r (from 1) has weight r ** -zipf_a."""

import numpy as np


def keys(degrees, labels, graph_seed, traffic):
    base = labels[np.flatnonzero(degrees[labels] > 0)]
    rng = np.random.default_rng([graph_seed, 0x21BF])
    ranks = rng.zipf(traffic["loop_args"]["zipf_a"], 4096) - 1
    return base[np.minimum(ranks, len(base) - 1)]
'''

MIXED_MIX = {
    "name": "mixed-open-fixture",
    "about": "Open loop at a fixed rate, BFS and SSSP mixed half and half, "
             "Zipf-skewed sources, answers repeated from a cache.",
    "entry": {"weighted": "repro.algos:sssp", "unweighted": "repro.algos:bfs"},
    "weighted": "both",
    "kwargs": {"strategy": "AD", "mode": "fused"},
    "roots": "zipf_fixture",
    "loop": "open_fixture",
    "loop_args": {"rate_per_s": 4000, "weighted_share": 0.5, "zipf_a": 1.3},
}
MIXED_CELL = {"name": "g500-s20-mixed-open", "config": "graph500-s20",
              "traffic": "mixed-open-fixture", "chips": 1,
              "why": "open loop over BFS and SSSP with Zipf sources"}

# Runs the copy's harness in a process of its own, twice: as it is, and
# with every BFS query answered by an SSSP traversal of the weighted
# graph.  Prints one JSON line per run: the result, what the metric
# readers saw, and the kinds of reference built.
DRIVER = '''
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[2]]
from bench import harness
from repro.algos import sssp
from repro.core.graph import CSRGraph

seen, refs = [], []
read = harness.reader


def spy(name):
    def read_and_keep(run):
        seen.append(run)
        return read(name)(run)
    return read_and_keep


harness.reader = spy
Reference = harness.reference.Reference


class Counted(Reference):
    def __init__(self, src, dst, wt, n):
        refs.append(wt is not None)
        super().__init__(src, dst, wt, n)


harness.reference.Reference = Counted
weighted = {}


def fault(src, dst, wt, n):
    if wt is not None:
        weighted["graph"] = CSRGraph.from_edges(src, dst, wt, n)
        return sssp
    return lambda graph, root, **kw: sssp(weighted["graph"], root, **kw)


for factory in (None, fault):
    seen.clear()
    refs.clear()
    out = harness.run_workload(sys.argv[3], int(sys.argv[4]), 0.5, False,
                               config_override={"scale": 8},
                               entry_factory=factory)
    qs = seen[0].queries
    print(json.dumps({"out": out, "refs": sorted(refs),
                      "kinds": sorted({q.weighted for q in qs}),
                      "cached": sum(q.cached for q in qs),
                      "counters": seen[0].counters,
                      "ordered": all(0 <= q.t_submit <= q.t_done
                                     for q in qs),
                      "completed": len(qs)}), flush=True)
'''


def _files(root: Path) -> dict:
    return {p.relative_to(root).as_posix(): p.read_bytes()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts and ".cache" not in p.parts}


@pytest.fixture(scope="module")
def extended(tmp_path_factory):
    """A copy of the benchmark with the open loop, the Zipf stream and
    the mixed mix added as new files, and a cell for them added to its
    ``BENCHMARK.json``; the two runs of :data:`DRIVER` in it."""
    copy = tmp_path_factory.mktemp("extended")
    shutil.copytree(ROOT / "bench", copy / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".cache"))
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    manifest["workloads"].append(MIXED_CELL)
    (copy / "BENCHMARK.json").write_text(json.dumps(manifest, indent=2))
    new = {"bench/loops/open_fixture.py": OPEN_LOOP,
           "bench/roots/zipf_fixture.py": ZIPF_STREAM,
           "bench/traffic/mixed-open-fixture.json": json.dumps(MIXED_MIX)}
    for rel, text in new.items():
        assert not (copy / rel).exists()
        (copy / rel).write_text(text)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    proc = subprocess.run(
        [sys.executable, "-c", DRIVER, str(copy), str(ROOT / "src"),
         MIXED_CELL["name"], str(SEED)],
        cwd=copy, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    sound, faulty = (json.loads(line) for line in proc.stdout.splitlines())
    return copy, set(new), sound, faulty, proc.stderr


def test_new_files_run_correct(extended):
    _, _, sound, _, stderr = extended
    out = sound["out"]
    assert out["correct"] is True and out["failed"] == 0
    assert out["checks"]["dist_mismatch"] == {"value": 0, "limit": 0}
    assert out["checks"]["queries_compared"]["value"] >= 2
    # both kinds ran, each row checked against the reference of its kind
    assert sound["kinds"] == [False, True] and sound["refs"] == [False, True]
    assert sound["cached"] >= 1 and sound["ordered"]
    assert sound["counters"] == {"cache_hits": sound["cached"]}
    # arrivals outrun the server: requests still queued at the close are
    # attempted, neither completed nor failed, and their count printed
    queued = int(re.search(r"queued=(\d+)", stderr).group(1))
    assert queued > 0 and out["attempted"] == sound["completed"] + queued
    assert {"mteps", "setup_s"} <= set(out["metrics"])


def test_no_existing_file_changed(extended):
    copy, new, _, _, _ = extended
    before, after = _files(ROOT / "bench"), _files(copy / "bench")
    added = {f"bench/{k}" for k in set(after) - set(before)}
    assert added == new
    assert {k: after[k] for k in before} == before
    manifest = json.loads((copy / "BENCHMARK.json").read_text())
    assert manifest["workloads"].pop() == MIXED_CELL
    assert manifest == json.loads((ROOT / "BENCHMARK.json").read_text())


def test_sssp_row_for_a_bfs_query_is_not_correct(extended):
    _, _, _, faulty, _ = extended
    out = faulty["out"]
    assert out["correct"] is False
    assert out["checks"]["dist_mismatch"]["value"] > 0
    assert out["failed"] >= 1
