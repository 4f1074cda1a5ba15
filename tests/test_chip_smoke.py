"""chip_smoke.py's phases, on the CPU at a tiny scale.

The script itself refuses to run without a TPU; these tests call its
phase functions directly (Pallas in interpret mode), so a wrong path,
argument or comparison shows up here before it costs chip time.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

SCALE = 8


@pytest.fixture(scope="module")
def graph():
    return chip_smoke.build_graph(SCALE)


@pytest.fixture(scope="module")
def roots(graph):
    return chip_smoke.top_degree_nodes(graph, chip_smoke.NUM_ROOTS)


@pytest.fixture(scope="module")
def want(graph, roots):
    return {"sssp": chip_smoke.oracle(graph, roots, True),
            "bfs": chip_smoke.oracle(graph, roots, False)}


def test_oracle_matches_engine_reference(graph, roots, want):
    from repro.core.engine import reference_distances
    np.testing.assert_array_equal(want["sssp"][0],
                                  reference_distances(graph, roots[0]))


def test_build_graph_is_graph500_graph(graph):
    from repro.data import graph500_graph
    ref = graph500_graph(scale=SCALE, edge_factor=16, weighted=True,
                         seed=chip_smoke.SEED)
    for a, b in ((graph.row_ptr, ref.row_ptr), (graph.col, ref.col),
                 (graph.wt, ref.wt)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_phase1_every_run_matches_oracle(graph, roots, want, capsys):
    chip_smoke.run_jobs(chip_smoke.phase1_jobs(graph, roots, want))
    out = capsys.readouterr().out
    assert out.count(" ok ") == (2 * len(chip_smoke.PHASE1_RUNS)
                                 * chip_smoke.NUM_ROOTS)


def test_phase1_mismatch_raises(graph, roots, want):
    bad = {k: v.copy() for k, v in want.items()}
    bad["bfs"][1, roots[0]] += 1
    with pytest.raises(AssertionError, match="differ from the oracle"):
        chip_smoke.phase1_run(graph, roots, bad, "WD", "fused", "bfs")


def test_run_jobs_raises_first_failure_without_waiting():
    import threading
    release = threading.Event()

    def boom():
        raise ValueError("boom")
    with pytest.raises(ValueError, match="boom"):
        chip_smoke.run_jobs([lambda: release.wait(60), boom])
    release.set()
    assert chip_smoke.run_jobs([lambda: 1, lambda: 2]) == [1, 2]


def test_phase2_serves_all_queries(graph, roots, want, capsys):
    chip_smoke.phase2(graph, roots, want)
    out = capsys.readouterr().out
    assert f"queries={chip_smoke.SERVE_QUERIES} ok" in out


def test_phase3_pallas_matches_xla_and_refuses_oversized(capsys):
    from repro.data import graph500_graph
    from repro.kernels import relax
    # 2**20 nodes: the WD kernel's node and slot tables alone (28 bytes
    # per node) pass the scoped-VMEM limit
    big = graph500_graph(scale=20, edge_factor=1, weighted=True, seed=1)
    assert 28 * big.num_nodes > relax.VMEM_BUDGET_BYTES
    chip_smoke.phase3(big, scale=7)
    out = capsys.readouterr().out
    assert out.count("backend=pallas") == 2
    assert "pallas refused the phase-1 graph up front" in out


def test_shards_phase_single_device_mesh(graph, roots, want, capsys):
    chip_smoke.phase_shards(graph, roots[0], want["sssp"][0], shards=1)
    out = capsys.readouterr().out
    assert "strategy=WD-async shards=1 ok" in out
    assert "bit-identical to single-device" in out


def test_main_runs_every_phase_and_ends_with_the_result(monkeypatch,
                                                       capsys):
    """``main`` end to end, with the platform check bypassed: every
    phase-1 run, the server and phase 3 report ``ok``, and the last line
    is the result object."""
    import json
    from repro.data import rmat_graph
    from repro.kernels import relax
    monkeypatch.setattr(chip_smoke, "require_tpu",
                        chip_smoke.device_summary)
    monkeypatch.setattr(chip_smoke, "PHASE1_SCALE", SCALE)
    monkeypatch.setattr(chip_smoke, "PHASE3_SCALE", 7)
    # keep this worker's later tests off the persistent compile cache
    monkeypatch.setattr(chip_smoke.compile_cache, "enable", lambda: "off")
    # a VMEM limit that admits phase 3's graph and not phase 1's, as the
    # chip's 16 MiB does at the real scales
    small = rmat_graph(scale=7, edge_factor=8, weighted=True, seed=1)
    big = chip_smoke.build_graph(SCALE)
    need = [sum(relax.kernel_vmem_blocks(
        "wd", n=g.num_nodes, f=g.num_nodes, e=g.num_edges).values())
        for g in (small, big)]
    assert need[0] < need[1]
    monkeypatch.setattr(relax, "VMEM_BUDGET_BYTES", need[0])
    assert chip_smoke.main([]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    text = "\n".join(lines)
    assert text.count("phase1 ") == (2 * len(chip_smoke.PHASE1_RUNS)
                                     * chip_smoke.NUM_ROOTS)
    assert "phase2 serve" in text and "pallas refused" in text
    # the stepped runs finish before any fused run starts
    order = [ln for ln in lines if "phase1 " in ln]
    stepped = [i for i, ln in enumerate(order) if "mode=stepped" in ln]
    assert stepped == list(range(len(stepped)))
    last = json.loads(lines[-1])
    assert last["ok"] is True and last["device"]["count"] >= 1


def test_script_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(ROOT,
                                                        "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "not a TPU" in proc.stderr


def test_script_alone_fails(tmp_path):
    """Without the rest of the repo the script cannot import the engine
    and ends non-zero, printing no result."""
    import shutil
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
