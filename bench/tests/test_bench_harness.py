"""The harness end to end on the CPU at small sizes: it refuses a
machine without a TPU, a sound run comes out correct, and a run with
the timed path broken underneath, or with the control in the program's
place, comes out not correct."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, harness

ROOT = Path(__file__).resolve().parents[2]
SMALL = {"g500-s20-sssp": {"scale": 8}, "g500-s20-bfs": {"scale": 8},
         "road-fla-sssp": {"side": 20}}
SEED = 2**34 + 11          # larger than 32 signed bits hold


def _run(cell, **kw):
    return harness.run_workload(cell, SEED, 0.3, False,
                                config_override=SMALL[cell], **kw)


def _cli(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "g500-s20-sssp",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_refuses_a_machine_without_a_tpu():
    proc = _cli(ROOT)
    _no_result(proc)
    assert "not a TPU" in proc.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _cli(tmp_path)
    _no_result(proc)
    assert "No module named 'repro'" in proc.stderr


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_sound_run_is_correct(cell):
    out = _run(cell)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    assert list(out)[-1] == "checks"
    assert out["checks"]["dist_mismatch"] == {"value": 0, "limit": 0}
    assert {"mteps", "setup_s"} <= set(out["metrics"])
    assert out["device"]["platform"] == "cpu"


def _planted(fault):
    """An entry factory: the program's own entry with ``fault`` planted
    in its fixed-point loop, where the distances are produced."""
    from repro.core import engine
    real = engine._fused.run_fixed_point

    def broken(graph, state, strategy, dist0, mask0, **kw):
        if fault == "unchanged":
            return dist0, 1, 0
        dist, iterations, edges = real(graph, state, strategy, dist0,
                                       mask0, **kw)
        far = jnp.argmax(jnp.where(dist < harness.reference.UNREACHED,
                                   dist, -1))
        return dist.at[far].add(1), iterations, edges

    def make(src, dst, wt, n):
        entry = harness.resolve_entry(
            harness.cell("g500-s20-sssp").traffic["entry"])

        def run(graph, root, **kw):
            engine._fused.run_fixed_point = broken
            try:
                return entry(graph, root, **kw)
            finally:
                engine._fused.run_fixed_point = real
        return run
    return make


@pytest.mark.parametrize("fault", ["unchanged", "altered"])
def test_planted_fault_is_not_correct(fault):
    out = _run("g500-s20-sssp", entry_factory=_planted(fault))
    assert out["correct"] is False
    assert out["checks"]["dist_mismatch"]["value"] > 0
    assert out["failed"] >= 1


@pytest.mark.parametrize("cell,kind", [("road-fla-sssp", "bf16"),
                                       ("g500-s20-bfs", "short"),
                                       ("g500-s20-sssp", "short"),
                                       ("g500-s20-sssp", "fp8")])
def test_control_is_not_correct(cell, kind):
    out = _run(cell, entry_factory=control.factory(kind))
    assert out["correct"] is False
    assert out["checks"]["dist_mismatch"]["value"] > 0


def test_same_keys_every_seed():
    """Every seed runs the same structural search keys in the same
    order; only their labels differ."""
    cfg = dict(harness.cell("g500-s20-sssp").config, scale=9)
    gen = harness.load_module(harness.BENCH / "gen" / "kronecker.py")
    stream = harness.load_module(harness.BENCH / "roots" /
                                 "graph500_search_keys.py")
    keys = []
    for seed in (2**33 + 1, 2**33 + 2):
        src, _, _, n, labels = gen.generate(cfg, seed)
        deg = np.bincount(src, minlength=n)
        k = stream.keys(deg, labels, cfg["graph_seed"],
                        harness.cell("g500-s20-sssp").traffic)
        assert len(set(k.tolist())) == len(k) and (deg[k] > 0).all()
        assert len(k) == (deg > 0).sum()
        keys.append(np.argsort(labels)[k])        # back to the structure
    np.testing.assert_array_equal(*keys)


def test_bf16_control_matches_reference_where_exact():
    """Below 256 every distance is exact in bfloat16: on a small
    Kronecker graph the bf16 control reads 0, as it must."""
    out = _run("g500-s20-bfs", entry_factory=control.factory("bf16"))
    assert out["checks"]["dist_mismatch"]["value"] == 0
