"""BENCHMARK.json and every file it names, against the benchmark's
contract: keys, names, limits, and a file for each configuration,
traffic mix and metric."""

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    text = (ROOT / "BENCHMARK.json").read_text()
    assert len(text.encode()) <= 64 * 1024
    return json.loads(text)


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    cmd = manifest["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    assert 1 <= len(manifest["paths"]) <= 16
    for p in manifest["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    for w in cmd[1:]:
        if "/" in w:
            assert any(w.startswith(p + "/") for p in manifest["paths"])
    rs = manifest["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51


def test_names_unique_and_valid(manifest):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in manifest[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), group
    metrics = [x["name"] for x in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(metrics) == len(set(metrics))


def test_configs(manifest):
    files = set()
    used = {w["config"] for w in manifest["workloads"]}
    assert 1 <= len(manifest["configs"]) <= 24
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("bench/") and c["file"] not in files
        files.add(c["file"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) and k in cfg for k in c["reduced"])
        assert (BENCH / "gen" / f"{cfg['generator']}.py").is_file()


def test_workloads(manifest):
    from bench import harness
    configs = {c["name"] for c in manifest["configs"]}
    ws = manifest["workloads"]
    assert 1 <= len(ws) <= 24
    pairs = set()
    for w in ws:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"]) and _line(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        traffic = json.loads(
            (BENCH / "traffic" / f"{w['traffic']}.json").read_text())
        assert traffic["name"] == w["traffic"]
        entry = traffic["entry"]
        assert all(":" in e for e in (entry.values()
                                      if isinstance(entry, dict) else [entry]))
        assert harness.kinds(traffic)
        loop = traffic.get("loop", harness.DEFAULT_LOOP)
        assert (BENCH / "loops" / f"{loop}.py").is_file()
        assert (BENCH / "roots" / f"{traffic['roots']}.py").is_file()
    assert sum(w["chips"] == 4 for w in ws) <= max(1, len(ws) // 2)


def test_metrics(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and 1 <= len(e2e) <= 16
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert 1 <= len(manifest["per_layer"]) <= 128
    layers = {}
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert _line(m["layer"]) and m["moves"] in e2e
        reporting = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", reporting)) <= reporting
        layers.setdefault(m["layer"], m["layer"])
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file(), m["name"]


def test_every_cell_reports_enough(manifest):
    for w in manifest["workloads"]:
        e2e = [m["name"] for m in manifest["end_to_end"]
               if w["name"] in m.get("workloads", [w["name"]])]
        layer = [m["name"] for m in manifest["per_layer"]
                 if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in e2e and len(e2e) >= 2 and layer, w["name"]


def test_metric_readers_load():
    from bench import harness
    for path in sorted((BENCH / "metrics").glob("*.py")):
        assert callable(harness.reader(path.stem))


def test_peaks_table():
    from bench import harness
    v5e = harness.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        harness.peaks("TPU v0 imaginary")
