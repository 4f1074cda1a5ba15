"""The benchmark's own generators: undirected, one weight per pair, the
same graph for the same seed."""

import numpy as np
import pytest

from bench import harness


def _gen(config, **small):
    mod = harness.load_module(harness.BENCH / "gen" /
                              f"{config['generator']}.py")
    cfg = dict(config, **small)
    return lambda seed: mod.generate(cfg, seed)


CASES = {
    "graph500-s20": {"scale": 9},
    "road-fla": {"side": 24},
}


@pytest.fixture(params=sorted(CASES))
def gen(request):
    cfg = harness.load_json(harness.BENCH / "configs" /
                            f"{request.param}.json")
    return _gen(cfg, **CASES[request.param]), cfg


def test_symmetric_one_weight_per_pair(gen):
    make, cfg = gen
    src, dst, wt, n, _ = make(2**33 + 5)
    assert src.dtype == dst.dtype == wt.dtype == np.int32
    assert np.all(src != dst)
    lo, hi = cfg["weights"]
    assert wt.min() >= lo and wt.max() <= hi
    fwd = {(int(s), int(d)): int(w) for s, d, w in zip(src, dst, wt)}
    assert len(fwd) == len(src)                       # no duplicate arcs
    assert all(fwd[(d, s)] == w for (s, d), w in fwd.items())


def test_same_seed_same_graph(gen):
    make, _ = gen
    a, b, c = make(7), make(7), make(8)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert any(not np.array_equal(x, y) for x, y in zip(a[:3], c[:3]))


def test_every_seed_same_shape(gen):
    """A new seed relabels and reweighs one structure: node, arc and
    degree counts (what the engine compiles for) stay the same."""
    make, _ = gen
    a, c = make(2**31 + 3), make(2**31 + 4)
    assert a[3] == c[3] and len(a[0]) == len(c[0])
    deg = [np.sort(np.bincount(x[0], minlength=x[3])) for x in (a, c)]
    np.testing.assert_array_equal(*deg)


def test_labels_map_the_structure(gen):
    """``labels`` carries each node of the structure to its label in the
    seed's graph: mapped back, two seeds give the same pairs."""
    make, _ = gen
    a, c = make(2**31 + 3), make(2**31 + 4)
    for x in (a, c):
        assert np.array_equal(np.sort(x[4]), np.arange(x[3]))
    inv = [np.argsort(x[4]) for x in (a, c)]
    arcs = [set(zip(i[x[0]].tolist(), i[x[1]].tolist()))
            for i, x in zip(inv, (a, c))]
    assert arcs[0] == arcs[1]


def test_graph500_seed_only_relabels():
    """Graph500: the weights come with the structure, so two seeds give
    the same weighted graph under two labellings."""
    cfg = harness.load_json(harness.BENCH / "configs" / "graph500-s20.json")
    a, c = (_gen(cfg, scale=9)(s) for s in (2**31 + 3, 2**31 + 4))
    assert not np.array_equal(a[4], c[4])
    weighted = []
    for src, dst, wt, _, labels in (a, c):
        inv = np.argsort(labels)
        weighted.append(sorted(zip(inv[src].tolist(), inv[dst].tolist(),
                                   wt.tolist())))
    assert weighted[0] == weighted[1]


def test_road_seed_only_relabels_the_grid():
    """Road grid: the weights come with the structure, and a seed's
    labels are a symmetry of the square, so grid neighbours stay grid
    neighbours and two seeds give the same weighted graph relabelled."""
    cfg = harness.load_json(harness.BENCH / "configs" / "road-fla.json")
    side = 16
    a, c = (_gen(cfg, side=side)(s) for s in (2**31 + 3, 2**31 + 4))
    assert not np.array_equal(a[4], c[4])
    weighted = []
    for src, dst, wt, _, labels in (a, c):
        grid = labels.reshape(side, side)
        assert all(set(np.abs(np.diff(grid, axis=ax)).ravel().tolist())
                   <= {1, side} for ax in (0, 1))
        inv = np.argsort(labels)
        weighted.append(sorted(zip(inv[src].tolist(), inv[dst].tolist(),
                                   wt.tolist())))
    assert weighted[0] == weighted[1]


def test_graph500_structure_is_cached(tmp_path, monkeypatch):
    """The pairs are drawn once per configuration and loaded after."""
    mod = harness.load_module(harness.BENCH / "gen" / "kronecker.py")
    monkeypatch.setattr(mod, "CACHE", tmp_path)
    cfg = dict(harness.load_json(harness.BENCH / "configs" /
                                 "graph500-s20.json"), scale=8)
    first = mod.structure(cfg)
    files = list(tmp_path.iterdir())
    assert len(files) == 1 and files[0].suffix == ".npy"
    other = mod.structure(dict(cfg, graph_seed=cfg["graph_seed"] + 1))
    assert len(list(tmp_path.iterdir())) == 2
    assert not np.array_equal(other, first)
    monkeypatch.setattr(mod, "_pairs", None)     # a new draw would fail
    np.testing.assert_array_equal(mod.structure(cfg), first)


def test_graph500_shape():
    cfg = harness.load_json(harness.BENCH / "configs" / "graph500-s20.json")
    src, dst, wt, n, _ = _gen(cfg, scale=10)(3)
    assert n == 1024
    deg = np.bincount(src, minlength=n)
    # Kronecker skew: a hub far above the mean, and isolated nodes
    assert deg.max() > 10 * deg.mean() and (deg == 0).any()
    assert len(src) < 2 * 16 * n


def test_road_shape():
    cfg = harness.load_json(harness.BENCH / "configs" / "road-fla.json")
    assert cfg["full_side"] ** 2 == 1_071_225
    assert cfg["side"] < cfg["full_side"] and "side" in cfg["reduced"]
    src, dst, wt, n, _ = _gen(cfg, side=32)(3)
    deg = np.bincount(src, minlength=n)
    assert n == 32 * 32 and deg.min() >= 2 and deg.max() <= 12
