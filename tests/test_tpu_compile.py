"""Compile the chip's programs for a described TPU v5e, with no chip.

The TPU compiler ships with jaxlib, so a program can be compiled for a
``v5e:2x2`` topology that is described, not attached: Mosaic refuses a
Pallas kernel that needs more scoped VMEM than it may use, and XLA
refuses a sharding it cannot partition, exactly as on the chip.  These
tests hold

* the Pallas relax kernels (``relax_lanes``, ``wd_relax_lanes``,
  ``find_offsets``) at the shapes ``chip_smoke.py`` phase 3 runs them
  (the suite's scale-14 rmat graph);
* the footprint model ``relax.kernel_vmem_blocks`` to Mosaic's verdict
  on both sides of the limit: a shape it puts just under the limit
  compiles, and shapes it puts over, by their tables or by the chunk
  loop's scratch alone, Mosaic refuses too;
* the sharded fused fixed point over a 4-device mesh, whose replicated
  rank-0 outputs need ``P()`` out_specs;
* the fused AD loop on a graph in the packed edge layout: the chip's
  compiler keeps one gather of the ``[E]`` word per lane map where the
  plain layout has two (``col`` and the weights), and does not copy
  the word's gather into each of its consumers.

The topology is described inside a module-scoped fixture, never at
import time: only one process at a time may load the TPU library, and
every test worker imports this file.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import fused, operators, shard
from repro.core.graph import CSRGraph
from repro.core.schedule import DEFAULT_SCHEDULE, default_schedule
from repro.kernels import find_offsets, relax

#: the suite rmat graph of chip_smoke.py phase 3:
#: rmat_graph(scale=14, edge_factor=8, weighted=True, seed=1)
RMAT_N, RMAT_E = 16384, 130387


@pytest.fixture(scope="module")
def topo():
    pytest.importorskip("libtpu", reason="the TPU compiler is not installed")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    """Compiles for a described chip cannot be read back from the
    persistent cache; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)


def _spec(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _lanes_args(s, n, lanes):
    return (_spec(s, (n,)), _spec(s, (lanes,)), _spec(s, (lanes,)),
            _spec(s, (lanes,)), _spec(s, (lanes,), jnp.bool_))


def _wd_args(s, n, f, e):
    return (_spec(s, (n,)), _spec(s, (f,)), _spec(s, (f,)), _spec(s, (f,)),
            _spec(s, (f,)), _spec(s, (e,)), _spec(s, (e,)))


def _total(kernel, **shape):
    return sum(relax.kernel_vmem_blocks(kernel, **shape).values())


def _kernel_in(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_relax_lanes_compiles_at_phase3_shape(one_chip, no_cache):
    """BS under backend="pallas": one block of N lanes over N nodes."""
    assert _total("lanes", n=RMAT_N) <= relax.VMEM_BUDGET_BYTES
    compiled = relax.relax_lanes.lower(
        *_lanes_args(one_chip, RMAT_N, RMAT_N), interpret=False).compile()
    assert _kernel_in(compiled)


def test_wd_relax_lanes_compiles_at_phase3_shape(one_chip, no_cache):
    """WD under backend="pallas": E lanes over the N-slot frontier."""
    assert (_total("wd", n=RMAT_N, f=RMAT_N, e=RMAT_E)
            <= relax.VMEM_BUDGET_BYTES)
    compiled = relax.wd_relax_lanes.lower(
        *_wd_args(one_chip, RMAT_N, RMAT_N, RMAT_E), cap_work=RMAT_E,
        interpret=False).compile()
    assert _kernel_in(compiled)


def test_find_offsets_compiles_at_phase3_shape(one_chip, no_cache):
    compiled = find_offsets.find_offsets.lower(
        _spec(one_chip, (RMAT_N,)), cap_work=RMAT_E,
        interpret=False).compile()
    assert _kernel_in(compiled)


def test_vmem_model_refusal_matches_mosaic(one_chip, no_cache):
    """A table the model puts over the scoped-VMEM limit is one Mosaic
    refuses as well: the model may be strict, never lenient."""
    n = 1 << 21
    assert _total("lanes", n=n) > relax.VMEM_BUDGET_BYTES
    with pytest.raises(Exception, match="(?i)vmem|scoped"):
        relax.relax_lanes.lower(*_lanes_args(one_chip, n, 1024),
                                interpret=False).compile()


def test_vmem_model_counts_the_loop_scratch(one_chip, no_cache):
    """Tiny tables, large tiles: only the chunk loop's modelled scratch
    takes the kernel over the limit, and Mosaic refuses it too."""
    n, tiles = 4096, dict(tile_r=32, tile_c=256, chunk=128)
    blocks = relax.kernel_vmem_blocks("lanes", n=n, **tiles)
    tables = sum(v for k, v in blocks.items() if k != "scratch")
    assert tables < relax.VMEM_BUDGET_BYTES // 100
    assert blocks["scratch"] > relax.VMEM_BUDGET_BYTES
    with pytest.raises(Exception, match="(?i)vmem|scoped"):
        relax.relax_lanes.lower(*_lanes_args(one_chip, n, 8192),
                                interpret=False, **tiles).compile()


def test_vmem_model_admits_a_kernel_just_under_the_limit(one_chip,
                                                         no_cache):
    """A shape the model puts within 5% under the limit compiles."""
    n, tiles = 4096, dict(tile_r=16, tile_c=256, chunk=512)
    total = _total("lanes", n=n, **tiles)
    assert 0.95 * relax.VMEM_BUDGET_BYTES < total <= relax.VMEM_BUDGET_BYTES
    compiled = relax.relax_lanes.lower(*_lanes_args(one_chip, n, 4096),
                                       interpret=False, **tiles).compile()
    assert _kernel_in(compiled)


def test_sharded_fused_wd_compiles_on_four_chips(topo, no_cache):
    """The lockstep sharded WD fixed point, partitioned over a 2x2 mesh:
    its replicated outputs (the rank-0 iteration and edge counters among
    them) take ``P()`` out_specs."""
    mesh = Mesh(np.array(topo.devices[:4]), (shard.AXIS,))
    split = NamedSharding(mesh, P(shard.AXIS))
    repl = NamedSharding(mesh, P())
    s, n, n_loc, e_loc = 4, 256, 64, 1024
    sg = shard.ShardedCSRGraph(
        row_ptr=_spec(split, (s, n_loc + 1)), col=_spec(split, (s, e_loc)),
        wt=_spec(split, (s, e_loc)), node_base=_spec(split, (s,)),
        num_local=_spec(split, (s,)), num_nodes=n, num_edges=s * e_loc,
        num_shards=s, nodes_per_shard=n_loc, edges_per_shard=e_loc)
    compiled = shard._sharded_fixed_point.lower(
        sg, _spec(repl, (1,)), _spec(repl, (n,)),
        _spec(repl, (n,), jnp.bool_), kernel="WD", max_iterations=1000,
        sched=DEFAULT_SCHEDULE, op=operators.shortest_path, mesh=mesh,
        backend="xla").compile()
    text = compiled.as_text()
    assert "all-reduce" in text        # the per-chunk halo combine
    mem = compiled.memory_analysis()
    # each device holds its own shard's block, not the whole stack
    assert mem.argument_size_in_bytes < 2 * (s * e_loc * 4)


def _lanemap_edge_gathers(text, e):
    """Instructions of optimized HLO named ``.../lanemap/gather`` that
    read an ``s32[e]`` operand (a gather, or the fusion around one)."""
    shape = dict(re.findall(r"^\s*(?:ROOT )?%([\w.-]+) = (\S+) ", text,
                            re.M))
    count = 0
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%[\w.-]+ = \S+ (?:fusion|gather)\((.*?)\)",
                     line)
        if not m or not re.search(r'op_name="[^"]*lanemap/gather"', line):
            continue
        operands = re.findall(r"%([\w.-]+)", m.group(1))
        count += any(shape.get(o, "").startswith(f"s32[{e}]{{")
                     for o in operands)
    return count


def test_packed_fused_ad_gathers_each_edge_once(one_chip, no_cache):
    """The AD loop at a small shape, packed and plain: every lane map of
    the packed layout keeps one ``[E]`` gather where the plain layout
    keeps two."""
    n, e = 4096, 49157
    row_ptr, edges = _spec(one_chip, (n + 1,)), _spec(one_chip, (e,))
    plain = CSRGraph(row_ptr, edges, edges, n, e, 1000)
    # the same shapes in the packed layout: a head takes 12 bits
    packed = CSRGraph.tree_unflatten((n, e, 1000, 12),
                                     (row_ptr, edges, edges))
    sched = dataclasses.replace(default_schedule("AD"), mdt=64)
    counts = []
    for g in (plain, packed):
        text = fused._fixed_point.lower(
            g, _spec(one_chip, (1,)), _spec(one_chip, (n,)),
            _spec(one_chip, (n,), jnp.bool_), kernel="AD",
            max_iterations=1000, sched=sched, op=operators.shortest_path,
            backend="xla").compile().as_text()
        counts.append(_lanemap_edge_gathers(text, e))
    assert counts[1] > 0 and counts[0] == 2 * counts[1]
