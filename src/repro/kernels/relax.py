"""Pallas relax kernels: fused scatter-combine in VMEM (docs/backends.md).

After PRs 1–4 every relax kernel was plain XLA gather/scatter — Pallas
appeared only in the :mod:`repro.kernels.find_offsets` merge-path helper,
which "A Programming Model for GPU Load Balancing" (arXiv:2301.04792)
argues is exactly backwards: the *schedule* (BS/WD/HP work assignment)
and the *per-edge apply* should be fused in one tiled kernel.  This
module is that kernel layer — the ``backend="pallas"`` implementation of
the relax hot path that the strategies and the fused engine dispatch
into (see ``repro.core.strategies`` / ``repro.core.fused``).

Two kernels, both parameterized over the :class:`repro.core.operators.EdgeOp`
monoid (min/max/add):

* :func:`relax_lanes` — **direct-mapped lanes**: each work item already
  knows its ``(src, dst, w)`` triple (BS edge columns, HP's MDT tiles,
  EP's edge worklist).  The kernel fuses the ``dist[src]`` gather, the
  operator's ``message``, the activation test against ``dist[dst]`` and
  the *segment-local scatter-combine* in VMEM.
* :func:`wd_relax_lanes` — **merge-path fused**: work item *k* first
  locates its (frontier slot, local edge) by ranking *k* against the
  inclusive degree prefix sum — the ``find_offsets`` search — and then
  relaxes that edge *in the same kernel*.  The rank (the old
  ``node_idx`` array) never leaves VMEM: no materialized ``[cap_work]``
  index array, no separate search dispatch.

TPU mapping (as in repro.kernels.find_offsets): dynamic
per-lane gathers don't vectorize on the VPU, so every gather/scatter is
a *broadcast compare* streamed over ``chunk``-wide table chunks resident
in VMEM:

* gather   ``dist[src]``:  ``Σ_chunk Σ_n [src == n] · dist[n]``
  (exactly-one-hot sum — pure VPU compare/select/add);
* scatter-combine into the proposal:  for each ``chunk``-node output
  chunk, fold ``where(dst == n  ∧  improves, cand, identity)`` over the
  tile's lanes with the monoid's reduction.  The fold happens entirely
  in the VMEM-resident output block, which Pallas revisits across grid
  steps (constant ``index_map``) — one accumulator, many lane tiles.

Block/lane shapes come from the :class:`repro.core.schedule.Schedule`
fields ``tile_r``/``tile_c``/``chunk`` (static jit arguments here); the
module constants :data:`TILE_R`/:data:`TILE_C`/:data:`CHUNK` are their
defaults — the pre-extraction constants, kept so zero-config callers
are bit-identical to the historical kernels.  Any feasible tile shape
yields the same results: the built-in monoids are associative and
commutative on int32, so regrouping the per-destination fold across
tiles cannot change the outcome (tests/test_schedule.py exercises
non-default shapes against the XLA path).

The kernels return a dense **proposal** array (the monoid fold of every
improving candidate per destination, identity elsewhere) instead of
mutating ``dist``: the caller applies it with one elementwise
:func:`apply_proposal`.  Because the built-in monoids are associative
and commutative on int32 (min/max idempotent; add wraps consistently),
folding per-destination candidates in kernel tile order is
**bit-identical** to the XLA path's ``dist.at[dst].min/max/add``
scatter — the parity contract ``tests/test_backends.py`` enforces for
every strategy × operator × mode.

Every entry point takes ``interpret=`` (default: on for CPU backends),
so CI exercises the same kernel code path the TPU runs compiled —
the same recipe as :mod:`repro.kernels.find_offsets`.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import operators
from repro.core.operators import EdgeOp

TILE_R, TILE_C = 8, 128          # VPU vector registers (schedule default)
TILE = TILE_R * TILE_C           # work items per grid step
CHUNK = 128                      # table chunk streamed per compare pass
_SUBLANES = 8                    # rows of one VMEM tile

#: the scoped-VMEM limit Mosaic compiles these kernels against on TPU
#: v5e ("Scoped allocation ... limit 16.00M" when it refuses one).  The
#: static feasibility oracle :mod:`repro.analysis.vmem` fails any kernel
#: whose footprint exceeds this, the block-size candidate enumeration in
#: :mod:`repro.core.costmodel` rejects a configuration before ever
#: compiling it, and ``engine.run(..., backend="pallas")`` refuses a
#: graph whose tables do not fit (:func:`check_vmem`).
VMEM_BUDGET_BYTES = 16 * 1024 * 1024

#: per-lane bytes of the in-kernel chunk loop's temporaries (the
#: broadcast-compare blocks, the lane tiles and the loop carries):
#: ``tile · (_SCRATCH_LANE_BYTES[kernel] + _SCRATCH_CHUNK_BYTES · chunk)``.
#: Fitted, with a few percent of headroom, to the smallest
#: ``vmem_limit_bytes`` under which Mosaic accepts each kernel (jax
#: 0.9.0, libtpu 0.0.34, v5e), bisected over tile_r ∈ {8, 16, 32},
#: tile_c ∈ {128, 256} and chunk ∈ {128, 256, 512};
#: tests/test_tpu_compile.py holds the model to Mosaic's verdict.
_SCRATCH_LANE_BYTES = {"lanes": 2400, "wd": 3000}
_SCRATCH_CHUNK_BYTES = 3


def kernel_vmem_blocks(kernel: str, *, n: int, f: int | None = None,
                       e: int | None = None, itemsize: int = 4,
                       tile_r: int = TILE_R, tile_c: int = TILE_C,
                       chunk: int = CHUNK) -> dict:
    """Scoped-VMEM footprint of one kernel launch, in bytes per block.

    The footprint model backing the budget checks
    (:mod:`repro.analysis.vmem`, :func:`check_vmem`): the whole-array
    tables (constant ``index_map`` ⇒ resident for the whole launch, one
    buffer each, padded to whole ``[8, chunk]`` tiles exactly as the
    entry points pad them) plus the chunk loop's scratch.  Keep in sync
    with the ``in_specs``/``out_specs`` of :func:`relax_lanes` and
    :func:`wd_relax_lanes` below.  Mosaic counts a table only when XLA
    leaves it in HBM, so the model counts every table: it may refuse a
    launch Mosaic would accept, never the reverse.

    ``kernel`` is ``"lanes"`` or ``"wd"``; ``n``/``f``/``e`` are the
    *unpadded* node / frontier-slot / edge counts; ``itemsize`` is the
    operator dtype's width (int32 ⇒ 4).  ``tile_r``/``tile_c``/``chunk``
    evaluate a candidate :class:`~repro.core.schedule.Schedule`'s block
    shapes — the feasibility oracle the block-size autotuner filters
    candidates through.
    """
    if kernel not in _SCRATCH_LANE_BYTES:
        raise ValueError(f"unknown kernel {kernel!r}; expected "
                         f"'lanes' or 'wd'")
    n_pad = _table_len(n, chunk)
    blocks = {
        "dist": n_pad * itemsize,            # full input
        "proposal": n_pad * itemsize,        # full output accumulator
        "updated": n_pad * 4,                # full output accumulator
        "scratch": tile_r * tile_c * (_SCRATCH_LANE_BYTES[kernel]
                                      + _SCRATCH_CHUNK_BYTES * chunk),
    }
    if kernel == "wd":
        if f is None or e is None:
            raise ValueError("kernel 'wd' needs f= and e= shapes")
        # prefix/exclusive/start/src_ids slot tables, full inputs
        blocks["slot_tables"] = 4 * _table_len(f, chunk) * 4
        # CSR col (int32) + wt (op dtype), full inputs
        blocks["edge_tables"] = _table_len(e, chunk) * (4 + itemsize)
    return blocks


def check_vmem(kernels, *, n: int, f: int | None = None,
               e: int | None = None, itemsize: int = 4,
               tile_r: int = TILE_R, tile_c: int = TILE_C,
               chunk: int = CHUNK) -> None:
    """Raise ``ValueError`` naming the bytes when any of ``kernels``
    (``"lanes"``/``"wd"``) cannot keep its tables for this shape in
    :data:`VMEM_BUDGET_BYTES` — the up-front refusal that replaces a
    Mosaic allocation failure deep inside compilation."""
    for kernel in kernels:
        blocks = kernel_vmem_blocks(kernel, n=n, f=f, e=e,
                                    itemsize=itemsize, tile_r=tile_r,
                                    tile_c=tile_c, chunk=chunk)
        total = sum(blocks.values())
        if total > VMEM_BUDGET_BYTES:
            detail = ", ".join(f"{k}={v}" for k, v in blocks.items())
            raise ValueError(
                f"backend='pallas' keeps its tables resident in VMEM: "
                f"kernel {kernel!r} needs {total} bytes for n={n}, f={f}, "
                f"e={e} ({detail}), over the {VMEM_BUDGET_BYTES}-byte "
                f"scoped-VMEM limit; use backend='xla' for this graph, or "
                f"shards= to cut it (docs/backends.md)")


def _round_up(n: int, m: int) -> int:
    return -(-max(int(n), 1) // m) * m


def _table_len(m: int, chunk: int) -> int:
    """Padded length of an ``m``-entry table: whole ``[8, chunk]`` tiles
    of the row layout (8 sublanes), so every table has the same
    per-entry VMEM cost, whatever ``m``."""
    return _round_up(m, _SUBLANES * chunk)


def _rows(x, chunk: int):
    """A padded ``[m]`` table in the kernels' ``[m // chunk, chunk]``
    row layout (see :func:`_chunk_loop`)."""
    return x.reshape(-1, chunk)


def _full_spec(chunk: int):
    """``m``-entry table -> its whole-array ``BlockSpec`` in row layout:
    a constant ``index_map``, so the block stays resident (revisited)
    across grid steps."""
    return lambda m: pl.BlockSpec((m // chunk, chunk), lambda i: (0, 0))


def _fold2(combine: str, a, b):
    """Elementwise monoid fold (the dense combine of two proposals)."""
    if combine == "min":
        return jnp.minimum(a, b)
    if combine == "max":
        return jnp.maximum(a, b)
    return a + b


def _reduce_tile(combine: str, vals):
    """Fold a [tile_r, tile_c, chunk] candidate block over its lane axes."""
    if combine == "min":
        return jnp.min(vals, axis=(0, 1))
    if combine == "max":
        return jnp.max(vals, axis=(0, 1))
    return jnp.sum(vals, axis=(0, 1))


def _ids3(base: int, tile_r: int, tile_c: int, chunk: int):
    """[tile_r, tile_c, chunk] iota along the chunk axis, offset ``base``
    (broadcasted_iota: TPU has no 1-D iota)."""
    return base + jax.lax.broadcasted_iota(
        jnp.int32, (tile_r, tile_c, chunk), 2)


def _chunk_loop(length: int, chunk: int, body, init):
    """``lax.fori_loop`` over the ``chunk``-row layout of a
    ``length``-entry table: ``body(c, off, carry) -> carry`` for row
    ``c``, whose first entry is ``off = c * chunk``.  An in-kernel loop,
    not a Python unroll, so Mosaic allocates one chunk's temporaries and
    the kernel's code size does not grow with the table (chunks still
    fold in ascending order, so results are unchanged).  Tables are laid
    out ``[length // chunk, chunk]`` because Mosaic indexes a VMEM ref
    dynamically only along the row (sublane) axis."""
    def step(c, carry):
        return body(c, c * chunk, carry)
    return jax.lax.fori_loop(0, length // chunk, step, init)


def _onehot_gather(table_ref, idx, length: int, dtype, *, tile_r: int,
                   tile_c: int, chunk: int):
    """``table[idx]`` per lane via broadcast compare-and-sum over chunks.

    ``idx`` must be clipped into ``[0, real_length)`` by the caller so
    exactly one chunk entry matches per lane (padded tail entries have
    ids >= real length and can never match)."""
    def body(c, off, out):
        blk = table_ref[c]
        sel = idx[:, :, None] == _ids3(off, tile_r, tile_c, chunk)
        return out + jnp.sum(
            jnp.where(sel, blk[None, None, :], jnp.zeros((), dtype)),
            axis=-1)
    return _chunk_loop(length, chunk, body,
                       jnp.zeros((tile_r, tile_c), dtype))


def _combine_pass(dist_ref, prop_ref, upd_ref, cand, dst, valid, *,
                  op: EdgeOp, n_pad: int, tile_r: int, tile_c: int,
                  chunk: int):
    """The fused scatter-combine: fold this tile's improving candidates
    into the VMEM proposal/updated accumulators, one ``chunk``-node
    output chunk at a time.  Returns the per-lane improve mask (int32
    0/1)."""
    ident = jnp.asarray(op.identity, op.dtype)

    def body(c, off, imp):
        cur = dist_ref[c]
        hit = ((dst[:, :, None] == _ids3(off, tile_r, tile_c, chunk))
               & (valid[:, :, None] != 0))
        ok = hit & op.improves(cand[:, :, None], cur[None, None, :])
        vals = jnp.where(ok, cand[:, :, None], ident)
        prop_ref[c] = _fold2(op.combine, prop_ref[c],
                             _reduce_tile(op.combine, vals))
        upd_ref[c] = upd_ref[c] | jnp.any(ok, axis=(0, 1)).astype(jnp.int32)
        return imp | jnp.any(ok, axis=-1).astype(jnp.int32)
    return _chunk_loop(n_pad, chunk, body,
                       jnp.zeros((tile_r, tile_c), jnp.int32))


def _init_accumulators(prop_ref, upd_ref, *, op: EdgeOp):
    """Zero the revisited output blocks on the first grid step."""
    @pl.when(pl.program_id(0) == 0)
    def _():
        prop_ref[...] = jnp.full(prop_ref.shape, op.identity, op.dtype)
        upd_ref[...] = jnp.zeros(upd_ref.shape, jnp.int32)


# ---------------------------------------------------------------------------
# kernel 1: direct-mapped lanes (BS columns, HP tiles, EP worklists)
# ---------------------------------------------------------------------------

def _lanes_kernel(dist_ref, src_ref, dst_ref, w_ref, valid_ref,
                  prop_ref, upd_ref, imp_ref, *, op: EdgeOp, n_pad: int,
                  tile_r: int, tile_c: int, chunk: int):
    src = src_ref[...]
    dst = dst_ref[...]
    w = w_ref[...]
    valid = valid_ref[...]
    _init_accumulators(prop_ref, upd_ref, op=op)
    val_src = _onehot_gather(dist_ref, src, n_pad, op.dtype, tile_r=tile_r,
                             tile_c=tile_c, chunk=chunk)
    cand = op.message(val_src, w)
    imp_ref[...] = _combine_pass(dist_ref, prop_ref, upd_ref, cand, dst,
                                 valid, op=op, n_pad=n_pad, tile_r=tile_r,
                                 tile_c=tile_c, chunk=chunk)


@partial(jax.jit, static_argnames=("op", "interpret", "tile_r", "tile_c",
                                   "chunk"))
def relax_lanes(dist, src, dst, w, valid, *,
                op: EdgeOp = operators.shortest_path,
                interpret: bool | None = None, tile_r: int = TILE_R,
                tile_c: int = TILE_C, chunk: int = CHUNK):
    """One fused relax over ``L`` direct-mapped lanes.

    ``dist [N]``; ``src``/``dst`` (pre-clipped to ``[0, N)``), ``w`` and
    ``valid`` are per-lane ``[L]``.  ``tile_r``/``tile_c``/``chunk``
    are the schedule's block shapes (defaults: the module constants).
    Returns ``(proposal [N], updated [N] bool, improve [L] bool)`` where
    ``proposal`` is the monoid fold of every improving candidate per
    destination (identity elsewhere); apply it with
    :func:`apply_proposal`."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    tile = tile_r * tile_c
    n = dist.shape[0]
    L = src.shape[0]
    n_pad = _table_len(n, chunk)
    l_tiles = _round_up(L, tile) // tile
    l_pad = l_tiles * tile

    dist_p = _rows(jnp.pad(dist, (0, n_pad - n), constant_values=op.identity),
                   chunk)

    def lanes(x, fill, dtype):
        return (jnp.pad(x.astype(dtype), (0, l_pad - L),
                        constant_values=fill)
                .reshape(l_tiles * tile_r, tile_c))

    src_p = lanes(src, 0, jnp.int32)
    dst_p = lanes(dst, 0, jnp.int32)
    w_p = lanes(w, 0, op.dtype)
    valid_p = lanes(valid, 0, jnp.int32)

    lane_spec = pl.BlockSpec((tile_r, tile_c), lambda i: (i, 0))
    full = _full_spec(chunk)
    prop, upd, imp = pl.pallas_call(
        partial(_lanes_kernel, op=op, n_pad=n_pad, tile_r=tile_r,
                tile_c=tile_c, chunk=chunk),
        grid=(l_tiles,),
        in_specs=[full(n_pad), lane_spec, lane_spec, lane_spec, lane_spec],
        out_specs=[full(n_pad), full(n_pad), lane_spec],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad // chunk, chunk), op.dtype),
            jax.ShapeDtypeStruct((n_pad // chunk, chunk), jnp.int32),
            jax.ShapeDtypeStruct((l_tiles * tile_r, tile_c), jnp.int32),
        ],
        interpret=interpret,
    )(dist_p, src_p, dst_p, w_p, valid_p)
    prop, upd = prop.reshape(-1), upd.reshape(-1)
    return (prop[:n], upd[:n].astype(jnp.bool_),
            imp.reshape(-1)[:L].astype(jnp.bool_))


# ---------------------------------------------------------------------------
# kernel 2: merge-path search fused with the relax (WD / HP tail)
# ---------------------------------------------------------------------------

def _wd_kernel(prefix_ref, excl_ref, start_ref, srcid_ref, col_ref, wt_ref,
               dist_ref, prop_ref, upd_ref, imp_ref, *, op: EdgeOp,
               n_pad: int, f_pad: int, e_pad: int, f_real: int,
               e_real: int, has_wt: bool, tile_r: int, tile_c: int,
               chunk: int):
    tile = tile_r * tile_c
    pid = pl.program_id(0)
    base = pid * tile
    k = (base
         + jax.lax.broadcasted_iota(jnp.int32, (tile_r, tile_c), 0) * tile_c
         + jax.lax.broadcasted_iota(jnp.int32, (tile_r, tile_c), 1))
    _init_accumulators(prop_ref, upd_ref, op=op)

    # merge-path search: rank(k) = #{prefix entries <= k}, streamed over
    # chunk-wide prefix chunks (same broadcast-compare as find_offsets) —
    # the node_idx array stays in registers/VMEM, never materialized.
    def rank_chunk(c, off, rank):
        blk = prefix_ref[c]
        return rank + jnp.sum(
            (blk[None, None, :] <= k[:, :, None]).astype(jnp.int32),
            axis=-1)
    rank = _chunk_loop(f_pad, chunk, rank_chunk,
                       jnp.zeros((tile_r, tile_c), jnp.int32))
    i = jnp.minimum(rank, f_real - 1)

    gather = partial(_onehot_gather, tile_r=tile_r, tile_c=tile_c,
                     chunk=chunk)
    # slot tables: start offset, exclusive prefix, global source id
    excl = gather(excl_ref, i, f_pad, jnp.int32)
    start = gather(start_ref, i, f_pad, jnp.int32)
    src = gather(srcid_ref, i, f_pad, jnp.int32)

    total = prefix_ref[(f_real - 1) // chunk, (f_real - 1) % chunk]
    eidx = jnp.clip(start + (k - excl), 0, e_real - 1)
    valid = (k < total).astype(jnp.int32)

    dst = gather(col_ref, eidx, e_pad, jnp.int32)
    if has_wt:
        w = gather(wt_ref, eidx, e_pad, op.dtype)
    else:
        w = jnp.ones((tile_r, tile_c), op.dtype)
    val_src = gather(dist_ref, src, n_pad, op.dtype)
    cand = op.message(val_src, w)
    imp_ref[...] = _combine_pass(dist_ref, prop_ref, upd_ref, cand, dst,
                                 valid, op=op, n_pad=n_pad, tile_r=tile_r,
                                 tile_c=tile_c, chunk=chunk)


@partial(jax.jit, static_argnames=("cap_work", "op", "interpret", "tile_r",
                                   "tile_c", "chunk"))
def wd_relax_lanes(dist, prefix, exclusive, start, src_ids, col, wt, *,
                   cap_work: int, op: EdgeOp = operators.shortest_path,
                   interpret: bool | None = None, tile_r: int = TILE_R,
                   tile_c: int = TILE_C, chunk: int = CHUNK):
    """Merge-path search + relax, fused: ``cap_work`` lanes rank
    themselves against the inclusive ``prefix [F]`` (the frontier's
    remaining-degree scan), read their edge through the per-slot
    ``start``/``exclusive``/``src_ids`` tables and the CSR ``col``/``wt``
    arrays, and scatter-combine in VMEM.  ``tile_r``/``tile_c``/``chunk``
    are the schedule's block shapes.  Returns ``(proposal [N],
    updated [N] bool, improve [cap_work] bool)``."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    tile = tile_r * tile_c
    n = dist.shape[0]
    f = prefix.shape[0]
    e = col.shape[0]
    n_pad = _table_len(n, chunk)
    f_pad = _table_len(f, chunk)
    e_pad = _table_len(e, chunk)
    l_tiles = _round_up(cap_work, tile) // tile

    big = jnp.iinfo(jnp.int32).max
    dist_p = _rows(jnp.pad(dist, (0, n_pad - n), constant_values=op.identity),
                   chunk)
    prefix_p = _rows(jnp.pad(prefix.astype(jnp.int32), (0, f_pad - f),
                             constant_values=big), chunk)
    pad_f = lambda x: _rows(jnp.pad(x.astype(jnp.int32), (0, f_pad - f)),
                            chunk)
    col_p = _rows(jnp.pad(col.astype(jnp.int32), (0, e_pad - e)), chunk)
    wt_p = _rows(jnp.zeros((e_pad,), op.dtype) if wt is None
                 else jnp.pad(wt.astype(op.dtype), (0, e_pad - e)), chunk)

    lane_spec = pl.BlockSpec((tile_r, tile_c), lambda i: (i, 0))
    full = _full_spec(chunk)
    prop, upd, imp = pl.pallas_call(
        partial(_wd_kernel, op=op, n_pad=n_pad, f_pad=f_pad, e_pad=e_pad,
                f_real=f, e_real=e, has_wt=wt is not None, tile_r=tile_r,
                tile_c=tile_c, chunk=chunk),
        grid=(l_tiles,),
        in_specs=[full(f_pad), full(f_pad), full(f_pad), full(f_pad),
                  full(e_pad), full(e_pad), full(n_pad)],
        out_specs=[full(n_pad), full(n_pad), lane_spec],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad // chunk, chunk), op.dtype),
            jax.ShapeDtypeStruct((n_pad // chunk, chunk), jnp.int32),
            jax.ShapeDtypeStruct((l_tiles * tile_r, tile_c), jnp.int32),
        ],
        interpret=interpret,
    )(prefix_p, pad_f(exclusive), pad_f(start), pad_f(src_ids), col_p,
      wt_p, dist_p)
    prop, upd = prop.reshape(-1), upd.reshape(-1)
    return (prop[:n], upd[:n].astype(jnp.bool_),
            imp.reshape(-1)[:cap_work].astype(jnp.bool_))


# ---------------------------------------------------------------------------
# applying a proposal: the drop-in for the XLA scatter
# ---------------------------------------------------------------------------

def apply_proposal(dist, proposal, op: EdgeOp):
    """Fold a dense proposal into ``dist`` elementwise.  Exactly the XLA
    path's ``op.scatter`` outcome: the proposal already carries the
    identity for untouched destinations, and the monoid is associative,
    so one elementwise combine reproduces the scatter bit-for-bit."""
    return _fold2(op.combine, dist, proposal)


def apply_relax(dist, updated, src, dst, w, valid, *,
                op: EdgeOp = operators.shortest_path,
                interpret: bool | None = None, tile_r: int = TILE_R,
                tile_c: int = TILE_C, chunk: int = CHUNK):
    """Pallas drop-in for ``repro.core.strategies._apply_relax`` — same
    signature, same returns ``(dist, updated, improve)``, same values
    bit-for-bit; the gather+message+activation+scatter-combine runs in
    one :func:`relax_lanes` kernel instead of separate XLA HLOs."""
    src_c = jnp.clip(src, 0, dist.shape[0] - 1)
    dst_c = jnp.clip(dst, 0, dist.shape[0] - 1)
    prop, upd, imp = relax_lanes(dist, src_c, dst_c, w, valid, op=op,
                                 interpret=interpret, tile_r=tile_r,
                                 tile_c=tile_c, chunk=chunk)
    return apply_proposal(dist, prop, op), updated | upd, imp


def tile_kwargs(sched) -> dict:
    """The Pallas block-shape kwargs of a
    :class:`~repro.core.schedule.Schedule` — what the strategy/fused
    dispatch layers forward into :func:`relax_lanes` /
    :func:`wd_relax_lanes` / :func:`apply_relax`."""
    return dict(tile_r=sched.tile_r, tile_c=sched.tile_c,
                chunk=sched.chunk)
