"""Priority-ordered (delta-stepping) fixed points.

Everything else in the engine is bulk-synchronous label-correcting: every
iteration relaxes the *whole* frontier, however spread out its tentative
values are.  On low-diameter skewed graphs that is the right call — the
paper's strategies exist to balance one huge frontier.  On high-diameter
inputs (road networks) BSP burns hundreds of near-empty iterations, and
the open ROADMAP line ("asynchronous and priority-ordered fixed points")
is exactly the delta-stepping answer of Meyer & Sanders, the ordering
the Gunrock/Osama programming-model line exposes as a work-ordering knob
(arXiv:2301.04792, arXiv:2212.08964).

Delta-stepping in one paragraph: partition tentative values into buckets
of width Δ (:func:`repro.core.worklist.bucket_index` — priority buckets
are worklist bookkeeping, not relax semantics).  Settle buckets in
order; within the current bucket, relax **light** edges (w ≤ Δ) to a
local fixed point — a candidate over a light edge can land in the same
bucket, so light closure may take several rounds — then relax the
settled nodes' **heavy** edges (w > Δ) exactly once: a heavy candidate
provably lands in a later bucket (for operators declaring
:attr:`repro.core.operators.EdgeOp.weight_additive`), so deferring it is
free and re-relaxation is avoided.  Δ interpolates between Dijkstra
(Δ=1: strict priority order, minimal work, maximal rounds) and
Bellman-Ford BSP (Δ=∞: one bucket, maximal parallelism).

Mapping onto this codebase:

* **buckets** extend the :mod:`repro.core.worklist` machinery — the
  frontier mask is intersected with a membership predicate over the
  value array (``bucket_index(dist, Δ) == b``) instead of being consumed
  whole.  Δ is a *dynamic* int32 scalar, so retuning it never
  recompiles;
* **light/heavy splitting** is a host-side edge partition into two CSR
  subgraphs sharing the parent graph's node numbering (edge *order* is
  preserved, so when every edge is light the light graph aliases the
  original arrays and the inner closure is bit-identical to BSP);
* **phases** reuse the dense-mask kernels of :mod:`repro.core.fused`
  verbatim (BS / WD / HP / NS / AD — any strategy declaring the
  ``PRIORITY_SCHEDULE`` capability), so every phase inherits the
  ``backend="pallas"`` lowering and the chunk-boundary semantics tests
  already pin down.  EP is excluded: an edge worklist has no per-node
  tentative value to bucket by;
* **epochs** run inside ``lax.while_loop``: one epoch = light closure of
  the minimum live bucket + one deferred heavy pass.  Stepped mode jits
  one epoch per dispatch (host loop collects per-epoch ``IterStats``
  with the settled bucket index); fused mode wraps epochs in an outer
  ``while_loop`` — one dispatch per traversal, same carry discipline as
  :func:`repro.core.fused._fixed_point`.

Iteration-count contract (docs/scheduling.md): ``iterations`` counts
**bucket epochs** — that is what ``max_iterations`` caps, identically in
stepped and fused mode.  The finer-grained work unit, comparable to a
BSP iteration, is a **relax round** (one light-closure pass, or a heavy
pass that actually had edges); the total rides in ``relax_rounds``.  In
the degenerate case Δ ≥ every finite rank (one bucket, no heavy edges)
the light closure *is* the BSP loop: equal rounds, equal edge totals,
bit-identical ``dist``.

Convergence: settling min-rank buckets first requires candidates never
to out-rank their source (``rank(message(v, w)) ≥ rank(v)``), which
holds for every monotone built-in (min: ``v+w ≥ v``, ``v ≥ v``; max:
``min(v,w) ≤ v`` so the reflected rank grows).  ``add`` is not
idempotent — reordering its relaxations changes the answer — so the
engine rejects ``schedule="delta"`` for non-idempotent operators.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import operators, worklist
from repro.core.fused import (
    DISPATCH_COUNTS, TRACE_COUNTS, _LIMB, _ad_step, _bs_step, _count_key,
    _hp_step, _limb_add, _ns_step, _plan, _wd_step)
from repro.core.graph import CSRGraph
from repro.core.operators import EdgeOp
from repro.core.schedule import DEFAULT_SCHEDULE, Schedule
from repro.core.strategies import PRIORITY_SCHEDULE

#: Δ = multiplier × mean edge weight when the caller does not pass one.
#: Small multiples of the mean keep buckets populated enough to relax in
#: parallel while still collapsing the iteration count on high-diameter
#: graphs; see docs/scheduling.md for tuning guidance.  The per-run knob
#: is ``Schedule.delta_multiplier``; this is its default.
DELTA_WEIGHT_MULTIPLIER = 4


def auto_delta(graph: CSRGraph,
               multiplier: int = DELTA_WEIGHT_MULTIPLIER) -> int:
    """Default bucket width: ``multiplier × mean(w)``, clamped to Δ ≥ 1.

    Unweighted graphs have unit weights, so the default is the bare
    multiplier (Δ=4: every edge light, buckets 4 BFS levels wide).  The
    clamp matters on zero-/uniform-weight inputs: without it a
    zero-mean weight array would yield Δ=0, degenerating delta-stepping
    into one bucket per distinct distance (and ``bucket_index`` would
    divide by zero)."""
    multiplier = max(1, int(multiplier))
    if not graph.weighted or graph.num_edges == 0:
        return multiplier
    mean = float(np.asarray(graph.wt).mean())
    return max(1, int(round(multiplier * mean)))


def _edge_subgraph(g: CSRGraph, keep: np.ndarray) -> CSRGraph:
    """Host-side CSR filter keeping edge order (stable within each row)."""
    rp = np.asarray(g.row_ptr, np.int64)
    kept_before = np.concatenate([[0], np.cumsum(keep, dtype=np.int64)])
    row_ptr = kept_before[rp].astype(np.int32)
    col = np.asarray(g.col)[keep]
    wt = np.asarray(g.wt)[keep] if g.weighted else None
    deg = row_ptr[1:] - row_ptr[:-1]
    return CSRGraph(
        row_ptr=jnp.asarray(row_ptr),
        col=jnp.asarray(col, jnp.int32),
        wt=None if wt is None else jnp.asarray(wt, jnp.int32),
        num_nodes=g.num_nodes,
        num_edges=int(col.shape[0]),
        max_degree=int(deg.max()) if deg.size else 0,
    )


@dataclasses.dataclass
class DeltaPlan:
    """One strategy lowered to delta-stepping phase kernels."""
    kernel: str                     # BS | WD | HP | NS | AD
    light: CSRGraph                 # w ≤ Δ edges (aliases the full graph
                                    # when nothing is heavy)
    heavy_graph: Optional[CSRGraph]  # w > Δ edges; None when none exist
    aux: Optional[jax.Array]        # NS child→parent map
    static: dict                    # threshold kwargs for the phase kernels
    delta: int

    @property
    def heavy(self) -> bool:
        return self.heavy_graph is not None

    def device_bytes(self) -> int:
        total = self.light.device_bytes()
        if self.heavy_graph is not None:
            total += self.heavy_graph.device_bytes()
        if self.aux is not None:
            total += self.aux.size * self.aux.dtype.itemsize
        return total


def plan_delta(strategy, state, graph: CSRGraph, *,
               op: EdgeOp = operators.shortest_path,
               delta: Optional[int] = None) -> DeltaPlan:
    """Lower a set-up strategy to its delta-stepping plan.

    Reuses the fused lowering (:func:`repro.core.fused._plan`) for the
    kernel name, phase graph (the split graph for NS) and schedule
    static, then splits that graph's edges at Δ.  Δ resolution:
    explicit ``delta`` argument > ``Schedule.delta`` > :func:`auto_delta`
    with ``Schedule.delta_multiplier``.  Operators without
    :attr:`EdgeOp.weight_additive` get an all-light split — correct for
    any monotone monoid, just with nothing to defer.  The measured AD
    selector (cost-model v2) is fused-BSP only; delta phases keep the
    fixed decision tree."""
    op = operators.resolve(op)
    if PRIORITY_SCHEDULE not in type(strategy).capabilities:
        raise ValueError(
            f"strategy {strategy.name!r} does not declare the "
            f"{PRIORITY_SCHEDULE!r} capability (docs/scheduling.md)")
    if not op.idempotent:
        raise ValueError(
            f"schedule='delta' reorders relaxations, which changes the "
            f"fixed point of non-idempotent operators; op {op.name!r} "
            f"has combine={op.combine!r} (docs/scheduling.md)")
    fplan = _plan(strategy, state, graph)
    g = fplan.graph
    static = dict(fplan.static)
    aux = fplan.aux
    if static.pop("measured", None):
        # measured AD rides its coefficients in the aux slot — the delta
        # phases use the fixed tree, so drop both
        aux = None
    sched = static.get("sched", DEFAULT_SCHEDULE)
    if delta is None:
        delta = (sched.delta if sched.delta is not None
                 else auto_delta(graph, sched.delta_multiplier))
    delta = int(delta)
    if delta < 1:
        raise ValueError(f"delta must be >= 1, got {delta}")
    if op.weight_additive and g.weighted and g.num_edges:
        light = np.asarray(g.wt) <= delta
    else:
        light = np.ones(int(g.num_edges), bool)
    if light.all():
        gl, gh = g, None               # alias: bit-parity with BSP for free
    else:
        gl, gh = _edge_subgraph(g, light), _edge_subgraph(g, ~light)
    return DeltaPlan(fplan.kernel, gl, gh, aux, static, delta)


# ---------------------------------------------------------------------------
# phases and epochs (traced helpers shared by the stepped/fused/batch jits)
# ---------------------------------------------------------------------------

def _phase(g: CSRGraph, aux, dist, cur, *, kernel: str, op: EdgeOp,
           backend: str, sched: Schedule = DEFAULT_SCHEDULE):
    """One phase = one dense-mask relax of ``cur`` over ``g``'s edges.

    Exactly the fused step kernels, pointed at the light or heavy
    subgraph.  Returns ``(dist, updated, edges)`` — ``edges`` counts
    ``g``-degrees of ``cur``, so light rounds bill light edges only."""
    if g.num_edges == 0:
        # static guard: HP's MDT tiles index g.col, which is empty here
        return dist, jnp.zeros_like(cur), jnp.int32(0)
    # the fused steps' fourth result, their relax tally, is not kept here
    if kernel == "BS":
        return _bs_step(g, dist, cur, op=op, backend=backend,
                        sched=sched)[:3]
    if kernel == "WD":
        return _wd_step(g, dist, cur, op=op, backend=backend,
                        sched=sched)[:3]
    if kernel == "HP":
        return _hp_step(g, dist, cur, sched=sched, op=op,
                        backend=backend)[:3]
    if kernel == "NS":
        return _ns_step(g, aux, dist, cur, op=op, backend=backend,
                        sched=sched)[:3]
    if kernel == "AD":
        return _ad_step(g, dist, cur, sched=sched, op=op,
                        backend=backend)[:3]
    raise ValueError(f"kernel {kernel!r} has no delta-stepping phase")


def _epoch(gl, gh, aux, dist, mask, delta, *, kernel: str, heavy: bool,
           op: EdgeOp, backend: str, **static):
    """Settle the minimum live bucket: light closure + one heavy pass.

    Returns ``(dist, mask, b, rounds, e_hi, e_lo)`` where ``b`` is the
    bucket index settled (``worklist.NO_BUCKET`` on an empty frontier),
    ``rounds`` the relax rounds spent (light passes, plus the heavy pass
    when it actually had edges) and the limbs this epoch's edge total."""
    descending = op.combine == "max"

    def in_bucket(dist, mask, b):
        return mask & (worklist.bucket_index(
            dist, delta, descending=descending) == b)

    b = worklist.min_live_bucket(
        mask, worklist.bucket_index(dist, delta, descending=descending))

    def cond(c):
        dist, mask = c[0], c[1]
        return jnp.any(in_bucket(dist, mask, b))

    def body(c):
        dist, mask, settled, rounds, e_hi, e_lo = c
        cur = in_bucket(dist, mask, b)
        settled = settled | cur
        mask = mask & ~cur
        dist, upd, e = _phase(gl, aux, dist, cur, kernel=kernel, op=op,
                              backend=backend, **static)
        # light candidates may land back in bucket b → another round
        mask = mask | upd
        e_hi, e_lo = _limb_add(e_hi, e_lo, e)
        return dist, mask, settled, rounds + 1, e_hi, e_lo

    init = (dist, mask, jnp.zeros_like(mask), jnp.int32(0), jnp.int32(0),
            jnp.int32(0))
    dist, mask, settled, rounds, e_hi, e_lo = lax.while_loop(cond, body, init)

    if heavy:
        # every settled node fires its heavy edges exactly once; the
        # candidates land in buckets > b (weight_additive contract), so
        # nothing here can re-open the bucket being settled
        dist, upd, e = _phase(gh, aux, dist, settled, kernel=kernel, op=op,
                              backend=backend, **static)
        mask = mask | upd
        rounds = rounds + (e > 0).astype(jnp.int32)
        e_hi, e_lo = _limb_add(e_hi, e_lo, e)
    return dist, mask, b, rounds, e_hi, e_lo


_STATIC_NAMES = ("kernel", "heavy", "op", "backend", "sched")


@partial(jax.jit, static_argnames=_STATIC_NAMES)
def _delta_epoch(gl, gh, aux, dist, mask, delta, *, kernel: str, heavy: bool,
                 op: EdgeOp, backend: str = "xla",
                 sched: Schedule = DEFAULT_SCHEDULE):
    TRACE_COUNTS[_count_key(f"delta-epoch:{kernel}", backend)] += 1
    return _epoch(gl, gh, aux, dist, mask, delta, kernel=kernel, heavy=heavy,
                  op=op, backend=backend, sched=sched)


@partial(jax.jit, static_argnames=_STATIC_NAMES + ("max_iterations",))
def _delta_fixed_point(gl, gh, aux, dist, mask, delta, *, kernel: str,
                       heavy: bool, max_iterations: int, op: EdgeOp,
                       backend: str = "xla",
                       sched: Schedule = DEFAULT_SCHEDULE):
    """Whole delta-stepping traversal, one dispatch (fused mode).

    Carry ``(it, dist, mask, e_hi, e_lo, rounds)``: ``it`` counts bucket
    epochs (the unit ``max_iterations`` caps), ``rounds`` relax rounds."""
    TRACE_COUNTS[_count_key(f"delta:{kernel}", backend)] += 1

    def cond(c):
        it, mask = c[0], c[2]
        return jnp.any(mask) & (it < max_iterations)

    def body(c):
        it, dist, mask, e_hi, e_lo, rounds = c
        dist, mask, _b, r, eh, el = _epoch(
            gl, gh, aux, dist, mask, delta, kernel=kernel, heavy=heavy,
            op=op, backend=backend, sched=sched)
        e_hi, e_lo = _limb_add(e_hi + eh, e_lo, el)
        return it + 1, dist, mask, e_hi, e_lo, rounds + r

    carry = (jnp.int32(0), dist, mask, jnp.int32(0), jnp.int32(0),
             jnp.int32(0))
    it, dist, mask, e_hi, e_lo, rounds = lax.while_loop(cond, body, carry)
    return dist, it, e_hi, e_lo, rounds


@partial(jax.jit, static_argnames=("heavy", "max_iterations", "op",
                                   "backend", "sched"))
def _delta_batch_fixed_point(gl, gh, dist_b, mask_b, delta, *, heavy: bool,
                             max_iterations: int, op: EdgeOp,
                             backend: str = "xla",
                             sched: Schedule = DEFAULT_SCHEDULE):
    """K delta-stepping traversals in one dispatch (WD phases, vmapped).

    Each row runs its own bucket sequence — rows settle *different*
    buckets in the same joint step, which is why this vmaps the whole
    per-row loop rather than sharing one bucket schedule."""
    TRACE_COUNTS[_count_key("delta:batch", backend)] += 1
    aux = jnp.zeros((1,), jnp.int32)

    def one(dist, mask):
        def cond(c):
            it, mask = c[0], c[2]
            return jnp.any(mask) & (it < max_iterations)

        def body(c):
            it, dist, mask, e_hi, e_lo, rounds = c
            dist, mask, _b, r, eh, el = _epoch(
                gl, gh, aux, dist, mask, delta, kernel="WD", heavy=heavy,
                op=op, backend=backend, sched=sched)
            e_hi, e_lo = _limb_add(e_hi + eh, e_lo, el)
            return it + 1, dist, mask, e_hi, e_lo, rounds + r

        carry = (jnp.int32(0), dist, mask, jnp.int32(0), jnp.int32(0),
                 jnp.int32(0))
        it, dist, mask, e_hi, e_lo, rounds = lax.while_loop(cond, body, carry)
        return dist, it, e_hi, e_lo, rounds

    return jax.vmap(one)(dist_b, mask_b)


# ---------------------------------------------------------------------------
# host-side drivers
# ---------------------------------------------------------------------------

def step_epoch(plan: DeltaPlan, dist, mask, *,
               op: EdgeOp = operators.shortest_path, backend: str = "xla"):
    """One bucket epoch (stepped mode).  Returns ``(dist, mask, bucket,
    rounds, edges)`` with the arrays on device and the counters synced —
    the delta analogue of one ``strategy.iterate`` call."""
    op = operators.resolve(op)
    aux = (jnp.zeros((1,), jnp.int32) if plan.aux is None else plan.aux)
    gh = plan.heavy_graph if plan.heavy else plan.light  # placeholder arg
    dist, mask, b, rounds, e_hi, e_lo = _delta_epoch(
        plan.light, gh, aux, dist, mask, jnp.int32(plan.delta),
        kernel=plan.kernel, heavy=plan.heavy, op=op, backend=backend,
        **plan.static)
    return dist, mask, int(b), int(rounds), int(e_hi) * _LIMB + int(e_lo)


def run_fixed_point(plan: DeltaPlan, dist0, mask0, *,
                    op: EdgeOp = operators.shortest_path,
                    max_iterations: int = 100000, backend: str = "xla"):
    """Whole delta-stepping traversal as a single fused dispatch.

    Returns ``(dist, epochs, relax_rounds, edges_relaxed)`` with ``dist``
    still on device.  ``max_iterations`` caps *epochs* — the same knob
    semantics as BSP iterations (docs/scheduling.md)."""
    op = operators.resolve(op)
    DISPATCH_COUNTS[_count_key(f"delta:{plan.kernel}", backend)] += 1
    aux = (jnp.zeros((1,), jnp.int32) if plan.aux is None else plan.aux)
    gh = plan.heavy_graph if plan.heavy else plan.light
    dist, it, e_hi, e_lo, rounds = _delta_fixed_point(
        plan.light, gh, aux, dist0, mask0, jnp.int32(plan.delta),
        kernel=plan.kernel, heavy=plan.heavy, max_iterations=max_iterations,
        op=op, backend=backend, **plan.static)
    jax.block_until_ready(dist)
    return dist, int(it), int(rounds), int(e_hi) * _LIMB + int(e_lo)


def run_batch_fixed_point(plan: DeltaPlan, dist_b, mask_b, *,
                          op: EdgeOp = operators.shortest_path,
                          max_iterations: int = 100000,
                          backend: str = "xla"):
    """K queries to their delta fixed points in one dispatch.

    Requires a WD plan (the batched phase kernel, matching the BSP batch
    driver).  Returns ``(dist_b, epochs, relax_rounds, edges)``; epochs /
    rounds report the slowest row (the batch completes when every row
    does, mirroring ``fused.run_batch_fixed_point``)."""
    if plan.kernel != "WD":
        raise ValueError(
            f"batched delta-stepping runs WD phases; got {plan.kernel!r}")
    op = operators.resolve(op)
    DISPATCH_COUNTS[_count_key("delta:batch", backend)] += 1
    gh = plan.heavy_graph if plan.heavy else plan.light
    dist_b, its, e_hi, e_lo, rounds = _delta_batch_fixed_point(
        plan.light, gh, dist_b, mask_b, jnp.int32(plan.delta),
        heavy=plan.heavy, max_iterations=max_iterations, op=op,
        backend=backend,
        sched=plan.static.get("sched", DEFAULT_SCHEDULE))
    jax.block_until_ready(dist_b)
    edges = sum(int(h) * _LIMB + int(l)
                for h, l in zip(np.asarray(e_hi), np.asarray(e_lo)))
    epochs = int(np.asarray(its).max()) if its.shape[0] else 0
    max_rounds = int(np.asarray(rounds).max()) if rounds.shape[0] else 0
    return dist_b, epochs, max_rounds, edges
