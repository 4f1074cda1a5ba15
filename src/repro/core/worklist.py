"""Data-driven worklist machinery.

GPU worklists are append-buffers fed by atomics.  JAX arrays are statically
shaped, so a worklist here is a fixed-capacity index array + a valid count,
and a "push" is a flag→scan→compact pipeline (the deterministic TPU analogue
of Merrill-style queue management the paper builds on).

Capacity *bucketing*: drivers round the live size up to the next power of two
and dispatch to a per-capacity jitted specialization.  This keeps wall-clock
work proportional to the live frontier (as on the GPU, where the launch
configuration tracks the worklist size) while staying shape-static inside
each call — and it bounds the number of compiled variants to O(log N).

Priority (distance) buckets: :mod:`repro.core.priority` extends the same
machinery from *capacity* buckets to *value* buckets — delta-stepping's
``⌊rank/Δ⌋`` partition of the frontier by tentative value.  The rank /
bucket-index / min-live-bucket helpers live here because they are
worklist bookkeeping, not relax semantics: a priority bucket is just a
worklist whose membership predicate reads the value array
(docs/scheduling.md).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.graph import INF

MIN_BUCKET = 256

#: bucket index of an empty slot — compares above every real bucket
#: (real indices are ≤ INF < int32 max), so ``min`` folds ignore it
NO_BUCKET = jnp.iinfo(jnp.int32).max


def bucket(n: int, minimum: int = MIN_BUCKET) -> int:
    """Round up to the next power of two (≥ minimum)."""
    n = max(int(n), minimum)
    return 1 << (n - 1).bit_length()


@partial(jax.jit, static_argnames=("cap",))
def compact_mask(mask: jax.Array, cap: int) -> jax.Array:
    """Boolean mask [N] -> index worklist [cap] (padded with -1).

    Worklists built this way are inherently deduplicated — the paper's
    "worklist condensing" happens by construction in the chunked path."""
    (idx,) = jnp.nonzero(mask, size=cap, fill_value=-1)
    return idx.astype(jnp.int32)


@jax.jit
def mask_count(mask: jax.Array) -> jax.Array:
    return jnp.sum(mask, dtype=jnp.int32)


#: row width of :func:`prefix_sum`'s two-level scan
_SCAN_ROW = 1024


def prefix_sum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum of a 1-D integer array: a scan within
    1024-wide rows plus a scan of the row totals (recursively).  Integer
    sums are exact in any order, so this equals ``jnp.cumsum(x)``; the
    TPU compiler takes about a second for it at every length, where it
    takes tens of seconds for ``jnp.cumsum`` at some lengths (2**20:
    34 s, 2**27: 29 s, jax 0.9.0 / libtpu 0.0.34)."""
    n = x.shape[0]
    if n <= _SCAN_ROW:
        return jnp.cumsum(x)
    rows = jnp.pad(x, (0, (-n) % _SCAN_ROW)).reshape(-1, _SCAN_ROW)
    inner = jnp.cumsum(rows, axis=1)
    carry = prefix_sum(inner[:, -1]) - inner[:, -1]
    return (inner + carry[:, None]).reshape(-1)[:n]


@partial(jax.jit, static_argnames=("cap",))
def run_fill(starts: jax.Array, lengths: jax.Array, total_hint: jax.Array,
             cap: int) -> tuple[jax.Array, jax.Array]:
    """Vectorized variable-length run fill (the work-chunked push).

    Given per-source ``starts`` (base offset of each source's run, e.g.
    ``row_ptr[node]``) and run ``lengths``, produce the concatenation
    ``[starts[0]..starts[0]+len0) ++ [starts[1]..) ++ ...`` padded to ``cap``.

    This reserves ONE output range per source — the array equivalent of the
    paper's single-atomic-per-node work chunking (§IV-D).  Returns
    ``(values [cap], valid mask [cap])``.
    """
    lengths = lengths.astype(jnp.int32)
    prefix = prefix_sum(lengths)                      # inclusive
    exclusive = prefix - lengths
    k = jnp.arange(cap, dtype=jnp.int32)
    # which run does output slot k belong to?  (merge-path / searchsorted)
    run = jnp.searchsorted(prefix, k, side="right").astype(jnp.int32)
    run_c = jnp.clip(run, 0, lengths.shape[0] - 1)
    local = k - exclusive[run_c]
    vals = starts[run_c] + local
    valid = k < jnp.minimum(total_hint, prefix[-1] if prefix.size else 0)
    return jnp.where(valid, vals, -1).astype(jnp.int32), valid


# ---------------------------------------------------------------------------
# Priority (value) buckets — delta-stepping support (repro.core.priority)
# ---------------------------------------------------------------------------

def bucket_rank(vals: jax.Array, *, descending: bool = False) -> jax.Array:
    """Map tentative values to a non-negative *rank* where smaller rank means
    "settle earlier".  ``min`` monoids (shortest_path, min_label) settle small
    values first; ``max`` monoids (widest_path) settle large values first, so
    their rank is the reflection ``INF - v``.  Values are clipped into
    ``[0, INF]`` so identity sentinels rank last, never negative."""
    v = jnp.clip(vals, 0, INF)
    return (INF - v) if descending else v


def bucket_index(vals: jax.Array, delta, *, descending: bool = False) -> jax.Array:
    """Delta-stepping bucket of each value: ``⌊rank / Δ⌋``.  ``delta`` is a
    traced int32 scalar (dynamic, so retuning Δ never recompiles)."""
    return (bucket_rank(vals, descending=descending) // delta).astype(jnp.int32)


def min_live_bucket(mask: jax.Array, bkt: jax.Array) -> jax.Array:
    """Smallest bucket index with a live frontier node (NO_BUCKET if empty)."""
    return jnp.min(jnp.where(mask, bkt, NO_BUCKET))
