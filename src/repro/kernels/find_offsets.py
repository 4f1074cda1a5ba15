"""Pallas kernel: WD offset search (the paper's ``find_offsets``).

Workload decomposition assigns work item *k* the (node, local-edge) found
by ranking *k* against the inclusive prefix-sum of frontier outdegrees —
``node_idx[k] = searchsorted(prefix, k, side='right')``.

TPU adaptation: dynamic per-lane gathers (classic binary search) don't
vectorize on the VPU, so the kernel computes ranks by *broadcast compare
and count*: ``rank(k) = Σ_i [prefix_i ≤ k]``, streamed over 128-wide
prefix chunks resident in VMEM.  Each grid step ranks an (8, 128) tile of
work items — exactly the VPU register shape — against the whole prefix.
O(F/128) vector ops per tile, no scatter/gather, MXU-free (VPU only).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_ROWS, TILE_COLS = 8, 128           # VPU vector registers
TILE = TILE_ROWS * TILE_COLS
PREFIX_CHUNK = 128


def _kernel(prefix_ref, out_ref, *, f_pad: int):
    pid = pl.program_id(0)
    base = pid * TILE
    # work-item ids for this tile, shaped to the VPU registers
    k = (base
         + jax.lax.broadcasted_iota(jnp.int32, (TILE_ROWS, TILE_COLS), 0)
         * TILE_COLS
         + jax.lax.broadcasted_iota(jnp.int32, (TILE_ROWS, TILE_COLS), 1))

    def body(c, rank):
        chunk = prefix_ref[c]       # row c of the [F/128, 128] layout
        # rank += #prefix entries ≤ k   (broadcast compare over the chunk)
        le = (chunk[None, None, :] <= k[:, :, None])
        return rank + jnp.sum(le.astype(jnp.int32), axis=-1)
    # an in-kernel loop: one chunk's temporaries, code size flat in F
    out_ref[...] = jax.lax.fori_loop(
        0, f_pad // PREFIX_CHUNK, body,
        jnp.zeros((TILE_ROWS, TILE_COLS), jnp.int32))


@partial(jax.jit, static_argnames=("cap_work", "interpret"))
def find_offsets(prefix: jax.Array, cap_work: int,
                 interpret: bool | None = None) -> jax.Array:
    """prefix [F] inclusive int32 -> node index per work item [cap_work]."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    f = prefix.shape[0]
    if f == 0:      # empty frontier: every work item ranks to slot 0,
        return jnp.zeros((cap_work,), jnp.int32)  # like searchsorted
    f_pad = -(-f // PREFIX_CHUNK) * PREFIX_CHUNK
    big = jnp.iinfo(jnp.int32).max
    prefix_p = jnp.pad(prefix, (0, f_pad - f),
                       constant_values=big).reshape(-1, PREFIX_CHUNK)
    n_tiles = -(-cap_work // TILE)
    out = pl.pallas_call(
        partial(_kernel, f_pad=f_pad),
        grid=(n_tiles,),
        # the whole prefix resident in VMEM, one 128-entry row per chunk
        in_specs=[pl.BlockSpec((f_pad // PREFIX_CHUNK, PREFIX_CHUNK),
                               lambda i: (0, 0))],
        out_specs=pl.BlockSpec((TILE_ROWS, TILE_COLS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tiles * TILE_ROWS, TILE_COLS),
                                       jnp.int32),
        interpret=interpret,
    )(prefix_p)
    return out.reshape(-1)[:cap_work]
