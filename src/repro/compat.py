"""The one ``shard_map`` entry point of the repo, for jax 0.9.0.

The repo is tested on the stack pinned in ``requirements-dev.txt``
(jax 0.9.0 / jaxlib 0.9.0, libtpu 0.0.34 on the chip).  Every
``shard_map`` call site (the sharded graph engine in
:mod:`repro.core.shard`, the expert-parallel MoE dispatch in
:mod:`repro.moe.sharded`, tests) goes through :func:`shard_map` here, so
the replication-check default lives in exactly one place.
"""

from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with the replication check (``check_vma``) off
    by default: the sharded engine's per-chunk monoid combines
    (``pmin``/``pmax``/delta-``psum``) produce values that are replicated
    *by construction* — identical collectives on identical operands —
    which the checker cannot always prove for non-``psum`` collectives.
    Replicated operands and results of any rank take ``P()``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)
