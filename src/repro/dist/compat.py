"""The repo's one spelling of ``jax.shard_map``.

Every repro call site routes through :func:`shard_map`, whose only
difference from ``jax.shard_map`` is that the varying-manual-axes check
is off unless asked for.
"""
from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` with ``check_vma`` defaulting to False."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
