"""Mesh construction: every mesh in the repo is built by :func:`make_mesh`.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — smoke tests must keep seeing 1 CPU device.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto``.

    jax >= 0.7 defaults to Explicit axes, under which the sealed exchange's
    ``shard_map`` output no longer feeds the batched open and
    ``with_sharding_constraint`` is refused; the repo's sharding code is
    written for Auto axes.  ``devices`` (e.g. a described topology's) are
    passed through.
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """The target deployment mesh.

    Single pod: 16 x 16 = 256 chips (TPU v5e pod), axes ("data", "model").
    Multi-pod:  2 x 16 x 16 = 512 chips, axes ("pod", "data", "model") —
    the "pod" axis carries pure data parallelism with hierarchical gradient
    reduction (reduce-scatter intra-pod, all-reduce across the DCN/pod axis).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_smoke_mesh(n_devices: int = 0) -> jax.sharding.Mesh:
    """A small mesh over whatever devices exist (tests / examples)."""
    n = n_devices or len(jax.devices())
    model = 2 if n % 2 == 0 else 1
    return make_mesh((n // model, model), ("data", "model"))
