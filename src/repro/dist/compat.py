"""Collective helpers for the fully-manual shard_map regions of the sync.

The gradient codec runs inside a ``jax.shard_map`` that is manual over every
mesh axis, while the model's forward/backward stays in the GSPMD-auto jit
around it.  That split dates from an older jaxlib whose SPMD partitioner
aborted on ``all_gather`` / ``all_to_all`` / ``axis_index`` (and on any
``lax.scan``) inside manual subgroups when auto axes were present.  Whether
jax 0.9.0 lowers a partial-auto sync (model axes auto, data axes manual)
around the scanned model is untested; until it is, every sync stays fully
manual and these helpers assume it.
"""
from __future__ import annotations

from collections.abc import Sequence

import jax
import jax.numpy as jnp


def axis_size(axis_name) -> int:
    """Static size of a (possibly tuple of) named mesh axis inside shard_map.

    ``lax.psum`` of a Python literal is constant-folded to the axis size, so
    this is trace-time static and free.
    """
    return jax.lax.psum(1, axis_name)


def flat_axis_index(axis_name) -> jax.Array:
    """Row-major linear index over one or more manual mesh axes."""
    names: Sequence = axis_name if isinstance(axis_name, tuple) else (axis_name,)
    idx = jnp.int32(0)
    for name in names:
        idx = idx * jax.lax.psum(1, name) + jax.lax.axis_index(name)
    return idx


def all_gather_stacked(x: jax.Array, axis_name) -> jax.Array:
    """All-gather ``x`` over ``axis_name`` into a stacked (n, *x.shape) array."""
    if axis_size(axis_name) == 1:
        return x[None]
    return jax.lax.all_gather(x, axis_name, axis=0, tiled=False)


def all_to_all_rows(parts: jax.Array, axis_name) -> jax.Array:
    """All-to-all over the leading axis: row p of ``parts`` goes to peer p.

    ``parts`` has leading dim n = size of ``axis_name``; the result's row p
    holds the row peer p addressed to this shard.
    """
    if axis_size(axis_name) == 1:
        return parts
    return jax.lax.all_to_all(parts, axis_name, split_axis=0, concat_axis=0, tiled=False)
