"""Logical-axis sharding annotations and logical→mesh partitioning rules.

Model code never mentions mesh axes.  Every init function returns a parallel
pytree of *logical axis tuples* (``("embed_fsdp", "heads")`` …) and forward
code annotates activations with :func:`shard`, e.g. ``shard(x, "batch",
"seq", None)``.  This module owns the translation onto whatever mesh the
launcher built:

- ``data`` / ``pod``   — batch-parallel axes; gradients are synchronized
  across them by the compressed collectives in ``train_step``;
- ``model``            — tensor-parallel axis for heads / ff / experts /
  vocab;
- ``embed_fsdp``       — parameter dimension additionally sharded over
  ``data`` when ``cfg.fsdp`` (ZeRO-3 style), gathered on the fly inside the
  train step.

:func:`shard` is context-dependent: outside any context it is the identity
(pure single-device use); inside :func:`axis_rules` it applies a
``with_sharding_constraint`` built from the active rule table.  The train
step installs a *manual-data* rule table (batch/fsdp axes are manual inside
its shard_map, so constraints may only mention the auto ``model`` axis);
the serve path installs the full table.
"""
from __future__ import annotations

from contextlib import contextmanager
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# Logical axes that live on the tensor-parallel mesh axis.
MODEL_AXES = ("vocab", "heads", "kv_heads", "ff", "expert")
# Logical axes that are never sharded (scan/layer stacking, plain embed dim).
REPLICATED_AXES = ("layers", "embed", "seq")

_ACTIVE: list[tuple[Mesh, dict]] = []


def data_axes(mesh: Mesh) -> tuple | None:
    """Batch-parallel mesh axes, outermost first (``("pod", "data")`` …)."""
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return axes or None


def manual_axes(mesh: Mesh) -> tuple:
    """Mesh axes the train step runs manually (its shard_map axis_names)."""
    return data_axes(mesh) or ()


def activation_rules(mesh: Mesh, *, manual_data: bool = False, fsdp: bool = False) -> dict:
    """Logical→mesh rule table for activation constraints.

    With ``manual_data=True`` (inside the train step's shard_map) the
    batch/fsdp axes are dropped: they are manual there and constraints may
    only reference auto axes.
    """
    rules: dict = {}
    if "model" in mesh.axis_names:
        for name in MODEL_AXES:
            rules[name] = "model"
    if not manual_data:
        dp = data_axes(mesh)
        if dp:
            rules["batch"] = dp
        if fsdp and "data" in mesh.axis_names:
            rules["embed_fsdp"] = "data"
    return rules


@contextmanager
def axis_rules(mesh: Mesh, rules: dict):
    """Activate a rule table for :func:`shard` during tracing."""
    _ACTIVE.append((mesh, rules))
    try:
        yield
    finally:
        _ACTIVE.pop()


def _axis_group_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def shard(x: jax.Array, *logical: str | None) -> jax.Array:
    """Constrain ``x``'s sharding by logical axis names (None = replicated).

    Identity outside an :func:`axis_rules` context, and per-dimension axes
    are dropped whenever the dimension is not evenly divisible by the mapped
    mesh-axis group (tiny reduced configs on wide meshes).
    """
    if not _ACTIVE:
        return x
    mesh, rules = _ACTIVE[-1]
    spec = []
    nontrivial = False
    for dim, name in enumerate(logical):
        axes = rules.get(name) if name is not None else None
        if axes is not None and x.shape[dim] % _axis_group_size(mesh, axes) != 0:
            axes = None
        spec.append(axes)
        nontrivial = nontrivial or axes is not None
    if not nontrivial:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


# ---------------------------------------------------------------------------
# Parameter PartitionSpecs from logical-axis pytrees
# ---------------------------------------------------------------------------


def _leaf_pspec(axes: tuple, shape: tuple | None, mesh: Mesh, fsdp: bool) -> P:
    entries = []
    for dim, name in enumerate(axes):
        mapped = None
        if name in MODEL_AXES and "model" in mesh.axis_names:
            mapped = "model"
        elif name == "embed_fsdp" and fsdp and "data" in mesh.axis_names:
            mapped = "data"
        if mapped is not None and shape is not None and shape[dim] % mesh.shape[mapped] != 0:
            mapped = None
        entries.append(mapped)
    return P(*entries)


def param_pspecs(logical: Any, mesh: Mesh, fsdp: bool, params_like: Any = None) -> Any:
    """PartitionSpec pytree for a parameter tree described by ``logical``.

    ``params_like`` (arrays or ShapeDtypeStructs) enables divisibility
    pruning: any mapped axis that does not evenly divide its dimension is
    dropped rather than left to fail at ``device_put``.
    """
    is_axes = lambda t: isinstance(t, tuple)
    axes_leaves, treedef = jax.tree.flatten(logical, is_leaf=is_axes)
    shape_leaves = [x.shape for x in jax.tree.leaves(params_like)] \
        if params_like is not None else [None] * len(axes_leaves)
    specs = [_leaf_pspec(a, s, mesh, fsdp) for a, s in zip(axes_leaves, shape_leaves)]
    return jax.tree.unflatten(treedef, specs)


def strip_to_manual(spec_tree: Any, mesh: Mesh) -> Any:
    """Project a PartitionSpec tree onto the manual (data/pod) axes only.

    shard_map ``in_specs``/``out_specs`` may not mention auto axes; the auto
    sharding of those dimensions is carried by the arrays themselves.
    """
    keep = set(manual_axes(mesh))

    def one(spec: P) -> P:
        entries = []
        for e in spec:
            if isinstance(e, tuple):
                kept = tuple(a for a in e if a in keep)
                entries.append(kept if kept else None)
            else:
                entries.append(e if e in keep else None)
        return P(*entries)

    return jax.tree.map(one, spec_tree, is_leaf=lambda s: isinstance(s, P))
