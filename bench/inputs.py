"""Weights and batches made from ``--seed``, for the program and the reference alike.

Both sides get the same numbers from these functions: each leaf in the
dtype it is served in, made in one jitted call (for the program, placed
with the step's shardings); the reference upcasts them to float32 as it
computes.  Nothing here comes from the program but the tree of leaf shapes
and dtypes and the per-leaf initialiser kinds named in the configuration
file (``init``), keyed by the leaf's last path component.

The token stream is a copy of the program's synthetic LM stream: a
Zipf-like marginal (``u ** 3`` of a uniform draw, scaled to the
vocabulary), labels shifted by one, every image-patch position and each
row's last position unlabelled, and for M-RoPE models (t, h, w) position
streams with the patches on a 16 x 16 grid at t = 0 and the text
continuing from the grid's far corner.  Every row of every step differs:
step ``i`` folds ``i`` into the seed's batch key.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

PATCH_GRID = 16


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits (seeds may exceed 2**31)."""
    seed = int(seed)
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    k = jax.random.key(0)
    k = jax.random.fold_in(k, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32(seed >> 32))


def leaf_paths(tree) -> list[str]:
    """'blocks/mixer/wq'-style names of a pytree's leaves, in leaf order."""
    out = []
    for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]:
        parts = []
        for p in path:
            parts.append(str(getattr(p, "key", getattr(p, "name", getattr(p, "idx", p)))))
        out.append("/".join(parts))
    return out


def _init_leaf(kind: str, key, shape, dtype) -> jax.Array:
    if kind == "fan_in":
        x = jax.random.normal(key, shape, jnp.float32) / np.sqrt(shape[-2])
    elif kind == "fan_in_last":
        x = jax.random.normal(key, shape, jnp.float32) / np.sqrt(shape[-1])
    elif kind == "zeros":
        x = jnp.zeros(shape, jnp.float32)
    elif kind == "ones":
        x = jnp.ones(shape, jnp.float32)
    elif kind == "a_log":
        x = jnp.broadcast_to(jnp.log(jnp.linspace(1.0, 16.0, shape[-1])), shape)
    elif kind == "dt_bias":
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (np.log(0.1) - np.log(1e-3)) + np.log(1e-3))
        x = dt + jnp.log(-jnp.expm1(-dt))
    else:
        raise ValueError(f"unknown initialiser kind {kind!r}")
    return x.astype(dtype)


def param_maker(shapes, init: dict):
    """``make(seed_words) -> params`` for the tree of ShapeDtypeStructs ``shapes``.

    ``init`` maps a leaf's last path component to its initialiser kind.
    Each leaf's key folds in the CRC-32 of its path, so a leaf's values do
    not depend on which other leaves the tree holds.
    """
    paths = leaf_paths(shapes)
    leaves, treedef = jax.tree.flatten(shapes)
    kinds = []
    for p in paths:
        name = p.rsplit("/", 1)[-1]
        if name not in init:
            raise KeyError(f"no initialiser kind for leaf {p!r}; add {name!r} to the "
                           f"configuration's 'init'")
        kinds.append(init[name])

    def make(words):
        k = jax.random.fold_in(jax.random.wrap_key_data(words), 1)
        out = [_init_leaf(kind, jax.random.fold_in(k, zlib.crc32(p.encode())),
                          x.shape, x.dtype)
               for p, kind, x in zip(paths, kinds, leaves)]
        return jax.tree.unflatten(treedef, out)

    return make


def key_words(seed: int) -> jax.Array:
    return jax.random.key_data(seed_key(seed))


def make_params(shapes, init: dict, seed: int, shardings=None):
    """The seed's parameters, in one jitted call (placed by ``shardings``)."""
    fn = jax.jit(param_maker(shapes, init), out_shardings=shardings)
    return fn(key_words(seed))


def batch_maker(rows: int, seq: int, vocab: int, patches: int = 0, vision_dim: int = 0,
                mrope: bool = False):
    """``make(seed_words, step) -> dict`` of one step's global batch.

    ``rows`` = data peers x rows per peer, peer-major.  Keys: tokens,
    labels (B, S) int32; positions (3, B, S) int32 when ``mrope``; patches
    (B, P, vision_dim) float32 when ``patches``.
    """

    def make(words, step):
        k = jax.random.fold_in(jax.random.fold_in(jax.random.wrap_key_data(words), 2), step)
        k_tok, k_extra = jax.random.split(k)
        u = jax.random.uniform(k_tok, (rows, seq), minval=1e-6, maxval=1.0)
        tokens = jnp.clip((jnp.power(u, 3.0) * vocab).astype(jnp.int32), 0, vocab - 1)
        labels = jnp.roll(tokens, -1, axis=1).at[:, -1].set(-1)
        out = {"tokens": tokens, "labels": labels}
        if patches:
            out["patches"] = jax.random.normal(k_extra, (rows, patches, vision_dim), jnp.float32)
            out["labels"] = labels.at[:, :patches].set(-1)
        if mrope:
            out["positions"] = mrope_positions(rows, seq, patches)
        return out

    return make


def mrope_positions(rows: int, seq: int, patches: int) -> jax.Array:
    """(3, B, S): patches at (0, h, w) on a 16-wide grid, text from the corner on."""
    idx = np.arange(patches)
    hh, ww = idx // PATCH_GRID, idx % PATCH_GRID
    start = int(max(hh.max(), ww.max()) + 1) if patches else 0
    text = start + np.arange(seq - patches)
    pos = np.stack([np.concatenate([np.zeros(patches, np.int64), text]),
                    np.concatenate([hh, text]),
                    np.concatenate([ww, text])]).astype(np.int32)
    return jnp.broadcast_to(jnp.asarray(pos)[:, None, :], (3, rows, seq))
