"""Two-stage quantizer primitives (paper §III).

The quantizer is `Q_λs[T_α(g)]`: truncation to [-α, α] (Eq. 3) followed by
stochastic quantization onto a codebook L = {l_0 < ... < l_s} (Eq. 4), where
the codebook is induced by a quantization-density function λ_s (uniform λ
recovers QSGD).  ``s = 2^b - 1`` intervals, codes in [0, s].

This module is the pure-jnp reference implementation; the Pallas kernels in
``repro.kernels`` implement the same contract for the TPU hot path and are
tested against these functions.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

_EPS = 1e-12


def num_levels(bits: int) -> int:
    """s = 2^b - 1 intervals -> s+1 codebook points."""
    return 2**bits - 1


def truncate(g: jax.Array, alpha: jax.Array) -> jax.Array:
    """T_alpha[g] (Eq. 3): clamp magnitudes to alpha, keeping sign."""
    return jnp.clip(g, -alpha, alpha)


def uniform_levels(alpha: jax.Array, bits: int) -> jax.Array:
    """Evenly spaced codebook over [-alpha, alpha] (QSGD / TQSGD)."""
    s = num_levels(bits)
    return jnp.linspace(-alpha, alpha, s + 1)


def levels_from_density(
    edges: jax.Array,
    lam: jax.Array,
    bits: int,
) -> jax.Array:
    """Build a codebook from a piecewise-constant density λ on |g| bins.

    ``edges`` are |g| bin edges over [0, alpha]; ``lam`` >= 0 is the (relative)
    quantization density per bin.  The codebook places the s interval
    boundaries at equal increments of the cumulative density, mirrored to the
    symmetric range [-alpha, alpha] (Eq. 18: λ ∝ p^(1/3), normalised so that
    ∫ λ = s).  Returns (s+1,) strictly increasing levels with l_0 = -alpha,
    l_s = +alpha.
    """
    s = num_levels(bits)
    alpha = edges[-1]
    # Mirror to the full range: [-alpha, alpha].
    full_edges = jnp.concatenate([-edges[::-1], edges[1:]])
    full_lam = jnp.concatenate([lam[::-1], lam])
    widths = jnp.diff(full_edges)
    cum = jnp.concatenate([jnp.zeros((1,), lam.dtype), jnp.cumsum(full_lam * widths)])
    total = jnp.maximum(cum[-1], _EPS)
    targets = jnp.linspace(0.0, total, s + 1)
    levels = jnp.interp(targets, cum, full_edges)
    # Pin the endpoints exactly and enforce strict monotonicity so that
    # interval lengths are never zero (degenerate λ would otherwise collapse
    # neighbouring levels).
    levels = levels.at[0].set(-alpha).at[-1].set(alpha)
    min_step = 2.0 * alpha * 1e-6 / (s + 1)
    levels = jax.lax.cummax(levels + min_step * jnp.arange(s + 1), axis=0) - min_step * jnp.arange(s + 1)
    return levels.astype(jnp.float32)


class QuantMeta(NamedTuple):
    """Per-tensor quantization metadata shipped alongside the codes.

    ``levels`` has static shape (s+1,). For a uniform quantizer the levels are
    the linspace over [-alpha, alpha]; decode is a pure table lookup either
    way, so the wire format is identical for all methods.
    """

    levels: jax.Array  # (s+1,) float32 codebook
    alpha: jax.Array   # scalar float32 truncation threshold (levels[-1])


def stochastic_encode(g: jax.Array, meta: QuantMeta, key: jax.Array) -> jax.Array:
    """Truncate + stochastically quantize ``g`` onto ``meta.levels`` (Eq. 4).

    Returns uint8 codes with the same shape as ``g`` (code k means levels[k]).
    Unbiased:  E[levels[code]] = truncate(g, alpha).
    """
    levels = meta.levels
    s = levels.shape[0] - 1
    gt = truncate(g, meta.alpha)
    # Interval index: k such that levels[k] <= gt < levels[k+1].
    k = jnp.clip(jnp.searchsorted(levels, gt, side="right") - 1, 0, s - 1)
    lo = levels[k]
    hi = levels[k + 1]
    # u < (gt − lo)/(hi − lo), division-free as in the kernels
    up = jax.random.uniform(key, g.shape) * jnp.maximum(hi - lo, _EPS) < gt - lo
    return (k + up.astype(k.dtype)).astype(jnp.uint8)


def decode(codes: jax.Array, meta: QuantMeta) -> jax.Array:
    """Map codes back to codebook values."""
    return jnp.take(meta.levels, codes.astype(jnp.int32))


def quantize(g: jax.Array, meta: QuantMeta, key: jax.Array) -> jax.Array:
    """encode+decode in one step: the quantized surrogate of ``g``."""
    return decode(stochastic_encode(g, meta, key), meta)


# ---------------------------------------------------------------------------
# Bit packing: codes (<= 8 bits each) into uint32 lanes for the wire format.
# Packs groups of 32 codes into ``bits`` uint32 words by bit-slicing, so the
# on-wire size is exactly bits/32 words per element (plus padding to 32).
# ---------------------------------------------------------------------------


def packed_size(n: int, bits: int) -> int:
    """Number of uint32 words for n codes at ``bits`` bits each."""
    groups = (n + 31) // 32
    return groups * bits


def pack_codes(codes: jax.Array, bits: int) -> jax.Array:
    """Pack flat uint8 codes (values < 2^bits) into a uint32 array.

    Layout: group g, bit-plane j -> word[g, j] holds bit j of codes
    [32g .. 32g+31] in its 32 lanes.  Shape: (ceil(n/32) * bits,).
    """
    n = codes.shape[0]
    groups = (n + 31) // 32
    padded = jnp.zeros((groups * 32,), jnp.uint32).at[:n].set(codes.astype(jnp.uint32))
    padded = padded.reshape(groups, 32)
    lane = (jnp.arange(32, dtype=jnp.uint32))[None, :, None]          # (1, 32, 1)
    planes = (padded[:, :, None] >> jnp.arange(bits, dtype=jnp.uint32)[None, None, :]) & 1
    words = jnp.sum(planes << lane, axis=1, dtype=jnp.uint32)          # (groups, bits)
    return words.reshape(-1)


def unpack_codes(words: jax.Array, n: int, bits: int) -> jax.Array:
    """Inverse of :func:`pack_codes`; returns (n,) uint8 codes."""
    groups = words.shape[0] // bits
    w = words.reshape(groups, bits)                                    # (groups, bits)
    lane = jnp.arange(32, dtype=jnp.uint32)[None, :, None]             # (1, 32, 1)
    bitvals = (w[:, None, :] >> lane) & 1                              # (groups, 32, bits)
    codes = jnp.sum(bitvals << jnp.arange(bits, dtype=jnp.uint32)[None, None, :], axis=2)
    return codes.reshape(-1)[:n].astype(jnp.uint8)

# Wire accounting lives in ``compressors.wire_bytes`` /
# ``compressors.wire_bits_per_element`` — the single source of truth for
# payload + codebook metadata costs (a former duplicate here charged the
# metadata differently and had no callers).
