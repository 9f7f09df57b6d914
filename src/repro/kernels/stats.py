"""Fused Pallas bucket-statistics kernel for the adaptive telemetry pass.

One VMEM pass over a flat fp32 bucket produces everything the online tail
estimator needs — replacing the per-step full sort (``jnp.quantile``) the
offline fit uses:

- per-bin counts of a 128-bin log2-spaced |g| histogram (bin 0 catches
  underflow/zeros, bin K-1 catches overflow);
- per-bin sums of ln|g| (the Hill-estimator accumulator: the tail's
  ``sum log(g_j/g_min)`` is a suffix sum of these minus ``n_tail ln g_min``);
- max |g|, sum g, sum g² (scale envelope + EMA moments).

Tiling matches the quantize kernels: (rows, 128) fp32 input blocked
(BLOCK_ROWS, 128) per grid step; every grid step accumulates into the same
(8, 128) output tile (row 0 counts, row 1 log-sums, row 2 max, row 3 sum,
row 4 sum-of-squares — max rows combine with ``maximum``, the rest add).
The per-block histogram is a loop over the 128 bins, each a masked full
reduction written into its lane of the histogram rows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .quantize import smem_spec

LANES = 128
BLOCK_ROWS = 64          # 64·128 elems per grid step

NUM_BINS = 128           # == LANES so one output row holds the histogram
LOG2_LO = -24.0          # |g| below 2^-24 (incl. zeros) lands in bin 0
LOG2_HI = 8.0            # |g| above 2^8 lands in bin NUM_BINS-1
STATS_ROWS = 8           # output tile rows (5 used, see module docstring)

BINS_PER_OCTAVE = int(NUM_BINS / (LOG2_HI - LOG2_LO))   # 4: edges at 2^(k/4)
# fp32 fractional edges 2^(q/4), q < BINS_PER_OCTAVE: every bin edge is one
# of these times a power of two, so the bin of |g| follows from its exponent
# and mantissa bits exactly (no log, whose rounding differs between Mosaic
# and XLA and would move elements across edges).
_FRACTIONS = np.float32(2.0) ** (np.arange(BINS_PER_OCTAVE, dtype=np.float32) / BINS_PER_OCTAVE)
_MANTISSA_EDGES = tuple(int(x) for x in _FRACTIONS.view(np.int32)[1:] & 0x7FFFFF)

_TINY = 1e-30            # ln argument floor for exact zeros


def bin_edges() -> jax.Array:
    """(NUM_BINS+1,) |g| bin edges: edges[0]=0, edges[k]=2^(LO+k·w) else.

    Bin k covers [edges[k], edges[k+1]); the telemetry quantile snaps to an
    upper edge so tail sums over whole bins are exact w.r.t. the histogram.
    """
    k = np.arange(NUM_BINS + 1)
    e = np.ldexp(_FRACTIONS[k % BINS_PER_OCTAVE], (LOG2_LO + k // BINS_PER_OCTAVE).astype(int))
    e[0] = 0.0
    return jnp.asarray(e, jnp.float32)


def bin_index(gabs: jax.Array) -> jax.Array:
    """int32 bin ids in [0, NUM_BINS-1] of ``|g|`` values (any shape).

    Bin k covers [edges[k], edges[k+1]) of :func:`bin_edges`; read exactly
    from the fp32 bits: BINS_PER_OCTAVE · (unbiased exponent − LOG2_LO)
    plus the number of fractional edges the mantissa reaches.  Zeros and
    values below 2^LOG2_LO clip to bin 0, values above 2^LOG2_HI (and inf)
    to the top bin.
    """
    bits = jax.lax.bitcast_convert_type(gabs, jnp.int32)
    mant = bits & 0x7FFFFF
    b = BINS_PER_OCTAVE * ((bits >> 23) - 127 - int(LOG2_LO))
    for edge in _MANTISSA_EDGES:
        b = b + (mant >= edge).astype(jnp.int32)
    return jnp.clip(b, 0, NUM_BINS - 1)


def _block_stats(g: jax.Array, valid: jax.Array) -> jax.Array:
    """(BM, 128) fp32 + validity mask -> (STATS_ROWS, NUM_BINS) partials.

    Stays in the (BM, 128) layout (Mosaic cannot flatten a block): bin k's
    count and ln-sum are masked full reductions, written into lane k of the
    two histogram rows.
    """
    # Select, never multiply by the mask: the rows of a partial last block
    # lie past the array, hold whatever the buffer held (NaN and inf
    # included), and 0 · NaN would poison every sum.
    gabs = jnp.where(valid, jnp.abs(g), 0.0)
    lnv = jnp.where(valid, jnp.log(jnp.maximum(gabs, _TINY)), 0.0)
    b = jnp.where(valid, bin_index(gabs), -1)         # padding matches no bin
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, NUM_BINS), 1)

    def one_bin(k, rows):
        m = (b == k).astype(jnp.float32)
        return (jnp.where(lane == k, jnp.sum(m), rows[0]),
                jnp.where(lane == k, jnp.sum(m * lnv), rows[1]))

    zeros = jnp.zeros((1, NUM_BINS), jnp.float32)
    counts, logsum = jax.lax.fori_loop(0, NUM_BINS, one_bin, (zeros, zeros))
    gv = jnp.where(valid, g, 0.0)
    gmax = jnp.max(gabs)
    gsum = jnp.sum(gv)
    gsq = jnp.sum(gv * gv)
    return jnp.concatenate(
        [
            counts,
            logsum,
            jnp.full((1, NUM_BINS), gmax, jnp.float32),
            jnp.full((1, NUM_BINS), gsum, jnp.float32),
            jnp.full((1, NUM_BINS), gsq, jnp.float32),
            jnp.zeros((STATS_ROWS - 5, NUM_BINS), jnp.float32),
        ],
        axis=0,
    )


def _merge(acc: jax.Array, part: jax.Array) -> jax.Array:
    """Combine two stats tiles: row 2 (max) joins with maximum, the rest add."""
    row = jax.lax.broadcasted_iota(jnp.int32, (STATS_ROWS, NUM_BINS), 0)
    return jnp.where(row == 2, jnp.maximum(acc, part), acc + part)


def _bucket_stats_kernel(n_ref, g_ref, out_ref):
    g = g_ref[...]
    bm = g.shape[0]
    base = pl.program_id(0) * bm
    row = jax.lax.broadcasted_iota(jnp.int32, (bm, LANES), 0) + base
    col = jax.lax.broadcasted_iota(jnp.int32, (bm, LANES), 1)
    valid = row * LANES + col < n_ref[0]
    part = _block_stats(g, valid)

    @pl.when(pl.program_id(0) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] = _merge(out_ref[...], part)


def bucket_stats_2d(g: jax.Array, n: int, *, interpret: bool) -> jax.Array:
    """g: (rows, 128) fp32, n true elements -> (STATS_ROWS, NUM_BINS) fp32."""
    rows = g.shape[0]
    grid = (pl.cdiv(rows, BLOCK_ROWS),)
    return pl.pallas_call(
        _bucket_stats_kernel,
        grid=grid,
        in_specs=[
            smem_spec(),  # n: full (1,) operand
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((STATS_ROWS, NUM_BINS), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((STATS_ROWS, NUM_BINS), jnp.float32),
        interpret=interpret,
    )(jnp.asarray([n], jnp.int32), g)


__all__ = [
    "BLOCK_ROWS",
    "LOG2_HI",
    "LOG2_LO",
    "NUM_BINS",
    "STATS_ROWS",
    "bin_edges",
    "bin_index",
    "bucket_stats_2d",
]
