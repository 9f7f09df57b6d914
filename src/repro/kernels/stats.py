"""Fused Pallas bucket-statistics kernel for the adaptive telemetry pass.

One VMEM pass over a flat fp32 bucket produces everything the online tail
estimator needs — replacing the per-step full sort (``jnp.quantile``) the
offline fit uses:

- per-bin counts of a 128-bin log2-spaced |g| histogram (bin 0 catches
  underflow/zeros, bin K-1 catches overflow);
- per-bin sums of ln|g| (the Hill-estimator accumulator: the tail's
  ``sum log(g_j/g_min)`` is a suffix sum of these minus ``n_tail ln g_min``);
- max |g|, sum g, sum g² (scale envelope + EMA moments).

Tiling: (rows, 128) fp32 input blocked (BLOCK_ROWS, 128) per grid step.
The histogram is accumulated lane-parallel across the whole grid: two VMEM
scratch accumulators, counts and ln-sums, hold one (8, 128) tile per bin.
A block is walked in chunks of CHUNK_ROWS rows whose bin ids and ln|g|
stay in registers; per bin, a chunk costs one compare, two selects and
vreg adds into the bin's tiles — no cross-lane reduction and no scalar
broadcast inside the bin loop.  On the last grid step each bin's tile is
reduced across its sublanes and lanes (strided loads, one transpose, one
sublane sum: fp32 vector adds, exact for integer counts) into the
(8, 128) output tile: row 0 counts, row 1 log-sums, bin k in lane k.  Rows
2-4 (max, sum, sum-of-squares) merge per block (max with ``maximum``, the
sums add).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .quantize import smem_spec

LANES = 128
SUBLANES = 8             # rows of one fp32 vreg
BLOCK_ROWS = 512         # rows of one grid step's block
CHUNK_ROWS = 64          # rows whose bin ids and ln|g| stay in vregs over the bin loop
BIN_UNROLL = 8           # bins per rolled-loop iteration

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


def _valid(shape: tuple[int, int], first, n) -> jax.Array:
    """Mask of the elements of a (rows, 128) tile whose flat index, counted
    from ``first`` at its top-left, lies inside the bucket's ``n``."""
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return first + row * LANES + col < n


def _bins_and_logs(g: jax.Array, valid: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Bin ids (padding: -1, which matches no bin) and masked ln|g| of a tile."""
    # Select, never multiply by the mask: the rows of a partial last block
    # lie past the array, hold whatever the buffer held (NaN and inf
    # included), and 0 · NaN would poison every sum.
    gabs = jnp.where(valid, jnp.abs(g), 0.0)
    lnv = jnp.where(valid, jnp.log(jnp.maximum(gabs, _TINY)), 0.0)
    return jnp.where(valid, bin_index(gabs), -1), lnv


def _fold_rows(x: jax.Array) -> jax.Array:
    """(rows, 128) -> (8, 128): the sum of its (8, 128) row groups, added
    one after another (plain vreg adds, no cross-lane reduction)."""
    acc = x[:SUBLANES]
    for r in range(SUBLANES, x.shape[0], SUBLANES):
        acc = acc + x[r:r + SUBLANES]
    return acc


def _bin_partials(b: jax.Array, lnv: jax.Array, k) -> tuple[jax.Array, jax.Array]:
    """Bin ``k``'s (8, 128) lane partials of a chunk: int32 count, ln-sum."""
    m = b == k
    return _fold_rows(jnp.where(m, 1, 0)), _fold_rows(jnp.where(m, lnv, 0.0))


def _moment_tile(g: jax.Array, valid: jax.Array) -> jax.Array:
    """A block's max |g|, Σg and Σg² in rows 2-4 of a stats tile (the
    histogram rows 0-1 left zero)."""
    gv = jnp.where(valid, g, 0.0)
    gmax = jnp.max(jnp.where(valid, jnp.abs(g), 0.0))
    gsum = jnp.sum(gv)
    gsq = jnp.sum(gv * gv)
    return jnp.concatenate(
        [
            jnp.zeros((2, NUM_BINS), jnp.float32),
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


def _lane_totals(sublane) -> jax.Array:
    """(1, NUM_BINS) fp32 totals of a (NUM_BINS·8, 128) lane-parallel
    accumulator, bin k in lane k.  ``sublane(s)`` gives the (NUM_BINS, 128)
    rows that hold sublane s of every bin's tile; their sum, transposed,
    puts bins in lanes, and one sublane-direction sum finishes.  Every add
    is a vector add in the accumulator's dtype (no matmul): int32 counts are
    exact in any order and round to fp32 once, at the end."""
    per_bin = sublane(0)
    for s in range(1, SUBLANES):
        per_bin = per_bin + sublane(s)
    return jnp.sum(per_bin.T, axis=0, keepdims=True).astype(jnp.float32)


def _block_stats(x: jax.Array, valid: jax.Array, counts: jax.Array,
                 log_sums: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One (BLOCK_ROWS, 128) block in the kernel's order, as values.

    Returns the block's moment tile and the count and ln-sum accumulators
    (:func:`accumulators`) with the block folded in: chunk by chunk, bin by
    bin, each bin's lane partials added to its (8, 128) tile.  The kernel
    runs the same pieces on VMEM scratch; the blockwise oracle runs this.
    """
    def chunk(j, acc):
        r0 = j * CHUNK_ROWS
        b, lnv = _bins_and_logs(jax.lax.dynamic_slice_in_dim(x, r0, CHUNK_ROWS),
                                jax.lax.dynamic_slice_in_dim(valid, r0, CHUNK_ROWS))

        def one_bin(k, acc):
            return tuple(
                jax.lax.dynamic_update_slice_in_dim(
                    a, jax.lax.dynamic_slice_in_dim(a, k * SUBLANES, SUBLANES) + p,
                    k * SUBLANES, 0)
                for a, p in zip(acc, _bin_partials(b, lnv, k)))

        return jax.lax.fori_loop(0, NUM_BINS, one_bin, acc)

    counts, log_sums = jax.lax.fori_loop(0, BLOCK_ROWS // CHUNK_ROWS, chunk,
                                         (counts, log_sums))
    return _moment_tile(x, valid), counts, log_sums


def _accumulate(n_ref, x_ref, out_ref, cnt_ref, ls_ref):
    """Grid step ``i`` of a statistics kernel over the (rows, 128) ``x``.

    The histogram stays lane-parallel across the grid: ``cnt_ref`` and
    ``ls_ref`` hold one (8, 128) tile per bin, and a block adds, per chunk
    of CHUNK_ROWS rows and per bin, one compare, two selects and the vreg
    adds of :func:`_bin_partials`.  The moment rows of ``out_ref`` merge per
    block.  The last step reduces each bin's tile across its lanes, once a
    bucket, into rows 0-1 of the output tile.
    """
    i = pl.program_id(0)
    n = n_ref[0]
    first = i * (BLOCK_ROWS * LANES)

    @pl.when(i == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)
        ls_ref[...] = jnp.zeros_like(ls_ref)

    x = x_ref[...]
    out_ref[...] = _merge(out_ref[...], _moment_tile(x, _valid(x.shape, first, n)))

    def chunk(j, carry):
        r0 = pl.multiple_of(j * CHUNK_ROWS, CHUNK_ROWS)
        start = first + r0 * LANES

        @pl.when(start < n)   # chunks wholly past the bucket add nothing
        def _():
            b, lnv = _bins_and_logs(x_ref[pl.ds(r0, CHUNK_ROWS), :],
                                    _valid((CHUNK_ROWS, LANES), start, n))

            def bins(kb, carry):   # unrolled by hand: Mosaic unrolls a loop only fully
                for u in range(BIN_UNROLL):
                    k = kb * BIN_UNROLL + u
                    rows = pl.ds(pl.multiple_of(k * SUBLANES, SUBLANES), SUBLANES)
                    cnt, ls = _bin_partials(b, lnv, k)
                    cnt_ref[rows, :] += cnt
                    ls_ref[rows, :] += ls
                return carry

            jax.lax.fori_loop(0, NUM_BINS // BIN_UNROLL, bins, 0)

        return carry

    jax.lax.fori_loop(0, BLOCK_ROWS // CHUNK_ROWS, chunk, 0)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        def sublane(ref):
            return lambda s: ref[pl.ds(s, NUM_BINS, stride=SUBLANES), :]

        out_ref[0:1, :] = _lane_totals(sublane(cnt_ref))
        out_ref[1:2, :] = _lane_totals(sublane(ls_ref))


def _bucket_stats_kernel(n_ref, g_ref, out_ref, cnt_ref, ls_ref):
    _accumulate(n_ref, g_ref, out_ref, cnt_ref, ls_ref)


_ACC_SHAPE = (NUM_BINS * SUBLANES, LANES)
_ACC_DTYPES = (jnp.int32, jnp.float32)     # counts, ln-sums


def accumulators() -> tuple[jax.Array, jax.Array]:
    """Zero count and ln-sum accumulators, one (8, 128) tile per bin."""
    return tuple(jnp.zeros(_ACC_SHAPE, d) for d in _ACC_DTYPES)


def accumulator_scratch() -> list:
    """The same two accumulators as VMEM scratch of a stats kernel."""
    return [pltpu.VMEM(_ACC_SHAPE, d) for d in _ACC_DTYPES]


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
        scratch_shapes=accumulator_scratch(),
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
