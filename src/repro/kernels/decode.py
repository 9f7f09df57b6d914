"""Fused unpack → dequantize → peer-reduce Pallas kernels for decode.

The decode half of every bucketed collective receives, per peer, a row of
packed uint32 wire words plus that peer's (s+1,) codebook, and needs either

- the **peer mean** (ring-mean / reduce-scatter sites): one (m,) fp32 vector
  averaging all peers' dequantized tensors, or
- the **peer concatenation** (the all-gather phase-2 sites): peer j's chunk
  decoded into its own output segment.

The pre-existing path (``vmap(unpack_codes)`` + ``jnp.take`` + ``jnp.mean``)
materializes the full (n_peers, m) int32 code tensor *and* the (n_peers, m)
fp32 value tensor in HBM before reducing — O(n_peers·m) traffic for an (m,)
result.  These kernels stream one (BLOCK_ROWS, 4·bits)-word tile per peer
through VMEM, unpack it with the bit-plane arithmetic inverse of
``quantize._pack_block``, dequantize against that peer's codebook, and
accumulate straight into the output tile; the unpacked codes never leave
VMEM.

Grid: ``(row_blocks, n_peers)`` with the peer axis innermost, so the output
tile for one row block stays resident while every peer's contribution is
folded in (zero-init at peer 0, divide by n at the last peer — the mean is a
*sequential* peer accumulation, which the ``ref`` oracles and the
shard_map-safe jnp fallbacks reproduce op-for-op: bit-exact for the
codebook variants, whose dequant is an exact table lookup; ulp-level FMA
discretion remains for the uniform multiply-add dequant).

Word layout matches ``core.quantizers.pack_codes``: flat element i lives in
group ``g = i // 32``, lane ``i % 32``; group g's ``bits`` bit-plane words
occupy word columns ``[g·bits, (g+1)·bits)``.  Reshaped to the kernel's
(rows, 128) element tiling that is exactly (rows, 4·bits) words per row.

Tiling: BLOCK_ROWS=128 for the uniform kernels (working set ≈ 0.5 MB);
BLOCK_ROWS=64 for the codebook kernels.  The per-peer codebooks (or alphas)
ride whole and flat in SMEM, read as scalars at the grid's peer index; the
codebook dequant is a select chain over them (``quantize.codebook_lookup``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .quantize import codebook_lookup, smem_spec

LANES = 128
BLOCK_ROWS = 128            # uniform decode tiles
BLOCK_ROWS_CODEBOOK = 64    # codebook decode tiles


def words_per_row(bits: int) -> int:
    """uint32 wire words per (128,) element row: 4 groups of 32 × bit-planes."""
    return (LANES // 32) * bits


def _unpack_block(words: jax.Array, bits: int) -> jax.Array:
    """(BM, 4·bits) int32 bit-plane words -> (BM, 128) int32 codes.

    Inverse of ``quantize._pack_block``: lane l of word column q·bits+j holds
    bit j of element 32q+l.  Arithmetic vs logical shift is irrelevant under
    the &1 mask, so int32 words decode the uint32 wire exactly.
    """
    bm = words.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (bm, 32), 1)
    cols = []
    for q in range(LANES // 32):
        code = jnp.zeros((bm, 32), jnp.int32)
        for j in range(bits):
            w = words[:, q * bits + j][:, None]                   # (BM, 1)
            code = code + (((w >> lane) & 1) << j)
        cols.append(code)
    return jnp.concatenate(cols, axis=1)


def _uniform_vals(words_ref, alpha_ref, *, s: int, bits: int) -> jax.Array:
    codes = _unpack_block(words_ref[0], bits).astype(jnp.float32)
    alpha = alpha_ref[pl.program_id(1)]
    step = 2.0 * alpha / s
    return codes * step - alpha


def _codebook_vals(words_ref, levels_ref, *, s: int, bits: int) -> jax.Array:
    # Dequant as a select chain over this peer's s+1 scalar levels (SMEM):
    # an exact table lookup with no gather and no MXU rounding.
    codes = _unpack_block(words_ref[0], bits)
    return codebook_lookup(levels_ref, codes, s=s, base=pl.program_id(1) * (s + 1))


def _reduce_tail(out_ref, vals: jax.Array, n_peers: int) -> None:
    """Accumulate one peer's dequantized tile; mean at the last peer."""
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] = out_ref[...] + vals

    @pl.when(p == n_peers - 1)
    def _():
        out_ref[...] = out_ref[...] / n_peers


def _uniform_decode_reduce_kernel(words_ref, alpha_ref, out_ref, *, s, bits, n_peers):
    _reduce_tail(out_ref, _uniform_vals(words_ref, alpha_ref, s=s, bits=bits), n_peers)


def _codebook_decode_reduce_kernel(words_ref, levels_ref, out_ref, *, s, bits, n_peers):
    _reduce_tail(out_ref, _codebook_vals(words_ref, levels_ref, s=s, bits=bits), n_peers)


def _uniform_decode_rows_kernel(words_ref, alpha_ref, out_ref, *, s, bits):
    out_ref[0] = _uniform_vals(words_ref, alpha_ref, s=s, bits=bits)


def _codebook_decode_rows_kernel(words_ref, levels_ref, out_ref, *, s, bits):
    out_ref[0] = _codebook_vals(words_ref, levels_ref, s=s, bits=bits)


def _call(kernel, words3: jax.Array, meta2: jax.Array, *, bits: int, block_rows: int,
          reduce: bool, interpret: bool, **kw) -> jax.Array:
    """Shared pallas_call builder.

    ``words3``: (n_peers, rows_p, 4·bits) int32 with rows_p a multiple of
    ``block_rows``; ``meta2``: (n_peers, k) fp32 per-peer codebook operand
    ((n, 1) alphas or (n, s+1) levels), passed flat and whole in SMEM (a
    per-peer (1, k) VMEM block would break the (8, 128) tiling).
    ``reduce=True`` accumulates the peer mean into one (rows_p, 128) tile
    set; ``reduce=False`` writes each peer's decode into its own
    (rows_p, 128) band of a (n_peers, rows_p, 128) output.
    """
    n_peers, rows_p, wc = words3.shape
    assert wc == words_per_row(bits) and rows_p % block_rows == 0
    nblocks = rows_p // block_rows
    grid = (nblocks, n_peers)
    if reduce:
        out_spec = pl.BlockSpec((block_rows, LANES), lambda i, p: (i, 0))
        out_shape = jax.ShapeDtypeStruct((rows_p, LANES), jnp.float32)
        kw = dict(kw, n_peers=n_peers)
    else:
        out_spec = pl.BlockSpec((1, block_rows, LANES), lambda i, p: (p, i, 0))
        out_shape = jax.ShapeDtypeStruct((n_peers, rows_p, LANES), jnp.float32)
    return pl.pallas_call(
        functools.partial(kernel, bits=bits, **kw),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_rows, wc), lambda i, p: (p, i, 0)),
            smem_spec(),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(words3, meta2.reshape(-1))


def uniform_decode_reduce_3d(words3, alphas2, *, bits: int, interpret: bool) -> jax.Array:
    s = 2**bits - 1
    return _call(_uniform_decode_reduce_kernel, words3, alphas2, bits=bits,
                 block_rows=BLOCK_ROWS, reduce=True, interpret=interpret, s=s)


def codebook_decode_reduce_3d(words3, levels2, *, bits: int, interpret: bool) -> jax.Array:
    s = levels2.shape[1] - 1
    return _call(_codebook_decode_reduce_kernel, words3, levels2, bits=bits,
                 block_rows=BLOCK_ROWS_CODEBOOK, reduce=True, interpret=interpret, s=s)


def uniform_decode_rows_3d(words3, alphas2, *, bits: int, interpret: bool) -> jax.Array:
    s = 2**bits - 1
    return _call(_uniform_decode_rows_kernel, words3, alphas2, bits=bits,
                 block_rows=BLOCK_ROWS, reduce=False, interpret=interpret, s=s)


def codebook_decode_rows_3d(words3, levels2, *, bits: int, interpret: bool) -> jax.Array:
    s = levels2.shape[1] - 1
    return _call(_codebook_decode_rows_kernel, words3, levels2, bits=bits,
                 block_rows=BLOCK_ROWS_CODEBOOK, reduce=False, interpret=interpret, s=s)


__all__ = [
    "BLOCK_ROWS",
    "BLOCK_ROWS_CODEBOOK",
    "codebook_decode_reduce_3d",
    "codebook_decode_rows_3d",
    "uniform_decode_reduce_3d",
    "uniform_decode_rows_3d",
    "words_per_row",
]
