"""Fused encode-side Pallas kernels: EF-correct→stats and quantize→pack→residual.

The encode half of every bucketed collective used to sweep the bucket bytes
through HBM several times per step — leaf-wise EF add, a telemetry stats
pass, the ``plan()`` statistics pass, encode, a separate bit-pack, the
own-dequantization and the ``corrected − own`` residual subtraction.  These
kernels collapse that pipeline to two VMEM passes:

- :func:`ef_correct_stats_2d` — reads a gradient bucket (and its EF
  residual) once, writes the corrected bucket ``c = g + e`` and accumulates
  the full statistics tile both ``core.compressors.plan_from_stats`` and
  ``adaptive.telemetry`` consume: per-bin counts of the ``kernels.stats``
  128-bin log2-spaced |g| histogram, per-bin ln|g| Hill sums, max |g| and
  the first two moments.  The grid step is the *same function* the
  standalone ``kernels.stats`` kernel runs (``stats._accumulate``: per-bin
  lane partials in VMEM scratch across the grid, one cross-lane reduction
  per bin on the last step), applied to the corrected tile, so ``c``'s
  stats are bit-identical to ``bucket_stats_2d(c)`` in interpret mode.

- :func:`uniform_encode_pack_resid_2d` / :func:`codebook_encode_pack_resid_2d`
  — quantize the corrected bucket, bit-pack the codes into the uint32 wire
  words (``quantize._pack_block`` layout, flattening row-major reproduces
  ``pack_codes`` exactly) and write ``c − dequant(code)`` — the next EF
  residual — in the same tile.  The int code tensor and the dequantized
  ``own`` tensor never reach HBM.  The codebook dequant uses the interval
  endpoints the encode already holds (``levels[code] == hi if up else lo``),
  so the residual is an *exact* match for ``c − take(levels, codes)``; the
  uniform dequant is the usual multiply-add with compiler-discretionary FMA
  (≤ ulp-level slack vs the oracle, same contract as ``kernels.decode``).

- :func:`uniform_encode_pack_2d` / :func:`codebook_encode_pack_2d` — the
  words-only variants for sites that need no residual (the two-phase
  phase-2 re-quantization, the per-leaf codec rows): unlike the PR-2
  ``quantize.*_encode_pack_2d`` kernels they do not write the code tensor
  back to HBM at all.

Tiling matches ``kernels.quantize``: (rows, 128) fp32 blocked
(BLOCK_ROWS, 128) per grid step; the stats kernel uses the
``stats.BLOCK_ROWS`` tile and accumulators of the standalone stats kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import stats as _s
from .quantize import (
    BLOCK_ROWS,
    LANES,
    _mask_tail,
    _pack_block,
    codebook_select,
    smem_spec,
    uniform_scalars,
    uniform_select,
)

__all__ = [
    "codebook_encode_pack_2d",
    "codebook_encode_pack_resid_2d",
    "ef_correct_stats_2d",
    "uniform_encode_pack_2d",
    "uniform_encode_pack_resid_2d",
]


# ---------------------------------------------------------------------------
# One-pass EF correction + statistics
# ---------------------------------------------------------------------------


def _ef_correct_stats_kernel(n_ref, g_ref, e_ref, c_ref, out_ref, cnt_ref, ls_ref):
    c_ref[...] = g_ref[...] + e_ref[...]
    _s._accumulate(n_ref, c_ref, out_ref, cnt_ref, ls_ref)


def ef_correct_stats_2d(
    g: jax.Array, e: jax.Array, n: int, *, interpret: bool
) -> tuple[jax.Array, jax.Array]:
    """g, e: (rows, 128) fp32, n true elements ->
    ((rows, 128) corrected fp32, (STATS_ROWS, NUM_BINS) stats of corrected)."""
    rows = g.shape[0]
    grid = (pl.cdiv(rows, _s.BLOCK_ROWS),)
    return pl.pallas_call(
        _ef_correct_stats_kernel,
        grid=grid,
        in_specs=[
            smem_spec(),  # n: full (1,) operand
            pl.BlockSpec((_s.BLOCK_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((_s.BLOCK_ROWS, LANES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((_s.BLOCK_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((_s.STATS_ROWS, _s.NUM_BINS), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((_s.STATS_ROWS, _s.NUM_BINS), jnp.float32),
        ],
        scratch_shapes=_s.accumulator_scratch(),
        interpret=interpret,
    )(jnp.asarray([n], jnp.int32), g, e)


# ---------------------------------------------------------------------------
# Fused quantize -> bit-pack [-> residual]
# ---------------------------------------------------------------------------


def _uniform_encode_pack_kernel(n_ref, alpha_ref, g_ref, rand_ref, words_ref, *, s, bits):
    g = g_ref[...]
    code_f = uniform_select(alpha_ref[0], alpha_ref[1], g, rand_ref[...], s=s)
    codes = _mask_tail(code_f.astype(jnp.int32), n_ref, g.shape[0])
    words_ref[...] = _pack_block(codes, bits)


def _uniform_encode_pack_resid_kernel(n_ref, alpha_ref, g_ref, rand_ref, words_ref,
                                      resid_ref, *, s, bits):
    alpha = alpha_ref[0]
    g = g_ref[...]
    code_f = uniform_select(alpha, alpha_ref[1], g, rand_ref[...], s=s)
    codes = _mask_tail(code_f.astype(jnp.int32), n_ref, g.shape[0])
    words_ref[...] = _pack_block(codes, bits)
    step = 2.0 * alpha / s
    resid_ref[...] = g - (codes.astype(jnp.float32) * step - alpha)


def _codebook_encode_pack_kernel(n_ref, g_ref, rand_ref, levels_ref, words_ref, *, s, bits):
    g = g_ref[...]
    code_f, _ = codebook_select(levels_ref, g, rand_ref[...], s=s)
    codes = _mask_tail(code_f.astype(jnp.int32), n_ref, g.shape[0])
    words_ref[...] = _pack_block(codes, bits)


def _codebook_encode_pack_resid_kernel(n_ref, g_ref, rand_ref, levels_ref, words_ref,
                                       resid_ref, *, s, bits):
    g = g_ref[...]
    code_f, val = codebook_select(levels_ref, g, rand_ref[...], s=s)
    codes = _mask_tail(code_f.astype(jnp.int32), n_ref, g.shape[0])
    words_ref[...] = _pack_block(codes, bits)
    resid_ref[...] = g - val


def _call_encode(kernel, operands, rows: int, *, bits: int, residual: bool,
                 interpret: bool, **kw):
    """Shared pallas_call builder for the encode-pack kernels.

    ``operands``: ordered list of (array, blocked) pairs — blocked operands
    tile (BLOCK_ROWS, 128); the rest ((1,) scalars / (s+1,) codebooks) ride
    unblocked.  Outputs: the (rows, 4·bits) word tensor, plus the
    (rows, 128) residual when ``residual``.
    """
    wc = (LANES // 32) * bits
    grid = (pl.cdiv(rows, BLOCK_ROWS),)
    in_specs = [
        pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)) if blocked
        else smem_spec()
        for _, blocked in operands
    ]
    out_specs = [pl.BlockSpec((BLOCK_ROWS, wc), lambda i: (i, 0))]
    out_shape = [jax.ShapeDtypeStruct((rows, wc), jnp.int32)]
    if residual:
        out_specs.append(pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((rows, LANES), jnp.float32))
    return pl.pallas_call(
        functools.partial(kernel, bits=bits, **kw),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs if residual else out_specs[0],
        out_shape=out_shape if residual else out_shape[0],
        interpret=interpret,
    )(*(x for x, _ in operands))


def uniform_encode_pack_2d(g, rand, alpha, n: int, *, bits: int, interpret: bool):
    """Fused uniform encode + bit-pack, words only: (rows, 4·bits) int32."""
    s = 2**bits - 1
    ops = [(jnp.asarray([n], jnp.int32), False), (uniform_scalars(alpha, s), False),
           (g, True), (rand, True)]
    return _call_encode(_uniform_encode_pack_kernel, ops, g.shape[0],
                        bits=bits, residual=False, interpret=interpret, s=s)


def uniform_encode_pack_resid_2d(g, rand, alpha, n: int, *, bits: int, interpret: bool):
    """Fused uniform encode + bit-pack + residual ``g − dequant(code)``.
    Returns ((rows, 4·bits) int32 words, (rows, 128) fp32 residual)."""
    s = 2**bits - 1
    ops = [(jnp.asarray([n], jnp.int32), False), (uniform_scalars(alpha, s), False),
           (g, True), (rand, True)]
    return _call_encode(_uniform_encode_pack_resid_kernel, ops, g.shape[0],
                        bits=bits, residual=True, interpret=interpret, s=s)


def codebook_encode_pack_2d(g, rand, levels, n: int, *, bits: int, interpret: bool):
    """Fused codebook encode + bit-pack, words only."""
    s = levels.shape[0] - 1
    ops = [(jnp.asarray([n], jnp.int32), False), (g, True), (rand, True),
           (levels, False)]
    return _call_encode(_codebook_encode_pack_kernel, ops, g.shape[0],
                        bits=bits, residual=False, interpret=interpret, s=s)


def codebook_encode_pack_resid_2d(g, rand, levels, n: int, *, bits: int, interpret: bool):
    """Fused codebook encode + bit-pack + exact residual."""
    s = levels.shape[0] - 1
    ops = [(jnp.asarray([n], jnp.int32), False), (g, True), (rand, True),
           (levels, False)]
    return _call_encode(_codebook_encode_pack_resid_kernel, ops, g.shape[0],
                        bits=bits, residual=True, interpret=interpret, s=s)
