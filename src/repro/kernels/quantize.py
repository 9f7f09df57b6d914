"""Pallas TPU kernels for the two-stage quantizer hot path.

The paper's per-step hot spot is the element-wise encode/decode over every
gradient element (d can be billions).  On TPU we fuse
truncate → scale → stochastic-round → code  into one VMEM pass.

Tiling: inputs are reshaped to (rows, 128) — the 128-lane register width —
and blocked (BLOCK_ROWS, 128) per grid step.  BLOCK_ROWS=256 keeps the
working set (g + rand + codes) at 256·128·(4+4+4) B ≈ 0.4 MB; the codebook
variants add a few same-shape temporaries (interval index, lo, hi).  The
(s+1,) codebook and the scalars ride whole in SMEM: each level is read as a
scalar and broadcast, so the codebook walk is s compares plus s+1 selects
per element, with no gather (Mosaic lowers only 2-D gathers) and no MXU
pass (whose fp32 rounding would make the lookup inexact).

Codes are emitted as int32 in-kernel (TPU stores are word-aligned; the
wrapper narrows to uint8 / packs to uint32 lanes outside).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BLOCK_ROWS = 256
MAX_UNROLL = 16    # codebook loops unroll fully up to 4 bits (Mosaic: all or none)


def uniform_scalars(alpha: jax.Array, s: int) -> jax.Array:
    """(2,) fp32 ``[α, s/(2α)]`` SMEM operand of the uniform encode kernels.

    The scale is divided out here, by the same XLA op the ``kernels.ref``
    oracle runs, rather than in the kernel: a scale that differed in its
    last bit would move every element's rounding point and break the
    bit-identical wire contract between kernel and oracle on the chip.
    """
    alpha = alpha.astype(jnp.float32).reshape(())
    return jnp.stack([alpha, s / (2.0 * alpha)])


def uniform_select(alpha, scale, g: jax.Array, rand: jax.Array, *, s: int) -> jax.Array:
    """Shared uniform stochastic-encode body -> float codes in [0, s].

    The single source of the truncate → scale → floor → stochastic-round
    math for every uniform encode kernel (the plain, the packed, and the
    fused residual variants in ``encode_fused``) — keeping the bit-identical
    wire contract between kernel families in one place.  ``scale`` is
    ``s/(2α)`` from :func:`uniform_scalars`.
    """
    u = (jnp.clip(g, -alpha, alpha) + alpha) * scale
    k = jnp.clip(jnp.floor(u), 0.0, float(s - 1))
    up = (rand < (u - k)).astype(jnp.float32)
    return jnp.clip(k + up, 0.0, float(s))


def codebook_select(levels_ref, g: jax.Array, rand: jax.Array,
                    *, s: int) -> tuple[jax.Array, jax.Array]:
    """Shared codebook stochastic-encode body.

    ``levels_ref`` holds the (s+1,) codebook in scalar memory (SMEM); each
    level is read as a scalar and broadcast, so the kernel needs no gather
    (Mosaic lowers only 2-D gathers) and no MXU pass (whose fp32 precision
    would make the lookup inexact).  Returns ``(codes float, dequant value)``
    in ``g``'s (BM, 128) layout: the dequant is the interval endpoint the
    rounding chose (``hi`` on round-up, ``lo`` otherwise), which equals
    ``levels[code]`` exactly — callers that don't need it (the non-residual
    kernels) leave it dead for DCE.  Single source of the compare-count,
    lo/hi select and rounding decision for every codebook encode kernel.
    """
    alpha = levels_ref[s]
    gt = jnp.clip(g, -alpha, alpha)

    def count(j, k):
        return k + (gt >= levels_ref[j]).astype(jnp.float32)

    k = jax.lax.fori_loop(1, s + 1, count, jnp.zeros_like(gt), unroll=s < MAX_UNROLL)
    k = jnp.clip(k, 0.0, float(s - 1))

    def pick(j, lo_hi):
        lv, jf = levels_ref[j], j.astype(jnp.float32)
        return (jnp.where(k == jf, lv, lo_hi[0]),
                jnp.where(k == jf - 1.0, lv, lo_hi[1]))

    lo, hi = jax.lax.fori_loop(0, s + 1, pick, (jnp.zeros_like(gt), jnp.zeros_like(gt)),
                               unroll=s < MAX_UNROLL)
    # rand < (gt − lo)/(hi − lo), without the division: XLA and Mosaic
    # round an fp32 quotient differently, a product they round alike
    up = (rand * jnp.maximum(hi - lo, 1e-12) < gt - lo).astype(jnp.float32)
    return k + up, jnp.where(up > 0.0, hi, lo)


def smem_spec() -> pl.BlockSpec:
    """Whole small operand (scalars, codebooks) in scalar memory."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _uniform_encode_kernel(alpha_ref, g_ref, rand_ref, out_ref, *, s: int):
    out_ref[...] = uniform_select(alpha_ref[0], alpha_ref[1], g_ref[...], rand_ref[...],
                                  s=s).astype(jnp.int32)


def uniform_encode_2d(
    g: jax.Array, rand: jax.Array, alpha: jax.Array, *, bits: int, interpret: bool
) -> jax.Array:
    """g, rand: (rows, 128) float32; returns (rows, 128) int32 codes."""
    rows = g.shape[0]
    s = 2**bits - 1
    grid = (pl.cdiv(rows, BLOCK_ROWS),)
    return pl.pallas_call(
        functools.partial(_uniform_encode_kernel, s=s),
        grid=grid,
        in_specs=[
            smem_spec(),  # [alpha, scale]
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        interpret=interpret,
    )(uniform_scalars(alpha, s), g, rand)


def _uniform_decode_kernel(alpha_ref, codes_ref, out_ref, *, s: int):
    alpha = alpha_ref[0]
    step = 2.0 * alpha / s
    out_ref[...] = codes_ref[...].astype(jnp.float32) * step - alpha


def uniform_decode_2d(
    codes: jax.Array, alpha: jax.Array, *, bits: int, interpret: bool
) -> jax.Array:
    rows = codes.shape[0]
    s = 2**bits - 1
    grid = (pl.cdiv(rows, BLOCK_ROWS),)
    return pl.pallas_call(
        functools.partial(_uniform_decode_kernel, s=s),
        grid=grid,
        in_specs=[
            smem_spec(),
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        interpret=interpret,
    )(alpha.reshape(1), codes)


def _codebook_encode_kernel(g_ref, rand_ref, levels_ref, out_ref, *, s: int):
    # compare-count interval index + select lo/hi — shared body in
    # codebook_select
    code_f, _ = codebook_select(levels_ref, g_ref[...], rand_ref[...], s=s)
    out_ref[...] = code_f.astype(jnp.int32)


def codebook_encode_2d(
    g: jax.Array, rand: jax.Array, levels: jax.Array, *, interpret: bool
) -> jax.Array:
    rows = g.shape[0]
    s = levels.shape[0] - 1
    grid = (pl.cdiv(rows, BLOCK_ROWS),)
    return pl.pallas_call(
        functools.partial(_codebook_encode_kernel, s=s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            smem_spec(),  # levels: full operand
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        interpret=interpret,
    )(g, rand, levels)


# ---------------------------------------------------------------------------
# Fused encode -> bit-pack: codes leave VMEM already packed into uint32 lanes
# (wire layout identical to ``core.quantizers.pack_codes``: group g of 32
# consecutive flat codes -> ``bits`` bit-plane words at [g*bits, (g+1)*bits)).
# ---------------------------------------------------------------------------


def _pack_block(codes: jax.Array, bits: int) -> jax.Array:
    """(BM, 128) int32 codes -> (BM, 4*bits) int32 bit-plane words.

    Column q*bits+j holds bit-plane j of the 32 consecutive codes
    [r*128 + 32q, r*128 + 32q + 32); flattening row-major reproduces the
    ``pack_codes`` word order exactly.
    """
    bm = codes.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (bm, 32), 1)
    cols = []
    for q in range(LANES // 32):
        sub = codes[:, 32 * q:32 * (q + 1)]
        for j in range(bits):
            plane = (sub >> j) & 1
            cols.append(jnp.sum(plane << lane, axis=1, dtype=jnp.int32))
    return jnp.stack(cols, axis=1)


def _mask_tail(codes: jax.Array, n_ref, bm: int) -> jax.Array:
    """Zero codes past the true element count so padding words match
    ``pack_codes``' zero padding bit-for-bit."""
    base = pl.program_id(0) * bm
    row = jax.lax.broadcasted_iota(jnp.int32, (bm, LANES), 0) + base
    col = jax.lax.broadcasted_iota(jnp.int32, (bm, LANES), 1)
    return jnp.where(row * LANES + col < n_ref[0], codes, 0)


def _uniform_encode_pack_kernel(n_ref, alpha_ref, g_ref, rand_ref, codes_ref, words_ref,
                                *, s: int, bits: int):
    g = g_ref[...]
    code_f = uniform_select(alpha_ref[0], alpha_ref[1], g, rand_ref[...], s=s)
    codes = _mask_tail(code_f.astype(jnp.int32), n_ref, g.shape[0])
    codes_ref[...] = codes
    words_ref[...] = _pack_block(codes, bits)


def uniform_encode_pack_2d(
    g: jax.Array, rand: jax.Array, alpha: jax.Array, n: int, *, bits: int, interpret: bool
) -> tuple[jax.Array, jax.Array]:
    """Fused uniform encode + bit-pack.  Returns ((rows,128) int32 codes,
    (rows, 4*bits) int32 words)."""
    rows = g.shape[0]
    s = 2**bits - 1
    wc = (LANES // 32) * bits
    grid = (pl.cdiv(rows, BLOCK_ROWS),)
    return pl.pallas_call(
        functools.partial(_uniform_encode_pack_kernel, s=s, bits=bits),
        grid=grid,
        in_specs=[
            smem_spec(),  # n: full (1,) operand
            smem_spec(),  # [alpha, scale]
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_ROWS, wc), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((rows, wc), jnp.int32),
        ],
        interpret=interpret,
    )(jnp.asarray([n], jnp.int32), uniform_scalars(alpha, s), g, rand)


def _codebook_encode_pack_kernel(n_ref, g_ref, rand_ref, levels_ref, codes_ref, words_ref,
                                 *, s: int, bits: int):
    g = g_ref[...]
    code_f, _ = codebook_select(levels_ref, g, rand_ref[...], s=s)
    codes = _mask_tail(code_f.astype(jnp.int32), n_ref, g.shape[0])
    codes_ref[...] = codes
    words_ref[...] = _pack_block(codes, bits)


def codebook_encode_pack_2d(
    g: jax.Array, rand: jax.Array, levels: jax.Array, n: int, *, bits: int, interpret: bool
) -> tuple[jax.Array, jax.Array]:
    """Fused non-uniform encode + bit-pack (codebook variant)."""
    rows = g.shape[0]
    s = levels.shape[0] - 1
    wc = (LANES // 32) * bits
    grid = (pl.cdiv(rows, BLOCK_ROWS),)
    return pl.pallas_call(
        functools.partial(_codebook_encode_pack_kernel, s=s, bits=bits),
        grid=grid,
        in_specs=[
            smem_spec(),  # n: full (1,) operand
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            smem_spec(),  # levels: full operand
        ],
        out_specs=[
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_ROWS, wc), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((rows, wc), jnp.int32),
        ],
        interpret=interpret,
    )(jnp.asarray([n], jnp.int32), g, rand, levels)


def _codebook_decode_kernel(codes_ref, levels_ref, out_ref, *, s: int):
    out_ref[...] = codebook_lookup(levels_ref, codes_ref[...], s=s)


def codebook_lookup(level_ref, codes: jax.Array, *, s: int, base=0) -> jax.Array:
    """Exact ``levels[codes]`` as a select chain over the s+1 scalar levels
    ``level_ref[base + j]`` (SMEM): no gather, no MXU rounding."""
    def pick(j, vals):
        return jnp.where(codes == j, level_ref[base + j], vals)

    return jax.lax.fori_loop(0, s + 1, pick, jnp.zeros(codes.shape, jnp.float32),
                             unroll=s < MAX_UNROLL)


def codebook_decode_2d(codes: jax.Array, levels: jax.Array, *, interpret: bool) -> jax.Array:
    rows = codes.shape[0]
    s = levels.shape[0] - 1
    grid = (pl.cdiv(rows, BLOCK_ROWS),)
    return pl.pallas_call(
        functools.partial(_codebook_decode_kernel, s=s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            smem_spec(),
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        interpret=interpret,
    )(codes, levels)
