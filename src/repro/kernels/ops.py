"""jit'd public wrappers around the Pallas quantization kernels.

Handles flattening/padding to the (rows, 128) kernel layout, dtype
narrowing, and the CPU fallback (interpret=True) so the same API runs in
tests and on TPU.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from typing import NamedTuple

from . import decode as _d
from . import encode_fused as _e
from . import quantize as _k
from . import stats as _s

LANES = _k.LANES


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _to_2d(x: jax.Array) -> tuple[jax.Array, int]:
    n = x.size
    rows = -(-n // LANES)
    pad = rows * LANES - n
    x2 = jnp.pad(x.reshape(-1), (0, pad)).reshape(rows, LANES)
    return x2, n


@partial(jax.jit, static_argnames=("bits", "interpret"))
def uniform_encode(
    g: jax.Array, alpha: jax.Array, bits: int, key: jax.Array, *, interpret: bool | None = None
) -> jax.Array:
    """Fused truncate + uniform stochastic encode.  Returns flat uint8 codes."""
    interpret = _use_interpret() if interpret is None else interpret
    g2, n = _to_2d(g.astype(jnp.float32))
    rand = jax.random.uniform(key, g2.shape, jnp.float32)
    codes = _k.uniform_encode_2d(g2, rand, alpha.astype(jnp.float32), bits=bits, interpret=interpret)
    return codes.reshape(-1)[:n].astype(jnp.uint8)


@partial(jax.jit, static_argnames=("bits", "interpret"))
def uniform_decode(
    codes: jax.Array, alpha: jax.Array, bits: int, *, interpret: bool | None = None
) -> jax.Array:
    interpret = _use_interpret() if interpret is None else interpret
    c2, n = _to_2d(codes.astype(jnp.int32))
    vals = _k.uniform_decode_2d(c2, alpha.astype(jnp.float32), bits=bits, interpret=interpret)
    return vals.reshape(-1)[:n]


@partial(jax.jit, static_argnames=("bits", "interpret"))
def uniform_encode_packed(
    g: jax.Array, alpha: jax.Array, bits: int, key: jax.Array, *, interpret: bool | None = None
) -> tuple[jax.Array, jax.Array]:
    """Fused truncate + uniform stochastic encode + bit-pack.

    Returns ``(words, codes)``: uint32 wire words (``packed_size(n, bits)``,
    bit-identical to ``pack_codes`` of the same codes) plus the flat uint8
    codes for local dequantization (error feedback).
    """
    from repro.core.quantizers import packed_size

    interpret = _use_interpret() if interpret is None else interpret
    g2, n = _to_2d(g.astype(jnp.float32))
    rand = jax.random.uniform(key, g2.shape, jnp.float32)
    codes, words = _k.uniform_encode_pack_2d(
        g2, rand, alpha.astype(jnp.float32), n, bits=bits, interpret=interpret)
    words = jax.lax.bitcast_convert_type(words.reshape(-1), jnp.uint32)[: packed_size(n, bits)]
    return words, codes.reshape(-1)[:n].astype(jnp.uint8)


@partial(jax.jit, static_argnames=("bits", "interpret"))
def codebook_encode_packed(
    g: jax.Array, levels: jax.Array, bits: int, key: jax.Array, *, interpret: bool | None = None
) -> tuple[jax.Array, jax.Array]:
    """Fused non-uniform encode + bit-pack onto ``levels``; see
    :func:`uniform_encode_packed` for the return contract."""
    from repro.core.quantizers import packed_size

    interpret = _use_interpret() if interpret is None else interpret
    g2, n = _to_2d(g.astype(jnp.float32))
    rand = jax.random.uniform(key, g2.shape, jnp.float32)
    codes, words = _k.codebook_encode_pack_2d(
        g2, rand, levels.astype(jnp.float32), n, bits=bits, interpret=interpret)
    words = jax.lax.bitcast_convert_type(words.reshape(-1), jnp.uint32)[: packed_size(n, bits)]
    return words, codes.reshape(-1)[:n].astype(jnp.uint8)


@partial(jax.jit, static_argnames=("interpret",))
def codebook_encode(
    g: jax.Array, levels: jax.Array, key: jax.Array, *, interpret: bool | None = None
) -> jax.Array:
    """Fused truncate + non-uniform stochastic encode onto ``levels``."""
    interpret = _use_interpret() if interpret is None else interpret
    g2, n = _to_2d(g.astype(jnp.float32))
    rand = jax.random.uniform(key, g2.shape, jnp.float32)
    codes = _k.codebook_encode_2d(g2, rand, levels.astype(jnp.float32), interpret=interpret)
    return codes.reshape(-1)[:n].astype(jnp.uint8)


@partial(jax.jit, static_argnames=("interpret",))
def codebook_decode(
    codes: jax.Array, levels: jax.Array, *, interpret: bool | None = None
) -> jax.Array:
    interpret = _use_interpret() if interpret is None else interpret
    c2, n = _to_2d(codes.astype(jnp.int32))
    vals = _k.codebook_decode_2d(c2, levels.astype(jnp.float32), interpret=interpret)
    return vals.reshape(-1)[:n]


# ---------------------------------------------------------------------------
# Fused decode: packed wire rows + per-peer codebooks -> peer mean (or per-
# peer rows) without materializing the (n_peers, m) unpacked code tensor.
# ---------------------------------------------------------------------------


def _to_words3(words: jax.Array, n: int, bits: int, block_rows: int) -> jax.Array:
    """(peers, packed_size(n, bits)) uint32 -> (peers, rows_p, 4·bits) int32.

    Pads each peer's word row out to whole (block_rows, 128)-element tiles;
    the pad words decode to garbage values that land past element ``n`` and
    are sliced off by the callers (packing is independent per 32-element
    group, so padding never perturbs valid elements).
    """
    from repro.core.quantizers import packed_size

    p, w = words.shape
    if w != packed_size(n, bits):
        raise ValueError(
            f"wire has {w} words per peer; {n} elements at {bits} bits need "
            f"{packed_size(n, bits)}")
    wc = _d.words_per_row(bits)
    rows = -(-n // LANES)
    rows_p = -(-rows // block_rows) * block_rows
    padded = jnp.pad(words, ((0, 0), (0, rows_p * wc - w)))
    return jax.lax.bitcast_convert_type(padded, jnp.int32).reshape(p, rows_p, wc)


@partial(jax.jit, static_argnames=("n", "bits", "interpret"))
def uniform_decode_reduce(
    words: jax.Array, alphas: jax.Array, n: int, bits: int, *, interpret: bool | None = None
) -> jax.Array:
    """Fused unpack + uniform dequant + peer mean.

    ``words``: (n_peers, packed_size(n, bits)) uint32 wire rows (one
    independently packed code row per peer, the ``pack_codes`` layout);
    ``alphas``: (n_peers,) truncation thresholds.  Returns the (n,) fp32
    mean over peers of ``code · 2α_p/s − α_p``.
    """
    interpret = _use_interpret() if interpret is None else interpret
    words3 = _to_words3(words, n, bits, _d.BLOCK_ROWS)
    a2 = alphas.astype(jnp.float32).reshape(-1, 1)
    out = _d.uniform_decode_reduce_3d(words3, a2, bits=bits, interpret=interpret)
    return out.reshape(-1)[:n]


@partial(jax.jit, static_argnames=("n", "bits", "interpret"))
def codebook_decode_reduce(
    words: jax.Array, levels: jax.Array, n: int, bits: int, *, interpret: bool | None = None
) -> jax.Array:
    """Fused unpack + codebook dequant + peer mean; ``levels``: (n_peers, s+1).

    Returns the (n,) fp32 mean over peers of ``levels_p[code]``.
    """
    interpret = _use_interpret() if interpret is None else interpret
    words3 = _to_words3(words, n, bits, _d.BLOCK_ROWS_CODEBOOK)
    out = _d.codebook_decode_reduce_3d(
        words3, levels.astype(jnp.float32), bits=bits, interpret=interpret)
    return out.reshape(-1)[:n]


@partial(jax.jit, static_argnames=("n", "bits", "interpret"))
def uniform_decode_rows(
    words: jax.Array, alphas: jax.Array, n: int, bits: int, *, interpret: bool | None = None
) -> jax.Array:
    """Fused unpack + uniform dequant, one (n,) row per peer (no reduce) —
    the all-gather phase-2 sites, where peer j's decode is output chunk j."""
    interpret = _use_interpret() if interpret is None else interpret
    words3 = _to_words3(words, n, bits, _d.BLOCK_ROWS)
    a2 = alphas.astype(jnp.float32).reshape(-1, 1)
    out = _d.uniform_decode_rows_3d(words3, a2, bits=bits, interpret=interpret)
    return out.reshape(words.shape[0], -1)[:, :n]


@partial(jax.jit, static_argnames=("n", "bits", "interpret"))
def codebook_decode_rows(
    words: jax.Array, levels: jax.Array, n: int, bits: int, *, interpret: bool | None = None
) -> jax.Array:
    """Fused unpack + codebook dequant, one (n,) row per peer (no reduce)."""
    interpret = _use_interpret() if interpret is None else interpret
    words3 = _to_words3(words, n, bits, _d.BLOCK_ROWS_CODEBOOK)
    out = _d.codebook_decode_rows_3d(
        words3, levels.astype(jnp.float32), bits=bits, interpret=interpret)
    return out.reshape(words.shape[0], -1)[:, :n]


class BucketStats(NamedTuple):
    """One-pass telemetry statistics of a flat gradient bucket."""

    counts: jax.Array    # (NUM_BINS,) log2-spaced |g| histogram counts
    log_sums: jax.Array  # (NUM_BINS,) per-bin sums of ln|g|
    g_max: jax.Array     # scalar max |g|
    g_sum: jax.Array     # scalar sum g
    g_sumsq: jax.Array   # scalar sum g²


@partial(jax.jit, static_argnames=("interpret",))
def bucket_stats(g: jax.Array, *, interpret: bool | None = None) -> BucketStats:
    """Fused histogram + Hill-sum + max/moments pass (``kernels.stats``).

    Replaces the full-sort quantile in the telemetry hot loop: everything
    the online power-law tail estimator needs comes out of one VMEM pass.
    """
    interpret = _use_interpret() if interpret is None else interpret
    g2, n = _to_2d(g.astype(jnp.float32))
    out = _s.bucket_stats_2d(g2, n, interpret=interpret)
    return BucketStats(counts=out[0], log_sums=out[1], g_max=out[2, 0],
                       g_sum=out[3, 0], g_sumsq=out[4, 0])


# ---------------------------------------------------------------------------
# Fused encode side (``kernels.encode_fused``): one-pass EF-correct→stats,
# and quantize→pack[→residual] without staging codes or owns in HBM.
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("interpret",))
def ef_correct_stats(
    g: jax.Array, e: jax.Array, *, interpret: bool | None = None
) -> tuple[jax.Array, BucketStats]:
    """One pass: ``c = g + e`` plus the full plan/telemetry statistics of c.

    Returns ``(corrected (n,) fp32, BucketStats)``.  Everything the plan
    consumes (counts, log-sums, max) is bit-identical to
    ``bucket_stats(g + e)`` — the same grid step as ``kernels.stats`` —
    without the extra HBM sweep; the EMA moment rows are plain reductions
    with ulp-level fusion discretion.
    """
    interpret = _use_interpret() if interpret is None else interpret
    g2, n = _to_2d(g.astype(jnp.float32))
    e2, _ = _to_2d(e.astype(jnp.float32))
    c2, out = _e.ef_correct_stats_2d(g2, e2, n, interpret=interpret)
    return c2.reshape(-1)[:n], BucketStats(
        counts=out[0], log_sums=out[1], g_max=out[2, 0],
        g_sum=out[3, 0], g_sumsq=out[4, 0])


def _packed_words(words2: jax.Array, n: int, bits: int) -> jax.Array:
    from repro.core.quantizers import packed_size

    return jax.lax.bitcast_convert_type(words2.reshape(-1), jnp.uint32)[: packed_size(n, bits)]


@partial(jax.jit, static_argnames=("bits", "interpret"))
def uniform_encode_pack(
    g: jax.Array, alpha: jax.Array, bits: int, key: jax.Array, *, interpret: bool | None = None
) -> jax.Array:
    """Fused truncate + uniform stochastic encode + bit-pack, words only.

    Returns the uint32 wire words (``packed_size(n, bits)``, bit-identical
    to ``pack_codes`` of the same codes); unlike ``uniform_encode_packed``
    the codes never reach HBM.
    """
    interpret = _use_interpret() if interpret is None else interpret
    g2, n = _to_2d(g.astype(jnp.float32))
    rand = jax.random.uniform(key, g2.shape, jnp.float32)
    words = _e.uniform_encode_pack_2d(g2, rand, alpha.astype(jnp.float32), n,
                                      bits=bits, interpret=interpret)
    return _packed_words(words, n, bits)


@partial(jax.jit, static_argnames=("bits", "interpret"))
def codebook_encode_pack(
    g: jax.Array, levels: jax.Array, bits: int, key: jax.Array, *, interpret: bool | None = None
) -> jax.Array:
    """Codebook variant of :func:`uniform_encode_pack` (words only)."""
    interpret = _use_interpret() if interpret is None else interpret
    g2, n = _to_2d(g.astype(jnp.float32))
    rand = jax.random.uniform(key, g2.shape, jnp.float32)
    words = _e.codebook_encode_pack_2d(g2, rand, levels.astype(jnp.float32), n,
                                       bits=bits, interpret=interpret)
    return _packed_words(words, n, bits)


@partial(jax.jit, static_argnames=("bits", "interpret"))
def uniform_encode_pack_residual(
    g: jax.Array, alpha: jax.Array, bits: int, key: jax.Array, *, interpret: bool | None = None
) -> tuple[jax.Array, jax.Array]:
    """Fused truncate + uniform encode + bit-pack + EF residual.

    Returns ``(words, residual)``: the uint32 wire words plus
    ``g − dequant(code)`` — the next error-feedback residual — computed in
    the same tile, so neither the codes nor the dequantized ``own`` tensor
    ever reach HBM.
    """
    interpret = _use_interpret() if interpret is None else interpret
    g2, n = _to_2d(g.astype(jnp.float32))
    rand = jax.random.uniform(key, g2.shape, jnp.float32)
    words, resid = _e.uniform_encode_pack_resid_2d(
        g2, rand, alpha.astype(jnp.float32), n, bits=bits, interpret=interpret)
    return _packed_words(words, n, bits), resid.reshape(-1)[:n]


@partial(jax.jit, static_argnames=("bits", "interpret"))
def codebook_encode_pack_residual(
    g: jax.Array, levels: jax.Array, bits: int, key: jax.Array, *, interpret: bool | None = None
) -> tuple[jax.Array, jax.Array]:
    """Codebook variant of :func:`uniform_encode_pack_residual`; the
    residual is an exact ``g − levels[code]`` (the dequant reuses the
    interval endpoint the stochastic rounding selected)."""
    interpret = _use_interpret() if interpret is None else interpret
    g2, n = _to_2d(g.astype(jnp.float32))
    rand = jax.random.uniform(key, g2.shape, jnp.float32)
    words, resid = _e.codebook_encode_pack_resid_2d(
        g2, rand, levels.astype(jnp.float32), n, bits=bits, interpret=interpret)
    return _packed_words(words, n, bits), resid.reshape(-1)[:n]


@partial(jax.jit, static_argnames=("interpret",))
def orthogonalize(p: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """Gram–Schmidt orthonormalization of a tall-skinny (rows, r) factor.

    The PowerSGD power-iteration step: pads to the (8k, 128) fp32 tile,
    runs the single-block ``kernels.orthogonalize`` kernel, slices the
    (rows, r) corner back out.  r must be ≤ 128 lanes.
    """
    from . import orthogonalize as _o

    interpret = _use_interpret() if interpret is None else interpret
    rows, r = p.shape
    rows_p = -(-rows // _o.SUBLANES) * _o.SUBLANES
    pp = jnp.pad(p.astype(jnp.float32), ((0, rows_p - rows), (0, _o.LANES - r)))
    out = _o.orthogonalize_2d(pp, r=r, interpret=interpret)
    return out[:rows, :r]
