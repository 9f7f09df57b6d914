"""Pure-jnp oracles for the quantization kernels.

These mirror ``repro.core.quantizers`` but take an explicit uniform-random
array (the kernels consume pre-generated random bits so the Pallas and
reference paths can be compared bit-exactly).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.core.quantizers import num_levels


def uniform_encode(g: jax.Array, alpha: jax.Array, bits: int, rand: jax.Array) -> jax.Array:
    """Fused truncate + uniform stochastic quantize.  codes in [0, s], uint8.

    code = floor(u) + 1[rand < frac(u)]  with  u = (clip(g, ±α) + α) · s/(2α).
    """
    s = num_levels(bits)
    scale = s / (2.0 * alpha)
    u = (jnp.clip(g, -alpha, alpha) + alpha) * scale
    k = jnp.clip(jnp.floor(u), 0, s - 1)
    frac = u - k
    code = k + (rand < frac).astype(u.dtype)
    return jnp.clip(code, 0, s).astype(jnp.uint8)


def uniform_decode(codes: jax.Array, alpha: jax.Array, bits: int) -> jax.Array:
    s = num_levels(bits)
    step = 2.0 * alpha / s
    return codes.astype(jnp.float32) * step - alpha


def codebook_encode(g: jax.Array, levels: jax.Array, rand: jax.Array) -> jax.Array:
    """Fused truncate + non-uniform stochastic quantize onto ``levels``.

    k = #{j in 1..s : g >= l_j} clipped to s-1;
    code = k + 1[rand · (l_{k+1} − l_k) < g − l_k]  (rand < pr without the
    division).  Matches quantizers.stochastic_encode given the same uniforms.
    """
    s = levels.shape[0] - 1
    alpha = levels[-1]
    gt = jnp.clip(g, -alpha, alpha)
    k = jnp.sum(gt[..., None] >= levels[1:][None, :], axis=-1)
    k = jnp.clip(k, 0, s - 1)
    lo = levels[k]
    hi = levels[k + 1]
    up = rand * jnp.maximum(hi - lo, 1e-12) < gt - lo
    return (k + up.astype(k.dtype)).astype(jnp.uint8)


def codebook_decode(codes: jax.Array, levels: jax.Array) -> jax.Array:
    return jnp.take(levels, codes.astype(jnp.int32))


# ---------------------------------------------------------------------------
# Fused encode-side oracles / fallbacks (``kernels.encode_fused``).
#
# The key-based functions mirror the ``ops`` wrappers' signatures exactly —
# same (rows, 128) padding, same uniform draw — so ``dist.sharded_codec``
# dispatches between the Pallas module and this one by name, and kernel vs
# fallback produce bit-identical wire words for the codebook methods (the
# uniform dequant inside the residual keeps the usual ulp-level FMA slack).
# All ops here are plain jnp and safe under shard_map tracing on the pinned
# toolchain.
# ---------------------------------------------------------------------------


def _flat_rand(g: jax.Array, key: jax.Array) -> tuple[jax.Array, jax.Array, int]:
    """Match the ops wrappers' RNG layout: pad to (rows, 128), draw there."""
    from .ops import _to_2d

    g2, n = _to_2d(g.astype(jnp.float32))
    rand = jax.random.uniform(key, g2.shape, jnp.float32)
    return g2, rand, n


def uniform_encode_pack(g: jax.Array, alpha: jax.Array, bits: int, key: jax.Array) -> jax.Array:
    """Sequential oracle of ``ops.uniform_encode_pack``: encode then pack."""
    from repro.core.quantizers import pack_codes

    g2, rand, n = _flat_rand(g, key)
    codes = uniform_encode(g2, alpha, bits, rand).reshape(-1)[:n]
    return pack_codes(codes, bits)


def _codebook_codes(flat: jax.Array, levels: jax.Array, rand: jax.Array) -> jax.Array:
    """Codebook stochastic codes via searchsorted + take.

    Bit-identical to the kernel's compare-count + select formulation
    (:func:`codebook_encode`): the interval index is the same exact integer
    either way, and ``take`` is the same exact lookup as the select chain —
    but a binary search beats s compares per element on CPU, which is what
    this fallback actually runs on.
    """
    levels = levels.astype(jnp.float32)
    s = levels.shape[0] - 1
    gt = jnp.clip(flat, -levels[s], levels[s])
    k = jnp.clip(jnp.searchsorted(levels, gt, side="right") - 1, 0, s - 1)
    lo = jnp.take(levels, k)
    hi = jnp.take(levels, k + 1)
    up = rand.reshape(-1)[: flat.size] * jnp.maximum(hi - lo, 1e-12) < gt - lo
    return (k + up.astype(k.dtype)).astype(jnp.uint8)


def codebook_encode_pack(g: jax.Array, levels: jax.Array, bits: int, key: jax.Array) -> jax.Array:
    """Sequential oracle of ``ops.codebook_encode_pack``."""
    from repro.core.quantizers import pack_codes

    g2, rand, n = _flat_rand(g, key)
    codes = _codebook_codes(g2.reshape(-1)[:n], levels, rand)
    return pack_codes(codes, bits)


def uniform_encode_pack_residual(
    g: jax.Array, alpha: jax.Array, bits: int, key: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Sequential oracle of ``ops.uniform_encode_pack_residual``."""
    from repro.core.quantizers import pack_codes

    s = num_levels(bits)
    g2, rand, n = _flat_rand(g, key)
    codes = uniform_encode(g2, alpha, bits, rand).reshape(-1)[:n]
    flat = g2.reshape(-1)[:n]
    alpha = alpha.astype(jnp.float32)
    resid = flat - (codes.astype(jnp.float32) * (2.0 * alpha / s) - alpha)
    return pack_codes(codes, bits), resid


def codebook_encode_pack_residual(
    g: jax.Array, levels: jax.Array, bits: int, key: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Sequential oracle of ``ops.codebook_encode_pack_residual``; the
    residual is the exact ``g − levels[code]``."""
    from repro.core.quantizers import pack_codes

    g2, rand, n = _flat_rand(g, key)
    flat = g2.reshape(-1)[:n]
    codes = _codebook_codes(flat, levels, rand)
    resid = flat - jnp.take(levels.astype(jnp.float32), codes.astype(jnp.int32))
    return pack_codes(codes, bits), resid


def bucket_stats_scatter(g: jax.Array):
    """O(n) scatter-add bucket statistics — the shard_map-safe jnp fallback.

    Counts and max are identical to the fused kernel (integer adds / exact
    max); the float ln/moment sums may differ in the last bits (reduction
    order), which neither the EMA telemetry nor the histogram plan cares
    about — the bit-exact contract is pinned kernel ↔ :func:`bucket_stats`.
    Returns ``(counts, log_sums, g_max, g_sum, g_sumsq)``.
    """
    from . import stats as S

    flat = g.reshape(-1).astype(jnp.float32)
    gabs = jnp.abs(flat)
    lnab = jnp.log(jnp.maximum(gabs, 1e-30))
    b = S.bin_index(gabs)
    # repro: allow REPRO104 (counts sum exact 1.0s — order-free; see above)
    counts = jnp.zeros((S.NUM_BINS,), jnp.float32).at[b].add(1.0)
    # repro: allow REPRO104 (last-bit slack documented in the EMA contract)
    log_sums = jnp.zeros((S.NUM_BINS,), jnp.float32).at[b].add(lnab)
    return counts, log_sums, jnp.max(gabs), jnp.sum(flat), jnp.sum(flat * flat)


def ef_correct_stats(g: jax.Array, e: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Blockwise oracle of ``ops.ef_correct_stats``: ``c = g + e`` plus the
    (STATS_ROWS, NUM_BINS) stats tile of c, walking the same block/merge
    order as the fused kernel (bit-exact in interpret mode)."""
    c = g.reshape(-1).astype(jnp.float32) + e.reshape(-1).astype(jnp.float32)
    return c, bucket_stats(c)


# ---------------------------------------------------------------------------
# Fused decode oracles (``kernels.decode``).
#
# The decode-reduce kernels fold peers into the output tile *sequentially*
# (grid peer axis innermost) and divide by n at the last peer; per element
# that is exactly ``(((v_0 + v_1) + ...) + v_{n-1}) / n`` regardless of the
# row blocking, so the oracle is a plain per-peer accumulation loop.  All
# remaining ops are element-wise (or exact table lookups), hence kernel and
# oracle agree bit-for-bit in interpret mode — and these same functions
# double as the shard_map-safe jnp fallback of ``dist.sharded_codec``, which
# streams one peer at a time instead of materializing the (n_peers, m)
# unpacked code tensor.
# ---------------------------------------------------------------------------


def uniform_decode_reduce(words: jax.Array, alphas: jax.Array, n: int, bits: int) -> jax.Array:
    """(peers, packed_words) uint32 + (peers,) alphas -> (n,) fp32 peer mean."""
    from repro.core.quantizers import unpack_codes

    s = num_levels(bits)
    p = words.shape[0]
    acc = jnp.zeros((n,), jnp.float32)
    for j in range(p):
        codes = unpack_codes(words[j], n, bits).astype(jnp.float32)
        alpha = alphas[j].astype(jnp.float32)
        acc = acc + (codes * (2.0 * alpha / s) - alpha)
    return acc / p


def codebook_decode_reduce(words: jax.Array, levels: jax.Array, n: int, bits: int) -> jax.Array:
    """(peers, packed_words) + (peers, s+1) codebooks -> (n,) fp32 peer mean."""
    from repro.core.quantizers import unpack_codes

    p = words.shape[0]
    acc = jnp.zeros((n,), jnp.float32)
    for j in range(p):
        codes = unpack_codes(words[j], n, bits)
        acc = acc + jnp.take(levels[j].astype(jnp.float32), codes.astype(jnp.int32))
    return acc / p


def uniform_decode_rows(words: jax.Array, alphas: jax.Array, n: int, bits: int) -> jax.Array:
    """(peers, packed_words) uint32 + (peers,) alphas -> (peers, n) fp32."""
    from repro.core.quantizers import unpack_codes

    s = num_levels(bits)
    rows = []
    for j in range(words.shape[0]):
        codes = unpack_codes(words[j], n, bits).astype(jnp.float32)
        alpha = alphas[j].astype(jnp.float32)
        rows.append(codes * (2.0 * alpha / s) - alpha)
    return jnp.stack(rows)


def codebook_decode_rows(words: jax.Array, levels: jax.Array, n: int, bits: int) -> jax.Array:
    """(peers, packed_words) + (peers, s+1) codebooks -> (peers, n) fp32."""
    from repro.core.quantizers import unpack_codes

    rows = []
    for j in range(words.shape[0]):
        codes = unpack_codes(words[j], n, bits)
        rows.append(jnp.take(levels[j].astype(jnp.float32), codes.astype(jnp.int32)))
    return jnp.stack(rows)


def bucket_stats(g: jax.Array) -> jax.Array:
    """Blockwise jnp oracle for ``stats.bucket_stats_2d``.

    Scans the padded (rows, 128) layout in the kernel's BLOCK_ROWS grid
    order, runs the kernel's own block body (``stats._block_stats``) on each
    block and folds the partials with its add-or-maximum merge
    (``stats._merge``) — so kernel and oracle agree bit-for-bit (interpret
    mode executes identical ops in identical order).  A scan, not an
    unrolled loop: the program stays one block body at any bucket size.
    """
    from . import stats as S

    flat = g.reshape(-1).astype(jnp.float32)
    n = flat.size
    rows = -(-n // S.LANES)
    blocks = -(-rows // S.BLOCK_ROWS)
    m = S.BLOCK_ROWS * S.LANES
    padded = jnp.pad(flat, (0, blocks * m - n)).reshape(blocks, S.BLOCK_ROWS, S.LANES)
    idx = jnp.arange(m, dtype=jnp.int32).reshape(S.BLOCK_ROWS, S.LANES)

    def fold(acc, xs):
        i, gb = xs
        return S._merge(acc, S._block_stats(gb, idx + i * m < n)), None

    acc0 = jnp.zeros((S.STATS_ROWS, S.NUM_BINS), jnp.float32)
    acc, _ = jax.lax.scan(fold, acc0, (jnp.arange(blocks, dtype=jnp.int32), padded))
    return acc


def orthogonalize(p: jax.Array) -> jax.Array:
    """Oracle for ``kernels.orthogonalize``: the identical padded-tile
    Gram–Schmidt loop (shared ``_gs_padded`` body ⇒ bit-exact vs the kernel
    in interpret mode), shard_map-safe jnp."""
    from .orthogonalize import LANES as _GS_LANES, SUBLANES as _GS_SUB, _gs_padded

    rows, r = p.shape
    rows_p = -(-rows // _GS_SUB) * _GS_SUB
    pp = jnp.pad(p.astype(jnp.float32), ((0, rows_p - rows), (0, _GS_LANES - r)))
    return _gs_padded(pp, r)[:rows, :r]
