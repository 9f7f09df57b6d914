"""Sharded wire codec: quantized gradient collectives inside shard_map.

These are the per-device bodies of the compressed synchronization modes
(paper Alg. 1 deployed as collectives).  Every function runs *inside* a
shard_map region and sees only the local shard of the gradient:

- :func:`two_phase_reduce_scatter_sharded` — phase 1 of two-phase sync:
  quantize each of the n peer-chunks of the local tensor, exchange codes
  all-to-all, dequantize and average.  Each peer ends with its own chunk of
  the mean (a compressed reduce-scatter).
- :func:`two_phase_mean` — both phases: reduce-scatter, then re-quantize the
  mean chunk and all-gather it back (each direction ships ~bits/32 of the
  fp32 payload).
- :func:`faithful_ring_mean` — the Error-Compensated-QSGD-style worker
  exchange: each peer's *full* tensor is quantized exactly once, every peer
  decodes the same n codewords, so all peers agree bit-for-bit on the mean
  and the per-peer quantizers stay unbiased.
- :func:`pack_dim` / :func:`unpack_dim` — the uint32 wire format of
  ``core.quantizers`` applied along an arbitrary axis, so code tensors can be
  exchanged without first flattening away the peer axis.

The bucketed fast path (:func:`bucketed_two_phase_mean`,
:func:`bucketed_faithful_ring_mean`, :func:`bucketed_hierarchical_mean`)
takes a *list* of coalesced fp32 buckets (``core.compressors.plan_buckets``),
plans one codebook per bucket, and fuses every bucket's packed codes and
bitcast codebook into a single wire tensor so each phase issues exactly one
collective regardless of bucket or leaf count.  An optional per-bucket
``bits`` plan (``repro.adaptive``) gives each bucket its own static wire
width inside the same fused tensor — offsets stay trace-time static, and the
collective count does not change.  Each function also returns the peer's own
per-bucket **EF residual** ``corrected − C(corrected)``, produced inside the
fused encode.

Per-chunk codebooks ride along with the codes as (levels, alpha) pairs —
``wire_bytes`` in ``core.compressors`` accounts for them.

The bucketed collective bodies are codec-agnostic: every local half of the
sync — planning, the fused encode-pack(-residual), the fused
decode(-reduce), and the static wire/state geometry — goes through the
:mod:`repro.core.codecs` registry (``get_codec(cfg.method)``), and the
bodies branch only on the codec *interface* (``chunkable``, aux state),
never on method strings.  The quantizer family's hooks preserve the
pre-registry wire layout byte-for-byte (codes then bitcast codebook per
bucket), so existing methods stay bit-identical; non-chunkable codecs
(``powersgd`` low-rank factors) ride the same fused tensors by tiling
their full wire into every two-phase row (an embedded all-gather) with a
zero-width phase-2 contribution.  Codec-opaque per-bucket state (the
warm-started PowerSGD Q) flows in via ``aux=`` and comes back concatenated
onto the EF residual row (``concat(resid, aux_new)``) — quantizers carry
no aux and return the residual rows unchanged.

The kernel/jnp dispatch helpers (:func:`encode_pack`,
:func:`encode_pack_residual`, :func:`decode_reduce`, :func:`decode_rows`,
``_plan_bucket``, …) live in ``core.codecs`` and are re-exported here for
the reference replay and the per-leaf codec: ``use_pallas`` selects the
``kernels.encode_fused``/``kernels.decode`` Pallas kernels (one VMEM pass;
codes and the dequantized ``own`` tensor never reach HBM), otherwise the
key-compatible sequential oracles in ``kernels.ref`` run the same op
sequence (bit-identical wire words; uniform dequant keeps ulp-level FMA
slack) and stay shard_map-safe.

Peer RNG independence: every encode folds ``compat.flat_axis_index`` of the
collective's own axes into the key.  The paper's Lemma 2 (mean error
concentrating as 1/n across workers) assumes independent stochastic rounding
per peer; a verbatim replicated key correlates the draws and the mean never
concentrates (``tests/test_rng_independence.py`` pins this).  Folding the
index of the *collective's* axes only — not every mesh axis — keeps the
hierarchical mode's replication invariant: peers that must produce identical
bytes (same pod, different data rank in the cross-pod exchange) still share
a stream.
"""
from __future__ import annotations

from collections.abc import Sequence

import jax
import jax.numpy as jnp

# Local codec halves live in core.codecs; re-exported names keep the
# long-standing import surface (reference replay, benches, tests) stable.
from repro.core.codecs import (  # noqa: F401  (re-exports)
    _UNIFORM_DECODE,
    _bucket_stats,
    _decode_dispatch,
    _encode_dispatch,
    _levels_from_wire,
    _levels_to_wire,
    _plan_bucket,
    bucket_cfgs as _registry_bucket_cfgs,
    bucket_state_sizes,
    decode_reduce,
    decode_rows,
    encode_pack,
    encode_pack_residual,
    get_codec,
)
from repro.core.compressors import CompressorConfig, plan
from repro.core.quantizers import (
    QuantMeta,
    pack_codes,
    unpack_codes,
)

from . import compat


# ---------------------------------------------------------------------------
# Bit packing along an arbitrary axis
# ---------------------------------------------------------------------------


def pack_dim(codes: jax.Array, dim: int, bits: int) -> jax.Array:
    """Bit-pack uint8 codes into uint32 words along axis ``dim``.

    Shape change: ``n -> ceil(n/32) * bits`` on that axis; all other axes
    are preserved (the packing is independent per lane).
    """
    moved = jnp.moveaxis(codes, dim, -1)
    lead = moved.shape[:-1]
    flat = moved.reshape(-1, moved.shape[-1])
    words = jax.vmap(lambda row: pack_codes(row, bits))(flat)
    return jnp.moveaxis(words.reshape(lead + (words.shape[-1],)), -1, dim)


def unpack_dim(words: jax.Array, dim: int, bits: int, n: int | None = None) -> jax.Array:
    """Inverse of :func:`pack_dim`; ``n`` recovers a non-multiple-of-32 axis."""
    moved = jnp.moveaxis(words, dim, -1)
    lead = moved.shape[:-1]
    if n is None:
        n = (moved.shape[-1] // bits) * 32
    flat = moved.reshape(-1, moved.shape[-1])
    codes = jax.vmap(lambda row: unpack_codes(row, n, bits))(flat)
    return jnp.moveaxis(codes.reshape(lead + (n,)), -1, dim)


# ---------------------------------------------------------------------------
# Local encode/decode helpers (flat fp32 <-> packed words + codebook)
# ---------------------------------------------------------------------------


def _peer_key(key: jax.Array, axis_name) -> jax.Array:
    """Decorrelate the replicated step key across the peers of a collective.

    Inside the fully-manual shard_map every peer receives the same key; the
    quantizer's unbiasedness across peers needs independent uniforms, so the
    peer's linear index over the collective's axes is folded in.
    """
    return jax.random.fold_in(key, compat.flat_axis_index(axis_name))


def _plan_encode_rows(cfg: CompressorConfig, rows: jax.Array, key: jax.Array,
                      use_pallas: bool) -> tuple[jax.Array, QuantMeta]:
    """Per-row plan + fused encode-pack.  rows: (k, m) fp32 -> ((k, words), metas).

    The per-leaf two-phase site: each peer chunk keeps the sort-based
    ``plan`` (the raw-tensor fallback fit), but the encode routes through
    the fused :func:`encode_pack` dispatch, so no unpacked code row is
    staged between encode and pack.
    """
    k = rows.shape[0]
    metas = jax.vmap(lambda r: plan(cfg, r))(rows)
    keys = jax.random.split(key, k)
    return jax.vmap(lambda r, m_lv, m_a, kk: encode_pack(
        cfg, r, QuantMeta(levels=m_lv, alpha=m_a), kk, use_pallas))(
        rows, metas.levels, metas.alpha, keys), metas


# ---------------------------------------------------------------------------
# Collective codecs
# ---------------------------------------------------------------------------


def two_phase_reduce_scatter_sharded(
    cfg: CompressorConfig,
    g: jax.Array,
    dim: int,
    axis_name,
    key: jax.Array,
    use_pallas: bool = False,
) -> jax.Array:
    """Compressed reduce-scatter: returns this peer's chunk of the peer-mean.

    The local tensor is split into n equal chunks along ``dim`` (n = size of
    ``axis_name``); chunk j is quantized with its own codebook and shipped to
    peer j; each peer dequantizes the n received codewords and averages.

"""
    n = compat.axis_size(axis_name)
    if n == 1:
        return g
    if g.shape[dim] % n:
        raise ValueError(f"dim {dim} of shape {g.shape} not divisible by axis size {n}")
    key = _peer_key(key, axis_name)

    chunk_shape = g.shape[:dim] + (g.shape[dim] // n,) + g.shape[dim + 1:]
    parts = jnp.moveaxis(g, dim, 0).reshape(n, g.shape[dim] // n, -1)
    flat = parts.reshape(n, -1).astype(jnp.float32)                  # (n, m)
    m = flat.shape[1]

    words, metas = _plan_encode_rows(cfg, flat, key, use_pallas)
    recv_words = compat.all_to_all_rows(words, axis_name)            # (n, w)
    recv_levels = compat.all_to_all_rows(metas.levels, axis_name)
    mean_flat = decode_reduce(cfg, recv_words, recv_levels, m, use_pallas)
    return jnp.moveaxis(mean_flat.reshape((chunk_shape[dim],) + g.shape[:dim] + g.shape[dim + 1:]),
                        0, dim)


def two_phase_mean(
    cfg: CompressorConfig,
    g: jax.Array,
    axis_name,
    key: jax.Array,
    use_pallas: bool = False,
) -> jax.Array:
    """Full two-phase compressed mean: reduce-scatter then all-gather.

    Both phases move quantized chunks, so per-device wire cost is
    ~2 · bits/32 of the fp32 all-reduce (see ``collectives.wire_bytes_per_device``).
    """
    n = compat.axis_size(axis_name)
    if n == 1:
        return g
    k1, k2 = jax.random.split(_peer_key(key, axis_name))

    flat = g.reshape(-1).astype(jnp.float32)
    pad = (-flat.size) % n
    padded = jnp.pad(flat, (0, pad))
    chunk = two_phase_reduce_scatter_sharded(cfg, padded, 0, axis_name, k1, use_pallas)

    # Phase 2: broadcast this peer's mean chunk, freshly quantized.
    meta2 = plan(cfg, chunk)
    words2 = encode_pack(cfg, chunk, meta2, k2, use_pallas)
    all_words = compat.all_gather_stacked(words2, axis_name)             # (n, w)
    all_levels = compat.all_gather_stacked(meta2.levels, axis_name)
    full = decode_rows(cfg, all_words, all_levels, chunk.size, use_pallas).reshape(-1)
    return full[: flat.size].reshape(g.shape).astype(g.dtype)


def faithful_ring_mean(
    cfg: CompressorConfig,
    g: jax.Array,
    axis_name,
    key: jax.Array,
    use_pallas: bool = False,
) -> jax.Array:
    """Unbiased ring mean: each peer's full tensor is quantized exactly once.

    All peers decode the same n codewords, so the result is bitwise identical
    everywhere and E[result] is the true mean of the peers' local tensors
    (the quantizer is unbiased per element, per peer).
    """
    n = compat.axis_size(axis_name)
    flat = g.reshape(-1).astype(jnp.float32)
    meta = plan(cfg, flat)
    words = encode_pack(cfg, flat, meta, _peer_key(key, axis_name) if n > 1 else key,
                        use_pallas)
    if n == 1:
        own = decode_reduce(cfg, words[None], meta.levels[None], flat.size, use_pallas)
        return own.reshape(g.shape).astype(g.dtype)
    all_words = compat.all_gather_stacked(words, axis_name)              # (n, w)
    all_levels = compat.all_gather_stacked(meta.levels, axis_name)
    mean_flat = decode_reduce(cfg, all_words, all_levels, flat.size, use_pallas)
    return mean_flat.reshape(g.shape).astype(g.dtype)


# ---------------------------------------------------------------------------
# Bucketed fast path: one fused wire tensor per phase for a whole bucket list
# ---------------------------------------------------------------------------


def _bucket_cfgs(
    cfg: CompressorConfig, n_buckets: int, bits: Sequence | None
) -> list[CompressorConfig]:
    """Per-bucket compressor configs for a (possibly heterogeneous) plan.

    Entries may be ints (bit widths), ``("method", value)`` pairs, or full
    configs — see ``core.codecs.bucket_cfgs``.  The plan is trace-time
    Python, so bucket offsets in the fused wire tensor stay static.
    """
    return _registry_bucket_cfgs(cfg, n_buckets, bits)


def _state_row(resid: jax.Array, aux_new) -> jax.Array:
    """One bucket's EF/state row: the residual plus any codec aux tail."""
    return resid if aux_new is None else jnp.concatenate([resid, aux_new])


def _bucket_aux(aux: list | None, b: int):
    return aux[b] if aux is not None else None


# Elastic partial participation (``repro.elastic``): each bucketed body
# optionally takes ``live``, a replicated (n,) float32 0/1 mask over the
# collective's peers.  The semantics, identical across modes:
#
# - a dropped peer's encode still runs (the straggler-timeout contract: the
#   codec path is side-effect-free), but its *wire* is zeroed before the
#   collective — a zeroed row decodes to exactly 0.0 for every registered
#   codec (uniform: α=0 ⇒ code·2α/s−α=0; codebook: all-zero levels look up
#   0; low-rank: P=0,Q=0 ⇒ PQᵀ=0; fp16: bits 0 ⇒ 0.0) — so the decode-
#   reduce mean is Σ_live/n and one multiply by ``n / max(n_live, 1)``
#   renormalizes it to the live-peer mean;
# - the dropped peer's EF row keeps the whole corrected bucket
#   (``e ← g + e``, nothing transmitted), so the residual is recovered —
#   not lost — when the peer rejoins;
# - liveness itself is a replicated pure function of ``(seed, step)``
#   (``elastic.schedule.live_mask``) — no mask collective, so the traced
#   collective count per mode is unchanged (REPRO101 budgets hold).
#
# ``live=None`` (the default) skips every masking op: elastic-off graphs
# stay byte-identical to the pre-elastic codec.


def _self_live(live, axis_name, n: int):
    """This peer's own liveness scalar from the replicated (n,) mask."""
    if live is None:
        return None
    return live[compat.flat_axis_index(axis_name)] if n > 1 else live[0]


def _live_scale(live: jax.Array, n: int) -> jax.Array:
    """The ``n / max(n_live, 1)`` renormalization of a zero-filled mean."""
    return jnp.float32(n) / jnp.maximum(jnp.sum(live), jnp.float32(1.0))


def _mask_wire(wire: jax.Array, self_live) -> jax.Array:
    """Zero a dropped peer's wire words (uint32 select; see above)."""
    if self_live is None:
        return wire
    return jnp.where(self_live > 0, wire, jnp.zeros_like(wire))


def _mask_resid(resid: jax.Array, flat: jax.Array, self_live) -> jax.Array:
    """Dropped peers keep the whole corrected bucket as their EF residual."""
    if self_live is None:
        return resid
    return jnp.where(self_live > 0, resid, flat)


def bucketed_faithful_ring_mean(
    cfg: CompressorConfig,
    buckets: list,
    axis_name,
    key: jax.Array,
    use_pallas: bool = False,
    bits: Sequence | None = None,
    stats: list | None = None,
    aux: list | None = None,
    live: jax.Array | None = None,
) -> tuple[list, list]:
    """Faithful ring mean over a bucket list with ONE all-gather total.

    Each bucket is encoded once by its registered codec — planned from the
    one-pass ``stats`` tuples (the fused EF-correct→stats pass; computed
    inline when None) — and all buckets' wire vectors are concatenated into
    a single uint32 tensor sliced back by ``codec.wire_words`` (static)
    offsets.  ``bits`` optionally assigns per-bucket plan entries (bit
    widths or ``("method", value)`` pairs).  ``aux`` threads codec-opaque
    warm state in; returns ``(mean_buckets, state_rows)`` with row ``b`` =
    ``concat(resid, aux_new)`` (just the EF residual for aux-free codecs).
    """
    n = compat.axis_size(axis_name)
    if n > 1:
        key = _peer_key(key, axis_name)
    self_live = _self_live(live, axis_name, n)
    scale = None if live is None else _live_scale(live, n)
    cfgs = _bucket_cfgs(cfg, len(buckets), bits)
    codecs = [get_codec(c.method) for c in cfgs]
    parts, states, sizes = [], [], []
    # obs.* named scopes label the encode/collective/decode phases in
    # profiler traces (see repro.obs.trace); they change no numerics.
    with jax.named_scope("obs.encode"):
        for b, g in enumerate(buckets):
            flat = g.reshape(-1).astype(jnp.float32)
            pln = codecs[b].plan(cfgs[b], flat, stats[b] if stats is not None else None,
                                 use_pallas)
            wire, resid, aux_new = codecs[b].encode_residual(
                cfgs[b], flat, pln, jax.random.fold_in(key, b), use_pallas,
                aux=_bucket_aux(aux, b))
            states.append(_state_row(_mask_resid(resid, flat, self_live), aux_new))
            parts.append(wire)
            sizes.append(flat.size)
    if n == 1:
        # Degenerate single-peer ring: the "mean" is this peer's own
        # dequantized transmission, recovered through the same fused decode
        # every multi-peer site uses (exact codebook lookup).
        with jax.named_scope("obs.decode"):
            means = [
                codecs[b].decode_reduce(cfgs[b], _mask_wire(parts[b], self_live)[None],
                                        m, use_pallas)
                for b, m in enumerate(sizes)
            ]
            if scale is not None:
                means = [m * scale for m in means]
        return means, states
    with jax.named_scope("obs.collective"):
        wire = _mask_wire(jnp.concatenate(parts), self_live)
        rows = compat.all_gather_stacked(wire, axis_name)                # (n, T)
    with jax.named_scope("obs.decode"):
        means, off = [], 0
        for b, m in enumerate(sizes):
            w = codecs[b].wire_words(cfgs[b], m)
            mean_b = codecs[b].decode_reduce(cfgs[b], rows[:, off:off + w], m,
                                             use_pallas)
            means.append(mean_b if scale is None else mean_b * scale)
            off += w
    return means, states


def bucketed_two_phase_mean(
    cfg: CompressorConfig,
    buckets: list,
    axis_name,
    key: jax.Array,
    use_pallas: bool = False,
    bits: Sequence | None = None,
    stats: list | None = None,
    aux: list | None = None,
    live: jax.Array | None = None,
) -> tuple[list, list]:
    """Two-phase compressed mean over a bucket list: ONE all-to-all (phase 1)
    plus ONE all-gather (phase 2) for every bucket together.

    Chunkable codecs ship one plan per bucket shared by its n peer-chunk
    rows (``codec.encode_chunks``); non-chunkable codecs tile their full
    wire into every row and finish in phase 1 (see ``core.codecs``).
    Phase-1 plans come from the one-pass ``stats``; the phase-2 mean-chunk
    re-encode computes its own inline.  ``bits`` optionally assigns
    per-bucket plan entries (both phases use the bucket's width).  ``aux``
    threads codec warm state; returns ``(mean_buckets, state_rows)`` as in
    :func:`bucketed_faithful_ring_mean`.

    Elastic note: dropout applies to *gradient contributions*, not to
    transport — chunk ownership is structural, so phase 2 runs unmasked
    (every peer, live or dropped, relays its chunk of the already-
    renormalized live mean; a dropped peer only zeroes its phase-1 rows).
    """
    n = compat.axis_size(axis_name)
    flats = [g.reshape(-1).astype(jnp.float32) for g in buckets]
    cfgs = _bucket_cfgs(cfg, len(buckets), bits)
    codecs = [get_codec(c.method) for c in cfgs]
    self_live = _self_live(live, axis_name, n)
    if n == 1:
        # Size-1 axis: nothing is transmitted (identity mean), so the EF
        # residual of this stage is exactly zero; codec aux passes through.
        # A dropped solo member keeps the whole bucket as residual — the
        # hierarchical caller excludes it at the cross-pod stage (pod_live).
        return flats, [
            _state_row(jnp.zeros_like(f) if self_live is None
                       else jnp.where(self_live > 0, jnp.zeros_like(f), f),
                       _bucket_aux(aux, b))
            for b, f in enumerate(flats)]
    scale = None if live is None else _live_scale(live, n)
    k1, k2 = jax.random.split(_peer_key(key, axis_name))
    parts, states, widths = [], [], []
    with jax.named_scope("obs.encode"):
        for b, flat in enumerate(flats):
            pln = codecs[b].plan(cfgs[b], flat, stats[b] if stats is not None else None,
                                 use_pallas)
            kb = jax.random.fold_in(k1, b)
            if codecs[b].chunkable:
                rows_b, resid = codecs[b].encode_chunks(cfgs[b], flat, pln, kb, n,
                                                        use_pallas)
                aux_new = None
            else:
                # Non-chunkable wire (low-rank factors): tile the full wire into
                # every all-to-all row — an embedded all-gather riding the same
                # fused tensor, decoded entirely in phase 1.
                wire_b, resid, aux_new = codecs[b].encode_residual(
                    cfgs[b], flat, pln, kb, use_pallas, aux=_bucket_aux(aux, b))
                rows_b = jnp.tile(wire_b[None], (n, 1))
            states.append(_state_row(_mask_resid(resid, flat, self_live), aux_new))
            parts.append(rows_b)
            widths.append(rows_b.shape[1])
    with jax.named_scope("obs.collective"):
        wire = _mask_wire(jnp.concatenate(parts, axis=1), self_live)     # (n, T1)
        recv = compat.all_to_all_rows(wire, axis_name)                   # (n, T1)

    # Phase 1 decode: this peer's chunk of each chunkable bucket's mean;
    # non-chunkable buckets decode their full mean here (every peer holds
    # every peer's tiled wire after the all-to-all).
    with jax.named_scope("obs.decode"):
        mean_chunks, full_means, off = [], {}, 0
        for b, flat in enumerate(flats):
            rows_b = recv[:, off:off + widths[b]]
            off += widths[b]
            if codecs[b].chunkable:
                mc = codecs[b].chunk_elems(cfgs[b], flat.size, n)
                ch = codecs[b].decode_reduce(cfgs[b], rows_b, mc, use_pallas)
                mean_chunks.append(ch if scale is None else ch * scale)
            else:
                fm = codecs[b].decode_reduce(cfgs[b], rows_b, flat.size, use_pallas)
                full_means[b] = fm if scale is None else fm * scale
                mean_chunks.append(None)

    # Phase 2: re-encode the mean chunks, one fused all-gather back (skipped
    # entirely when no bucket chunks — then phase 1 already produced every
    # full mean).
    parts2, widths2 = [], []
    with jax.named_scope("obs.encode"):
        for b, ch in enumerate(mean_chunks):
            if ch is None:
                widths2.append(0)
                continue
            pln2 = codecs[b].plan(cfgs[b], ch, None, use_pallas)
            parts2.append(codecs[b].encode(cfgs[b], ch, pln2, jax.random.fold_in(k2, b),
                                           use_pallas))
            widths2.append(parts2[-1].shape[0])
    rows2 = None
    if parts2:
        with jax.named_scope("obs.collective"):
            rows2 = compat.all_gather_stacked(jnp.concatenate(parts2), axis_name)  # (n, T2)
    with jax.named_scope("obs.decode"):
        means, off = [], 0
        for b, flat in enumerate(flats):
            if mean_chunks[b] is None:
                means.append(full_means[b])
                continue
            mc = mean_chunks[b].size
            vals = codecs[b].decode_rows(cfgs[b], rows2[:, off:off + widths2[b]], mc,
                                         use_pallas)                     # row j = chunk j
            off += widths2[b]
            means.append(vals.reshape(n * mc)[: flat.size])
    return means, states


def bucketed_hierarchical_mean(
    cfg: CompressorConfig,
    buckets: list,
    dp: tuple,
    key: jax.Array,
    use_pallas: bool = False,
    bits: Sequence | None = None,
    stats: list | None = None,
    aux: list | None = None,
    live: jax.Array | None = None,
) -> tuple[list, list]:
    """Two-phase inside the innermost data axis, faithful exchange of the
    pod means across the leading pod axes — 3 collectives total.

    The intra-pod phase folds the *full* dp index into its key: same-data-rank
    workers in different pods encode different data, so nothing forces them to
    share a stream, and leaving them correlated caps the phase-1 error at
    1/sqrt(data) instead of 1/sqrt(n).  (The cross-pod faithful stage keeps
    per-pod streams — members of one pod must emit identical bytes.)
    The EF state (residual + codec aux) comes from the intra-pod stage (what
    this peer actually transmitted); the cross-pod stage plans from inline
    pod-mean stats and runs aux-cold (its encode is of a pod *mean*, not
    this peer's gradient, so warm factors would be the wrong subspace).
    """
    pod_axes, data_axis = dp[:-1], dp[-1:]
    k1, k2 = jax.random.split(key)
    k1 = _peer_key(k1, dp)
    live_sub = pod_live = None
    if live is not None:
        # Renormalization is per stage: the intra-pod mean over the pod's
        # live members (this pod's row of the (n_pod, nd) mask), the
        # cross-pod mean over live pods (a pod is live iff any member is).
        # Pods weigh equally regardless of live count — the same mean-of-
        # pod-means composition as full participation.
        n_pod = compat.axis_size(pod_axes)
        nd = compat.axis_size(data_axis)
        mat = live.reshape(n_pod, nd)
        live_sub = mat[compat.flat_axis_index(pod_axes)]
        pod_live = jnp.max(mat, axis=1)
    means, states = bucketed_two_phase_mean(cfg, buckets, data_axis, k1, use_pallas,
                                            bits, stats, aux, live_sub)
    means, _ = bucketed_faithful_ring_mean(cfg, means, pod_axes, k2, use_pallas, bits,
                                           live=pod_live)
    return means, states
