"""Codec-plugin registry: the compressor interface the bucketed collectives
drive.

Every compressor family registers one :class:`Codec` per method name.  A
codec owns the *local* halves of the sync — planning, the fused
encode→pack(→residual) pass, the fused decode(→reduce) pass, and the static
wire/state geometry — while ``dist.sharded_codec`` owns only the collective
wiring (all-gather / all-to-all, key folding, fused-tensor offsets).  The
collective bodies never inspect ``cfg.method``; they branch exclusively on
the interface (``chunkable``, ``state_extra``), which is what lets a new
family (fp8, sparsification, …) plug in without touching collective code.

Wire contract
    A codec's transmission for an ``n``-element flat bucket is a single 1-D
    uint32 vector of exactly ``wire_words(cfg, n)`` words (trace-time
    static): everything a *peer* needs to decode — packed codes and the
    bitcast per-bucket codebook for the quantizers, the bitcast P/Q factors
    for ``powersgd``.  ``decode_reduce`` consumes the (peers, wire_words)
    gathered rows and returns the (n,) fp32 peer mean; ``decode_rows``
    returns one decoded row per peer (the all-gather phase-2 shape).

Chunking contract (``chunkable``)
    Chunkable codecs additionally split a bucket into ``n_chunks`` peer
    chunks for the two-phase reduce-scatter: ``encode_chunks`` returns
    (n_chunks, chunk_wire_words) rows such that row ``j`` decodes to peer
    ``j``'s chunk of ``chunk_elems`` elements.  Non-chunkable codecs (the
    low-rank family — factor matrices do not slice element-wise) are carried
    through the same all-to-all by tiling their full wire into every row
    (an embedded all-gather), decoded fully in phase 1, with a zero-width
    phase-2 contribution.

State contract (``state_extra``)
    The bucket-resident EF/state row for an ``n``-element bucket is
    ``concat(resid, aux)`` of length ``n + state_extra(cfg, n)``.  The
    ``aux`` tail is opaque codec memory carried step-to-step (``powersgd``:
    the warm-started Q factor); quantizers have none, keeping the PR-5
    layout — and all existing graphs — byte-identical.

The quantizer family (``core.compressors.METHODS``) is registered at import;
``powersgd`` registers lazily from ``core.lowrank`` on first registry miss.
"""
from __future__ import annotations

from collections.abc import Sequence

import jax
import jax.numpy as jnp

from .compressors import METHODS, CompressorConfig, plan_from_stats, wire_bytes
from .quantizers import QuantMeta, packed_size


# ---------------------------------------------------------------------------
# Local encode/decode dispatch (kernel vs jnp-oracle), shared by all sites
# ---------------------------------------------------------------------------

# Methods whose codebook is the uniform linspace: the fused kernels encode/
# dequantize them straight from α (code · 2α/s − α) instead of a table walk.
_UNIFORM_DECODE = ("qsgd", "tqsgd", "dsgd")


def _encode_dispatch(cfg: CompressorConfig, op: str, flat: jax.Array, meta: QuantMeta,
                     key: jax.Array, use_pallas: bool):
    """Kernel/jnp dispatch for the fused encode ops (mirror of
    ``_decode_dispatch``): ``use_pallas`` selects ``kernels.encode_fused``
    via the ``kernels.ops`` wrappers, else the key-compatible sequential
    oracles in ``kernels.ref`` (shard_map-safe, bit-identical words)."""
    if use_pallas:
        from repro.kernels import ops as mod
    else:
        from repro.kernels import ref as mod
    if cfg.method in _UNIFORM_DECODE:
        return getattr(mod, f"uniform_{op}")(flat, meta.alpha, cfg.bits, key)
    return getattr(mod, f"codebook_{op}")(flat, meta.levels, cfg.bits, key)


def encode_pack(cfg: CompressorConfig, flat: jax.Array, meta: QuantMeta, key: jax.Array,
                use_pallas: bool) -> jax.Array:
    """Flat fp32 -> packed uint32 wire words in one fused pass (no codes,
    no residual reach HBM)."""
    return _encode_dispatch(cfg, "encode_pack", flat, meta, key, use_pallas)


def encode_pack_residual(cfg: CompressorConfig, flat: jax.Array, meta: QuantMeta,
                         key: jax.Array, use_pallas: bool) -> tuple[jax.Array, jax.Array]:
    """Flat fp32 -> (uint32 wire words, ``flat − dequant(code)`` residual).

    The fused EF encode: the residual is written in the same pass as the
    pack, so the unpacked codes and the dequantized ``own`` tensor never
    leave VMEM on the kernel path.  Exact for codebook methods
    (``levels[code]`` is the interval endpoint the rounding chose); the
    uniform dequant keeps ulp-level FMA slack.
    """
    return _encode_dispatch(cfg, "encode_pack_residual", flat, meta, key, use_pallas)


def decode_reduce(cfg: CompressorConfig, words: jax.Array, levels: jax.Array, n: int,
                  use_pallas: bool) -> jax.Array:
    """Fused unpack → dequant → peer mean of gathered codec rows.

    ``words``: (peers, packed_words) uint32 wire rows; ``levels``: (peers,
    s+1) codebooks; returns the (n,) fp32 mean over peers, never
    materializing the (peers, n) unpacked tensor.  ``use_pallas`` selects the
    ``kernels.decode`` Pallas kernels (interpret-mode off-TPU); the fallback
    is the sequential-peer jnp oracle from ``kernels.ref``, which runs the
    same op sequence (bit-exact for codebook methods, ulp-level FMA slack
    for the uniform dequant — see ``tests/test_decode_kernels.py``) and is
    safe under shard_map tracing.  Every peer of a
    collective runs one compiled program over identical gathered bytes, so
    peers agree bit-for-bit on the result regardless of path (the
    peer-agreement contract).
    """
    return _decode_dispatch(cfg, "decode_reduce", words, levels, n, use_pallas)


def decode_rows(cfg: CompressorConfig, words: jax.Array, levels: jax.Array, n: int,
                use_pallas: bool) -> jax.Array:
    """Fused unpack → dequant of gathered rows, one (n,) row per peer.

    The all-gather phase-2 shape: peer j's decode is output chunk j, so the
    (peers, n) result *is* the payload (no reduction) — the fusion removes
    the (peers, n) int32 code intermediate.  Same dispatch contract as
    :func:`decode_reduce`.
    """
    return _decode_dispatch(cfg, "decode_rows", words, levels, n, use_pallas)


def _decode_dispatch(cfg: CompressorConfig, op: str, words: jax.Array, levels: jax.Array,
                     n: int, use_pallas: bool) -> jax.Array:
    """Select kernel vs fallback module and uniform vs codebook variant.

    Uniform-codebook methods dequantize from α alone (``levels[:, -1]``);
    everything else walks the shipped codebook.
    """
    if use_pallas:
        from repro.kernels import ops as mod
    else:
        from repro.kernels import ref as mod
    if cfg.method in _UNIFORM_DECODE:
        return getattr(mod, f"uniform_{op}")(words, levels[:, -1], n, cfg.bits)
    return getattr(mod, f"codebook_{op}")(words, levels, n, cfg.bits)


def _bucket_stats(flat: jax.Array, use_pallas: bool):
    """One-pass (counts, log_sums, g_max, …) statistics dispatch for the
    secondary plan sites (phase-2 chunks, pod means) that have no
    precomputed stats from the train step's fused EF-correct pass."""
    from repro.adaptive.telemetry import bucket_statistics

    return bucket_statistics(flat, use_pallas=use_pallas)


def _plan_bucket(cfg: CompressorConfig, flat: jax.Array, stat, use_pallas: bool) -> QuantMeta:
    """Histogram-driven plan from precomputed or inline one-pass stats."""
    if stat is None:
        stat = _bucket_stats(flat, use_pallas)
    return plan_from_stats(cfg, stat[0], stat[1], stat[2])


def _levels_to_wire(levels: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(levels.astype(jnp.float32), jnp.uint32)


def _levels_from_wire(words: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(words, jnp.float32)


# ---------------------------------------------------------------------------
# The codec interface
# ---------------------------------------------------------------------------

#: Collective ops one *bucketed* sync of a compressed codec may issue, by
#: mode — the whole value proposition of the fused wire tensor (the count is
#: bounded by the mode, never by the bucket or leaf count).  Declared here,
#: on the registry, so ``repro.analysis.jaxpr_lint`` (REPRO101) and the
#: benchmarks check every registered codec against the same numbers.
COLLECTIVE_BUDGETS = {
    "faithful": 1,       # one fused all-gather of every peer's wire
    "two_phase": 2,      # all-to-all reduce-scatter + all-gather of chunk wires
    "hierarchical": 3,   # intra-pod two-phase + the cross-pod faithful exchange
}


class Codec:
    """One registered compressor method (see the module docstring contracts).

    All geometry methods (``wire_words``, ``chunk_*``, ``state_extra``)
    return trace-time-static Python ints — fused-tensor offsets and EF-state
    shapes are resolved while tracing, never at run time.
    """

    name: str = ""
    #: supports the two-phase peer-chunk split (reduce-scatter layout)
    chunkable: bool = True
    #: the method's fidelity knob is ``cfg.rank`` (else ``cfg.bits``)
    rank_based: bool = False
    #: fixed wire bits/element for plan-less passthrough codecs (``fp16``:
    #: 16); ``None`` means the width is ``cfg.bits`` (the quantizers)
    fixed_wire_bits: int | None = None

    # -- planning ----------------------------------------------------------
    def plan(self, cfg: CompressorConfig, flat: jax.Array, stat, use_pallas: bool):
        """Data-dependent per-bucket plan (codebook fit); opaque to callers."""
        return None

    # -- trace-time contracts ----------------------------------------------
    def collective_budget(self, mode: str, n_buckets: int = 1) -> int:
        """Max collective eqns one bucketed sync of this codec traces under
        ``mode``.  Uncompressed paths (``dsgd``) fall back to one ``pmean``
        per bucket; every compressed codec shares the fused-wire budgets in
        :data:`COLLECTIVE_BUDGETS`.
        """
        if mode == "dsgd" or self.name == "dsgd":
            return int(n_buckets)
        try:
            return COLLECTIVE_BUDGETS[mode]
        except KeyError:
            raise ValueError(
                f"unknown sync mode {mode!r}; expected one of "
                f"{tuple(COLLECTIVE_BUDGETS) + ('dsgd',)}") from None

    # -- static geometry ---------------------------------------------------
    def wire_words(self, cfg: CompressorConfig, n: int) -> int:
        """uint32 words of one peer's full-bucket transmission."""
        raise NotImplementedError

    def wire_bytes(self, cfg: CompressorConfig, n: int) -> int:
        """Accounted wire bytes (may exceed 4·wire_words by out-of-band
        metadata, e.g. the quantizers' α word)."""
        return 4 * self.wire_words(cfg, n)

    def state_extra(self, cfg: CompressorConfig, n: int) -> int:
        """Opaque aux words appended to the bucket's EF residual row."""
        return 0

    # -- encode ------------------------------------------------------------
    def encode(self, cfg: CompressorConfig, flat: jax.Array, pln, key: jax.Array,
               use_pallas: bool) -> jax.Array:
        """Flat (n,) fp32 -> (wire_words,) uint32 wire."""
        raise NotImplementedError

    def encode_residual(self, cfg: CompressorConfig, flat: jax.Array, pln,
                        key: jax.Array, use_pallas: bool, aux=None):
        """-> (wire, EF residual ``flat − own``, new aux or None)."""
        raise NotImplementedError

    # -- decode ------------------------------------------------------------
    def decode_reduce(self, cfg: CompressorConfig, rows: jax.Array, n: int,
                      use_pallas: bool) -> jax.Array:
        """(peers, wire_words) gathered rows -> (n,) fp32 peer mean."""
        raise NotImplementedError

    def decode_rows(self, cfg: CompressorConfig, rows: jax.Array, n: int,
                    use_pallas: bool) -> jax.Array:
        """(peers, wire_words) rows -> (peers, n) fp32, one row per peer."""
        raise NotImplementedError

    # -- two-phase chunking (chunkable codecs only) ------------------------
    def chunk_elems(self, cfg: CompressorConfig, n: int, n_chunks: int) -> int:
        raise NotImplementedError

    def chunk_wire_words(self, cfg: CompressorConfig, n: int, n_chunks: int) -> int:
        raise NotImplementedError

    def encode_chunks(self, cfg: CompressorConfig, flat: jax.Array, pln,
                      key: jax.Array, n_chunks: int, use_pallas: bool):
        """-> ((n_chunks, chunk_wire_words) rows, (n,) EF residual)."""
        raise NotImplementedError


class QuantizerCodec(Codec):
    """The paper's scalar quantizers as the first registered family.

    Wire layout per bucket (unchanged from the pre-registry codec, pinned
    bit-exact by ``tests/test_mesh_invariance.py``):
    ``[packed_size(n, bits) code words][s+1 bitcast codebook words]``.
    """

    chunkable = True

    def __init__(self, name: str):
        self.name = name

    def plan(self, cfg, flat, stat, use_pallas):
        return _plan_bucket(cfg, flat, stat, use_pallas)

    def wire_words(self, cfg, n):
        return packed_size(n, cfg.bits) + cfg.s + 1

    def wire_bytes(self, cfg, n):
        return wire_bytes(cfg, n)

    def encode(self, cfg, flat, pln, key, use_pallas):
        words = encode_pack(cfg, flat, pln, key, use_pallas)
        return jnp.concatenate([words, _levels_to_wire(pln.levels)])

    def encode_residual(self, cfg, flat, pln, key, use_pallas, aux=None):
        words, resid = encode_pack_residual(cfg, flat, pln, key, use_pallas)
        return jnp.concatenate([words, _levels_to_wire(pln.levels)]), resid, None

    def _split(self, cfg, rows, n):
        w = packed_size(n, cfg.bits)
        return rows[:, :w], _levels_from_wire(rows[:, w:w + cfg.s + 1])

    def decode_reduce(self, cfg, rows, n, use_pallas):
        words, levels = self._split(cfg, rows, n)
        return decode_reduce(cfg, words, levels, n, use_pallas)

    def decode_rows(self, cfg, rows, n, use_pallas):
        words, levels = self._split(cfg, rows, n)
        return decode_rows(cfg, words, levels, n, use_pallas)

    def chunk_elems(self, cfg, n, n_chunks):
        # chunks pad to 32 elements so packed chunk words slice cleanly
        return (n + (-n) % (n_chunks * 32)) // n_chunks

    def chunk_wire_words(self, cfg, n, n_chunks):
        return packed_size(self.chunk_elems(cfg, n, n_chunks), cfg.bits) + cfg.s + 1

    def encode_chunks(self, cfg, flat, pln, key, n_chunks, use_pallas):
        padded = jnp.pad(flat, (0, (-flat.size) % (n_chunks * 32)))
        words, resid = encode_pack_residual(cfg, padded, pln, key, use_pallas)
        wc = packed_size(padded.size // n_chunks, cfg.bits)
        lv = jnp.tile(_levels_to_wire(pln.levels)[None], (n_chunks, 1))
        return jnp.concatenate([words.reshape(n_chunks, wc), lv], axis=1), resid[: flat.size]


class Fp16Codec(Codec):
    """Raw half-precision passthrough: the size-adaptive small-bucket tier.

    Small buckets (below ``TrainStepConfig.fp16_threshold`` elements) skip
    quantization entirely and ship bitcast fp16 — the Hivemind
    ``SizeAdaptiveCompression`` pattern: for tiny tensors the codebook
    overhead (s+1 words) rivals the payload and full half-precision is both
    cheaper to compute and lower-error.  The wire is two fp16 values packed
    per uint32 word (low half = even element), so it rides the same fused
    uint32 tensor as every other codec.  ``plan`` is ``None`` (nothing to
    fit), the encode draws no RNG (rounding is deterministic
    nearest-even), and the EF residual is the roundoff ``flat − f32(f16(
    flat))``.  ``cfg.bits`` is ignored (see ``fixed_wire_bits``).
    """

    name = "fp16"
    chunkable = True
    rank_based = False
    fixed_wire_bits = 16

    def plan(self, cfg, flat, stat, use_pallas):
        return None

    def wire_words(self, cfg, n):
        return (n + 1) // 2

    def wire_bytes(self, cfg, n):
        return 2 * n

    @staticmethod
    def _pack(flat: jax.Array) -> jax.Array:
        h = flat.astype(jnp.float16)
        if h.size % 2:
            h = jnp.pad(h, (0, 1))
        u = jax.lax.bitcast_convert_type(h, jnp.uint16).astype(jnp.uint32)
        return u[0::2] | (u[1::2] << 16)

    @staticmethod
    def _unpack(rows: jax.Array, n: int) -> jax.Array:
        lo = (rows & jnp.uint32(0xFFFF)).astype(jnp.uint16)
        hi = (rows >> 16).astype(jnp.uint16)
        u = jnp.stack([lo, hi], axis=-1).reshape(rows.shape[:-1] + (-1,))
        h = jax.lax.bitcast_convert_type(u, jnp.float16)
        return h[..., :n].astype(jnp.float32)

    def encode(self, cfg, flat, pln, key, use_pallas):
        return self._pack(flat)

    def encode_residual(self, cfg, flat, pln, key, use_pallas, aux=None):
        wire = self._pack(flat)
        resid = flat - flat.astype(jnp.float16).astype(jnp.float32)
        return wire, resid, None

    def decode_reduce(self, cfg, rows, n, use_pallas):
        return jnp.mean(self._unpack(rows, n), axis=0)

    def decode_rows(self, cfg, rows, n, use_pallas):
        return self._unpack(rows, n)

    def chunk_elems(self, cfg, n, n_chunks):
        # chunks pad to 2 elements so packed chunk words slice cleanly
        return (n + (-n) % (n_chunks * 2)) // n_chunks

    def chunk_wire_words(self, cfg, n, n_chunks):
        return self.chunk_elems(cfg, n, n_chunks) // 2

    def encode_chunks(self, cfg, flat, pln, key, n_chunks, use_pallas):
        padded = jnp.pad(flat, (0, (-flat.size) % (n_chunks * 2)))
        words = self._pack(padded)
        resid = flat - flat.astype(jnp.float16).astype(jnp.float32)
        return words.reshape(n_chunks, -1), resid


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, Codec] = {}


def register_codec(codec: Codec, *, override: bool = False) -> Codec:
    """Register ``codec`` under ``codec.name``.

    A second registration of the same name raises — two plugins silently
    shadowing each other is exactly the dispatch ambiguity the registry
    exists to rule out.  Pass ``override=True`` to replace a registered
    codec deliberately (tests, method shims).
    """
    if not codec.name:
        raise ValueError("codec must set a non-empty name")
    if not override and codec.name in _REGISTRY:
        raise ValueError(
            f"codec {codec.name!r} is already registered "
            f"({type(_REGISTRY[codec.name]).__name__}); pass override=True "
            "to replace it deliberately")
    _REGISTRY[codec.name] = codec
    return codec


def _ensure_builtin() -> None:
    # The low-rank family registers on import; deferred so that core.codecs
    # stays importable before core.lowrank (and kernels) exist in a trace.
    if "powersgd" not in _REGISTRY:
        from . import lowrank  # noqa: F401  (registers powersgd)


def get_codec(method: str) -> Codec:
    """The registered :class:`Codec` for ``method``."""
    _ensure_builtin()
    try:
        return _REGISTRY[method]
    except KeyError:
        raise KeyError(
            f"no codec registered for method {method!r}; known: {known_methods()}"
        ) from None


def known_methods() -> tuple[str, ...]:
    """All registered method names (sorted)."""
    _ensure_builtin()
    return tuple(sorted(_REGISTRY))


for _m in METHODS:
    register_codec(QuantizerCodec(_m))
del _m
register_codec(Fp16Codec())


# ---------------------------------------------------------------------------
# Per-bucket config plans (the ``bits_plan`` entries, now method-aware)
# ---------------------------------------------------------------------------


def bucket_cfg_entry(cfg: CompressorConfig, entry) -> CompressorConfig:
    """Resolve one per-bucket plan entry to a :class:`CompressorConfig`.

    ``entry`` is an int (bit width under ``cfg.method``), a
    ``("method", value)`` pair (value = rank for rank-based codecs, bits
    otherwise), or a full :class:`CompressorConfig`.  Malformed entries
    raise ``ValueError`` naming the entry and the accepted forms.
    """
    import dataclasses

    if isinstance(entry, CompressorConfig):
        return entry
    if isinstance(entry, tuple | list):
        if len(entry) != 2 or not isinstance(entry[0], str):
            raise ValueError(
                f"malformed bits_plan entry {entry!r}: a sequence entry must "
                "be a ('method', value) pair — e.g. ('tqsgd', 3) or "
                "('powersgd', 2)")
        method, value = entry
        try:
            value = int(value)
        except (TypeError, ValueError):
            raise ValueError(
                f"malformed bits_plan entry {entry!r}: value must be an int "
                f"(rank for rank-based codecs, bits otherwise), got "
                f"{type(entry[1]).__name__}") from None
        if get_codec(method).rank_based:
            if method == cfg.method and value == cfg.rank:
                return cfg
            return dataclasses.replace(cfg, method=method, rank=value)
        if method == cfg.method and value == cfg.bits:
            return cfg
        return dataclasses.replace(cfg, method=method, bits=value)
    try:
        entry = int(entry)
    except (TypeError, ValueError):
        raise ValueError(
            f"malformed bits_plan entry {entry!r}: expected an int bit "
            "width, a ('method', value) pair, or a CompressorConfig") from None
    return cfg if entry == cfg.bits else dataclasses.replace(cfg, bits=entry)


def bucket_cfgs(
    cfg: CompressorConfig, n_buckets: int, plan: Sequence | None
) -> list[CompressorConfig]:
    """Per-bucket compressor configs for a (possibly heterogeneous) plan.

    ``plan=None`` keeps ``cfg`` everywhere; otherwise one config per bucket
    from :func:`bucket_cfg_entry`.  The plan is trace-time Python, so bucket
    offsets in the fused wire tensor stay static.
    """
    if plan is None:
        return [cfg] * n_buckets
    if len(plan) != n_buckets:
        raise ValueError(f"bit plan has {len(plan)} entries for {n_buckets} buckets")
    return [bucket_cfg_entry(cfg, e) for e in plan]


def size_adaptive_plan(
    cfg: CompressorConfig, plan: Sequence | None, sizes: Sequence[int],
    threshold: int,
) -> Sequence | None:
    """Apply the fp16 small-bucket tier to a per-bucket plan.

    Buckets of at most ``threshold`` elements are overridden to the
    ``fp16`` passthrough codec (the Hivemind ``SizeAdaptiveCompression``
    pattern — see :class:`Fp16Codec`); larger buckets keep their ``plan``
    entry (or ``cfg`` itself when ``plan`` is None).  ``threshold <= 0``
    disables the tier and returns ``plan`` unchanged, so tier-off graphs
    stay byte-identical.  Trace-time Python: the tier decision is static
    per compiled step, like every other plan entry.
    """
    if threshold <= 0 or not any(int(m) <= threshold for m in sizes):
        return plan
    base = list(plan) if plan is not None else [cfg] * len(sizes)
    if len(base) != len(sizes):
        raise ValueError(f"bit plan has {len(base)} entries for {len(sizes)} buckets")
    return tuple(("fp16", cfg.bits) if int(m) <= threshold else e
                 for e, m in zip(base, sizes))


def bucket_state_sizes(
    cfg: CompressorConfig, sizes: Sequence[int], plan: Sequence | None = None
) -> list[int]:
    """EF/state row length per bucket: ``m + state_extra`` under the plan."""
    cfgs = bucket_cfgs(cfg, len(sizes), plan)
    return [int(m) + get_codec(c.method).state_extra(c, int(m))
            for m, c in zip(sizes, cfgs)]
