"""Pass 1 (``REPRO1xx``): jaxpr linter over the traced sync surface.

Traces every registered sync mode × codec method through the
``dist.train_step._make_sync_fn`` shard_map closure (the collective
surface ``make_train_step`` compiles) and the single-device
``dist.reference`` replay, then walks each ClosedJaxpr:

- **REPRO101** — collective eqns (``all_gather`` / ``all_to_all`` / ``psum``
  / ``ppermute`` / …) counted against the budget the codec registry
  declares per sync mode (``core.codecs.Codec.collective_budget``): 1 for
  ``faithful``, 2 for ``two_phase``, 3 for ``hierarchical``, one ``pmean``
  per bucket for uncompressed ``dsgd``.  This is the reusable checker that
  replaced the ad-hoc trace-count assertions in
  ``benchmarks/adaptive_bench.py``.
- **REPRO102** — every ``random_bits`` / ``threefry2x32`` draw inside a
  shard_map region must have a data dependency on ``axis_index``: peers
  folding the same step key without the axis index draw *identical*
  quantization noise, the exact correlated-RNG bug PR 2 fixed.  Key
  derivation (``random_split`` / ``random_fold_in``) is exempt — only
  payload draws are checked.
- **REPRO103** — float64 values anywhere in the trace.
- **REPRO104** — float scatter-add without ``unique_indices`` (reduction
  order, and therefore the synced bytes, become schedule-dependent).
- **REPRO105** — non-uint32 operands crossing an ``all_gather`` /
  ``all_to_all`` boundary in a compressed trace (the wire contract: one
  uint32 word vector per bucket).

Findings anchored to a source line honor the ``# repro: allow REPROxxx``
comment suppression (see :mod:`repro.analysis`).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import pathlib

import jax
import jax.numpy as jnp
from jax.extend.core import Literal

from . import Finding, suppressed_codes

#: primitives that move bytes between peers (the budgeted set)
COLLECTIVES = frozenset(
    {"all_to_all", "all_gather", "psum", "ppermute", "all_gather_invariant",
     "reduce_scatter"})

#: collectives whose operands are wire tensors under the compressed codec
_WIRE_COLLECTIVES = frozenset(
    {"all_to_all", "all_gather", "all_gather_invariant", "ppermute",
     "reduce_scatter"})

#: payload RNG draws (key derivation — random_split/random_fold_in — exempt)
_RNG_CONSUMERS = frozenset({"random_bits", "threefry2x32"})


def _inner_jaxpr(v):
    """The Jaxpr inside a ClosedJaxpr/Jaxpr param value, else None."""
    j = getattr(v, "jaxpr", None)
    if j is not None and hasattr(j, "eqns"):
        return j
    return v if hasattr(v, "eqns") else None


def _sub_jaxprs(eqn):
    """Yield ``(jaxpr, outer_invars | None)`` for each sub-jaxpr of ``eqn``.

    ``outer_invars`` maps positionally onto the sub-jaxpr's invars when the
    correspondence is 1:1 (pjit / shard_map / scan / custom_* calls);
    ``cond`` branches bind ``eqn.invars[1:]``; anything else yields None
    and the caller must treat the mapping as unknown.
    """
    if eqn.primitive.name == "cond":
        for br in eqn.params["branches"]:
            yield _inner_jaxpr(br), list(eqn.invars[1:])
        return
    for v in eqn.params.values():
        j = _inner_jaxpr(v)
        if j is None:
            continue
        yield j, (list(eqn.invars) if len(j.invars) == len(eqn.invars) else None)


def walk_eqns(jaxpr):
    """Depth-first iterator over every eqn of ``jaxpr`` and its sub-jaxprs."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)  # ClosedJaxpr -> Jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub, _ in _sub_jaxprs(eqn):
            yield from walk_eqns(sub)


def count_collectives(jaxpr) -> collections.Counter:
    """Collective-primitive counts over ``jaxpr`` (Closed or plain), at any
    nesting depth — the reusable checker behind REPRO101 and the
    ``adaptive_bench`` collective-count rows."""
    acc: collections.Counter = collections.Counter()
    for eqn in walk_eqns(jaxpr):
        if eqn.primitive.name in COLLECTIVES:
            acc[eqn.primitive.name] += 1
    return acc


@dataclasses.dataclass(frozen=True)
class WireOp:
    """One collective eqn's operand/result geometry."""

    primitive: str
    in_bytes: int        # bytes this device feeds into the collective
    out_bytes: int       # bytes it holds afterwards
    dtypes: tuple[str, ...]


def collective_wire_sizes(jaxpr) -> list[WireOp]:
    """Measured wire-tensor sizes of every collective in the trace — the
    jaxpr side of the ``encode_hbm_bytes`` / ``decode_hbm_bytes``
    cross-check (``tests/test_analysis.py``)."""
    out = []
    for eqn in walk_eqns(jaxpr):
        if eqn.primitive.name not in COLLECTIVES:
            continue
        ins = [v.aval for v in eqn.invars if hasattr(v, "aval")]
        outs = [v.aval for v in eqn.outvars if hasattr(v, "aval")]
        out.append(WireOp(
            primitive=eqn.primitive.name,
            in_bytes=sum(a.size * a.dtype.itemsize for a in ins),
            out_bytes=sum(a.size * a.dtype.itemsize for a in outs),
            dtypes=tuple(str(a.dtype) for a in ins),
        ))
    return out


# ---------------------------------------------------------------------------
# Source anchoring + suppression
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=256)
def _source_lines(path: str) -> tuple[str, ...]:
    try:
        return tuple(pathlib.Path(path).read_text().splitlines())
    except OSError:
        return ()


def _eqn_site(eqn):
    """``(file, line)`` of the user frame that bound ``eqn``, else None."""
    try:
        from jax._src import source_info_util

        frame = source_info_util.user_frame(eqn.source_info.traceback)
    except Exception:
        return None
    if frame is None:
        return None
    return frame.file_name, frame.start_line


def _emit(findings: list[Finding], code: str, eqn, label: str, message: str) -> None:
    site = _eqn_site(eqn)
    if site is not None:
        path, line = site
        if code in suppressed_codes(list(_source_lines(path)), line):
            return
        where = f"{path}:{line}"
    else:
        where = label
    findings.append(Finding(code, where, f"[{label}] {message}"))


# ---------------------------------------------------------------------------
# REPRO102: axis_index -> RNG-key taint analysis
# ---------------------------------------------------------------------------


def _check_rng(jaxpr, tainted: set, in_shard_map: bool, label: str,
               findings: list[Finding]) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "axis_index":
            tainted.update(eqn.outvars)
            continue
        any_taint = any(not isinstance(v, Literal) and v in tainted
                        for v in eqn.invars)
        for sub, outer in _sub_jaxprs(eqn):
            inner = (set(sub.invars) if any_taint else set()) if outer is None \
                else {iv for iv, ov in zip(sub.invars, outer)
                      if not isinstance(ov, Literal) and ov in tainted}
            _check_rng(sub, inner, in_shard_map or name == "shard_map",
                       label, findings)
        if in_shard_map and name in _RNG_CONSUMERS and not any_taint:
            _emit(findings, "REPRO102", eqn, label,
                  f"{name} key has no data dependency on axis_index — all "
                  "peers draw identical quantization noise")
        if any_taint:
            tainted.update(eqn.outvars)


# ---------------------------------------------------------------------------
# The per-trace lint (REPRO102-105) and budget check (REPRO101)
# ---------------------------------------------------------------------------


def lint_trace(jaxpr, label: str, *, compressed: bool = True) -> list[Finding]:
    """REPRO102/103/104/105 over one traced computation.

    ``compressed=False`` (the dsgd fp32 paths) skips the uint32 wire-dtype
    rule — an fp32 ``pmean`` is that mode's contract.
    """
    findings: list[Finding] = []
    core = getattr(jaxpr, "jaxpr", jaxpr)
    _check_rng(core, set(), False, label, findings)
    for eqn in walk_eqns(core):
        name = eqn.primitive.name
        for v in list(eqn.outvars) + list(eqn.invars):
            aval = getattr(v, "aval", None)
            if aval is not None and getattr(aval, "dtype", None) == jnp.float64:
                _emit(findings, "REPRO103", eqn, label,
                      f"{name} touches float64 (dtype {aval.dtype})")
                break
        if name == "scatter-add" and not eqn.params.get("unique_indices", False):
            aval = eqn.outvars[0].aval
            if jnp.issubdtype(aval.dtype, jnp.floating):
                _emit(findings, "REPRO104", eqn, label,
                      "float scatter-add without unique_indices: reduction "
                      "order (and synced bytes) become schedule-dependent")
        if compressed and name in _WIRE_COLLECTIVES:
            for v in eqn.invars:
                aval = getattr(v, "aval", None)
                if aval is not None and aval.dtype != jnp.uint32:
                    _emit(findings, "REPRO105", eqn, label,
                          f"{name} moves {aval.dtype} across the compressed "
                          "wire; the codec contract is uint32 words")
    # one eqn site can be traced many times (per bucket, per phase)
    return list(dict.fromkeys(findings))


def check_budget(jaxpr, budget: int, label: str) -> list[Finding]:
    """REPRO101: total collective count vs the registry-declared budget."""
    counts = count_collectives(jaxpr)
    total = sum(counts.values())
    if total > budget:
        detail = ", ".join(f"{k}={v}" for k, v in sorted(counts.items()))
        return [Finding("REPRO101", label,
                        f"[{label}] {total} collectives traced ({detail}) "
                        f"vs a budget of {budget}")]
    return []


# ---------------------------------------------------------------------------
# Trace harness: tiny sync closures over fake host devices
# ---------------------------------------------------------------------------

_N_DEV = 4
_LEAF_SIZES = (2048, 1024)
_BUCKET_MB = 0.008  # ~2048-element buckets -> 2 buckets over _LEAF_SIZES


@dataclasses.dataclass(frozen=True)
class SyncTrace:
    """One traced mode × method sync closure plus its budget inputs."""

    label: str
    closed: object       # ClosedJaxpr
    n_buckets: int
    budget: int
    compressed: bool


def _require_devices() -> None:
    if len(jax.devices()) < _N_DEV:
        raise RuntimeError(
            f"the jaxpr pass traces over {_N_DEV} devices; run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={_N_DEV} "
            "(the CLI and CI job set this before importing jax)")


def _param_trees():
    params_like = {f"p{i}": jax.ShapeDtypeStruct((s,), jnp.float32)
                   for i, s in enumerate(_LEAF_SIZES)}
    from jax.sharding import PartitionSpec as P

    pspecs = {k: P() for k in params_like}
    return params_like, pspecs


def _bits_plan(method: str, n_buckets: int):
    """Heterogeneous per-bucket plan (the adaptive wire) for quantizer
    methods; rank-based codecs keep the base config."""
    from repro.core.codecs import get_codec

    if get_codec(method).rank_based or n_buckets < 2:
        return None
    return tuple(2 + (i % 3) for i in range(n_buckets))


def sync_trace(method: str, mode: str) -> SyncTrace:
    """Trace ``_make_sync_fn`` for one mode × method with EF + telemetry
    threaded (where the mode supports them) and a heterogeneous bit plan."""
    from repro.adaptive.controller import AdaptiveConfig
    from repro.core.codecs import get_codec
    from repro.core.compressors import CompressorConfig
    from repro.dist.train_step import (TrainStepConfig, _make_sync_fn,
                                       init_ef_state, init_telemetry_state,
                                       local_bucket_sizes)

    _require_devices()
    AxisType = jax.sharding.AxisType
    shape, axes = ((2, 2), ("pod", "data")) if mode == "hierarchical" \
        else ((_N_DEV,), ("data",))
    mesh = jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
    params_like, pspecs = _param_trees()
    compressed = not (mode == "dsgd" or method == "dsgd")
    cfg = CompressorConfig(method=method, bits=3, rank=2, approx_gmin=True)
    ts0 = TrainStepConfig(sync=mode, compressor=cfg, bucket_mb=_BUCKET_MB)
    n_buckets = len(local_bucket_sizes(params_like, mesh, pspecs, ts0))
    ts = TrainStepConfig(
        sync=mode, compressor=cfg, bucket_mb=_BUCKET_MB,
        error_feedback=compressed,
        adaptive=AdaptiveConfig() if compressed else None,
        bits_plan=_bits_plan(method, n_buckets) if compressed else None)
    stacked = {k: jnp.zeros((_N_DEV,) + tuple(v.shape), v.dtype)
               for k, v in params_like.items()}
    extras = []
    if ts.error_feedback:
        extras.append(init_ef_state(params_like, mesh, pspecs, ts))
    if ts.adaptive is not None:
        extras.append(init_telemetry_state(params_like, mesh, pspecs, ts))
    # geometry-only trace key; never executed
    key = jax.random.key(0)  # repro: allow REPRO204 (trace-time aval only)
    jfn = jax.jit(_make_sync_fn(ts, mesh, pspecs, stacked))
    closed = jfn.trace(stacked, key, *extras).jaxpr
    budget = get_codec(method).collective_budget(mode, n_buckets)
    return SyncTrace(label=f"sync:{mode}/{method}", closed=closed,
                     n_buckets=n_buckets, budget=budget, compressed=compressed)


def reference_trace(method: str, mode: str) -> SyncTrace:
    """Trace the single-device ``dist.reference`` replay (no collectives —
    budget 0 — but the dtype/determinism rules still apply)."""
    from repro.core.compressors import CompressorConfig
    from repro.dist import reference
    from repro.dist.train_step import TrainStepConfig

    cfg = CompressorConfig(method=method, bits=3, rank=2, approx_gmin=True)
    ts = TrainStepConfig(sync=mode, compressor=cfg, bucket_mb=_BUCKET_MB)
    leaves = [jnp.zeros((_N_DEV, s), jnp.float32) for s in _LEAF_SIZES]
    key = jax.random.key(0)  # repro: allow REPRO204 (trace-time aval only)
    closed = jax.jit(
        lambda lv, k: reference.reference_sync(ts, lv, (_N_DEV,), k)
    ).trace(leaves, key).jaxpr
    compressed = not (mode == "dsgd" or method == "dsgd")
    return SyncTrace(label=f"reference:{mode}/{method}", closed=closed,
                     n_buckets=0, budget=0, compressed=compressed)


#: the mode sweep (dsgd = the uncompressed pmean baseline)
MODES = ("faithful", "two_phase", "hierarchical", "dsgd")

#: reference replays are method-redundant; spot-check one per decode family
_REFERENCE_METHODS = ("tqsgd", "tnqsgd", "powersgd", "dsgd")


def run_pass(methods=None, modes=None, *, quick: bool = False):
    """Trace + lint the full mode × method matrix.

    Returns ``(findings, stats)``; ``stats`` carries per-trace collective
    counts for the JSON report.  ``quick`` restricts to one method per
    codec family (the tier-1 test configuration).
    """
    from repro.core.codecs import known_methods

    if methods is None:
        methods = ("tqsgd", "powersgd", "dsgd") if quick else known_methods()
    if modes is None:
        modes = MODES
    findings: list[Finding] = []
    traces: dict[str, dict] = {}
    for mode in modes:
        for method in methods:
            st = sync_trace(method, mode)
            findings += check_budget(st.closed, st.budget, st.label)
            findings += lint_trace(st.closed, st.label, compressed=st.compressed)
            traces[st.label] = {
                "collectives": dict(count_collectives(st.closed)),
                "budget": st.budget, "n_buckets": st.n_buckets}
        ref_methods = methods if quick else \
            tuple(m for m in _REFERENCE_METHODS if m in methods)
        for method in ref_methods:
            rt = reference_trace(method, mode)
            findings += lint_trace(rt.closed, rt.label, compressed=rt.compressed)
            traces[rt.label] = {"collectives": dict(count_collectives(rt.closed))}
    return findings, {"traces": len(traces), "per_trace": traces}
