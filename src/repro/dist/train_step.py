"""Distributed train step: GSPMD fwd/bwd + fully-manual compressed grad sync.

``make_train_step`` builds one jitted step function over an arbitrary
(data[, model][, pod]) mesh.  The step has two regions:

1. **Auto (GSPMD) region** — the global batch is split into one *client* per
   data shard and per-client loss/grads are computed with ``vmap`` (params
   broadcast, batch mapped).  The stacked client axis is sharded over the
   data/pod mesh axes, so each device computes and holds exactly its own
   worker's gradient — the DSGD worker model, with tensor/expert parallelism
   and fsdp parameter sharding left to the partitioner.
2. **Manual (shard_map) region** — the stacked gradients enter a fully
   manual shard_map (every mesh axis manual; see ``dist.compat`` for why
   the sync does not mix manual data axes with auto model axes) where the
   selected mode averages clients with real collectives:

   ======================  ====================================================
   ``dsgd``                exact fp32 ``pmean`` (the uncompressed baseline)
   ``two_phase``           compressed reduce-scatter + compressed all-gather
   ``hierarchical``        two-phase inside each pod, then a faithful
                           quantized exchange of pod-means across ``pod``
   ``faithful``            ring mean — each peer's tensor quantized once,
                           unbiased across peers (Wu et al., 1806.08054)
   ======================  ====================================================

   Gradients arrive model-sharded, so each (data, model) shard quantizes its
   own slice and the collectives only cross the data/pod axes.

The optimizer update then runs back in the auto region on sharded
params/state.  ``streamed=True`` swaps the one-shot ``value_and_grad`` for a
layer-streamed schedule: a forward scan that saves per-unit activations and
a reverse scan of per-unit VJPs, so at most one scan unit's backward graph
is live at a time.  It is numerically equivalent to the plain schedule.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.adaptive import telemetry as adaptive_telemetry
from repro.adaptive.controller import AdaptiveConfig
from repro.core import compressors
from repro.core.codecs import size_adaptive_plan
from repro.core.compressors import CompressorConfig
from repro.elastic.schedule import ElasticConfig, live_mask
from repro.models import transformer
from repro.obs import metrics as obs_metrics
from repro.optim.optimizers import Optimizer

from . import compat, sharded_codec as sc, sharding

SYNC_MODES = ("dsgd", "two_phase", "hierarchical", "faithful")

_KEY_SEED = 0x5EED


@dataclasses.dataclass(frozen=True)
class TrainStepConfig:
    """Per-step gradient synchronization configuration.

    ``bucket_mb > 0`` (the default) routes the sync through the bucketed
    codec: the gradient pytree is coalesced into ~``bucket_mb``-MB fp32
    buckets with one codebook per bucket and one fused collective per phase
    (vs one plan + 2-4 collectives per *leaf* on the per-leaf path, selected
    with ``bucket_mb=0``).  ``error_feedback=True`` carries a per-client
    EF residual through the step signature — ``step_fn(params,
    opt_state, ef_state, batch, step) -> (params, opt_state, ef_state,
    metrics)`` — compensating the truncated quantizers' bias
    (``core.error_feedback`` semantics: transmit C(g+e), keep e' = g+e-C(g+e)).

    ``adaptive`` (an :class:`repro.adaptive.AdaptiveConfig`) threads a
    per-client telemetry pytree through the signature the same way — the
    state slot follows ``ef_state`` when both are on — updated inside the
    sync region from the exact buckets the codec quantizes, with no extra
    collectives.  ``bits_plan`` assigns each bucket its own static wire
    width; bit plans are static per compiled step, so the adaptive runtime
    (``repro.adaptive.runtime``) swaps between compiled steps through a
    cache keyed on the bit tuple instead of retracing.

    The EF residual is **bucket-resident**: one stacked (n_clients,
    bucket_elems) fp32 array per codec bucket (:func:`init_ef_state`), not
    a leaf pytree — the residual the fused encode emits is carried to the
    next step as-is, with no per-step ``bucket_concat``/``bucket_split`` of
    the EF state and no leaf-spec constraint round-trip.

    ``metrics_gnorm=False`` drops the global gradient-norm metric; when on
    (the default) it is computed from the already-flat mean buckets inside
    the sync region (one ``psum`` over the model axes) instead of
    re-reducing the leaf pytree in the auto region.

    ``metrics_compression=True`` additionally emits a per-bucket
    :class:`repro.obs.metrics.CompressionMetrics` pytree under
    ``metrics["compression"]`` (leaves ``(n_dp, n_buckets)``, one row per
    data peer), gated exactly like ``metrics_gnorm``: everything is
    computed from tensors already resident inside the sync region (the
    fused encode's residual, the one-pass stats, the plan), the
    model-shard reduction rides the *same* vectorized ``psum`` as the
    gnorm, and the traced collective count per sync mode is unchanged
    (``analysis.count_collectives`` asserts this in ``tests/test_obs.py``).
    Requires the bucketed codec; omitted (like the sync itself) on meshes
    without data axes.
    """

    sync: str = "dsgd"
    streamed: bool = False
    compressor: CompressorConfig = dataclasses.field(default_factory=CompressorConfig)
    bucket_mb: float = 4.0
    error_feedback: bool = False
    adaptive: AdaptiveConfig | None = None
    bits_plan: tuple[int, ...] | None = None
    metrics_gnorm: bool = True
    metrics_compression: bool = False
    #: deterministic partial participation (``repro.elastic``): the step
    #: computes a per-step live mask in-graph and the sync renormalizes the
    #: peer mean over the live count; dropped peers' EF rows keep
    #: accumulating.  Adds ``metrics["live"]`` / ``metrics["live_count"]``.
    elastic: ElasticConfig | None = None
    #: size-adaptive compression tier: buckets of at most this many (local)
    #: elements ship raw half precision through the registered ``fp16``
    #: passthrough codec instead of the quantizer (0 = off).
    fp16_threshold: int = 0

    def __post_init__(self):
        if self.sync not in SYNC_MODES:
            raise ValueError(f"unknown sync mode {self.sync!r}; expected one of {SYNC_MODES}")
        if self.bucket_mb < 0:
            raise ValueError("bucket_mb must be >= 0 (0 selects the per-leaf codec)")
        if self.error_feedback:
            if self.sync == "dsgd" or self.compressor.method == "dsgd":
                raise ValueError("error_feedback requires a compressed sync mode/method")
            if self.bucket_mb <= 0:
                raise ValueError("error_feedback requires the bucketed codec (bucket_mb > 0)")
        if self.adaptive is not None:
            if self.sync == "dsgd" or self.compressor.method == "dsgd":
                raise ValueError("adaptive telemetry requires a compressed sync mode/method")
            if self.bucket_mb <= 0:
                raise ValueError("adaptive telemetry requires the bucketed codec (bucket_mb > 0)")
        if self.metrics_compression and self.bucket_mb <= 0:
            raise ValueError("metrics_compression requires the bucketed codec (bucket_mb > 0)")
        if self.elastic is not None and self.bucket_mb <= 0:
            raise ValueError("elastic sync requires the bucketed codec (bucket_mb > 0)")
        if self.fp16_threshold < 0:
            raise ValueError("fp16_threshold must be >= 0 (0 disables the tier)")
        if self.fp16_threshold > 0 and self.bucket_mb <= 0:
            raise ValueError("fp16_threshold targets the bucketed codec (bucket_mb > 0)")
        if self.bits_plan is not None:
            if self.bucket_mb <= 0:
                raise ValueError("bits_plan targets the bucketed codec (bucket_mb > 0)")
            norm = []
            for b in self.bits_plan:
                if isinstance(b, tuple | list):
                    # method-aware plan entry: ("method", value) — value is
                    # the rank for rank-based codecs, the bit width otherwise.
                    # The registry validates the shape, the method name, and
                    # the value range with actionable messages.
                    from repro.core.codecs import bucket_cfg_entry

                    bucket_cfg_entry(self.compressor, b)
                    norm.append((str(b[0]), int(b[1])))
                else:
                    if not (1 <= int(b) <= 8):
                        raise ValueError("bits_plan entries must be in [1, 8]")
                    norm.append(int(b))
            object.__setattr__(self, "bits_plan", tuple(norm))

    @property
    def bucket_elements(self) -> int:
        return int(self.bucket_mb * (1 << 20) / 4)


# ---------------------------------------------------------------------------
# Spec derivation helpers (shared with the launch/dryrun tooling)
# ---------------------------------------------------------------------------


def batch_pspecs(batch_like: Any, dp) -> Any:
    """PartitionSpecs for a ``models.transformer.Batch``: batch dim over ``dp``."""

    def for_field(x, batch_dim: int):
        if x is None:
            return None
        return P(*(dp if d == batch_dim else None for d in range(x.ndim)))

    b = batch_like
    return transformer.Batch(
        tokens=for_field(b.tokens, 0),
        labels=for_field(b.labels, 0),
        positions=for_field(b.positions, 1 if (b.positions is not None and b.positions.ndim == 3) else 0),
        patches=for_field(b.patches, 0),
        frames=for_field(b.frames, 0),
    )


def _opt_specs(opt_state_like: Any, params_like: Any, pspecs: Any) -> Any:
    """Optimizer-state specs: mirror leaves get the matching param's spec.

    State trees mirror the param tree leaf-for-leaf (momentum: one mirror;
    AdamW: two), but may interleave non-mirroring leaves such as a scalar
    step counter.  Each state leaf is shape-matched against the param tree
    in cyclic traversal order: a match takes the param's spec and advances
    the cursor; anything else (true scalars, odd bookkeeping) stays
    replicated with ``P()`` instead of silently replicating the *entire*
    state the way blanket cyclic indexing did.
    """
    p_shapes = [tuple(x.shape) for x in jax.tree.leaves(params_like)]
    spec_leaves = jax.tree.leaves(pspecs, is_leaf=lambda s: isinstance(s, P))
    leaves, treedef = jax.tree.flatten(opt_state_like)
    n = len(p_shapes)
    out, cursor = [], 0
    for leaf in leaves:
        if n and hasattr(leaf, "shape") and tuple(leaf.shape) == p_shapes[cursor % n]:
            out.append(spec_leaves[cursor % n])
            cursor += 1
        else:
            out.append(P())
    if n and cursor % n:
        # Partial mirror cycle: a non-mirror leaf with a coincidental param
        # shape desynced the cursor, so the assignment is unreliable — keep
        # the whole state replicated (always valid) rather than guessing.
        return jax.tree.unflatten(treedef, [P() for _ in leaves])
    return jax.tree.unflatten(treedef, out)


def _tree_map_with_specs(fn, tree: Any, spec_tree: Any) -> Any:
    leaves, treedef = jax.tree.flatten(tree)
    specs = jax.tree.leaves(spec_tree, is_leaf=lambda s: isinstance(s, P))
    return jax.tree.unflatten(treedef, [fn(x, s) for x, s in zip(leaves, specs)])


def _auto_only_entries(spec: P, mesh) -> tuple:
    """Spec entries with the manual (data/pod) axes removed — the stacked
    per-client gradients keep only their model-parallel sharding."""
    manual = set(sharding.manual_axes(mesh))
    entries = []
    for e in spec:
        axes = e if isinstance(e, tuple) else (e,) if e is not None else ()
        kept = tuple(a for a in axes if a not in manual)
        entries.append(kept[0] if len(kept) == 1 else (kept if kept else None))
    return tuple(entries)


# ---------------------------------------------------------------------------
# Gradient synchronization (runs inside a fully manual shard_map)
# ---------------------------------------------------------------------------


def _sync_leaf(ts: TrainStepConfig, g: jax.Array, key: jax.Array, dp: tuple) -> jax.Array:
    if ts.sync == "dsgd" or ts.compressor.method == "dsgd":
        return jax.lax.pmean(g, dp)
    cfg = ts.compressor
    if ts.sync == "faithful":
        return sc.faithful_ring_mean(cfg, g, dp, key, cfg.use_pallas)
    if ts.sync == "two_phase" or len(dp) == 1:
        return sc.two_phase_mean(cfg, g, dp, key, cfg.use_pallas)
    # hierarchical: compress within the innermost data axis, then exchange
    # pod-level means across the leading pod axes with a fresh quantization.
    # The intra-pod key folds the full dp index so same-data-rank workers in
    # different pods stay decorrelated (see bucketed_hierarchical_mean).
    pod_axes, data_axis = dp[:-1], dp[-1:]
    k1, k2 = jax.random.split(key)
    g = sc.two_phase_mean(cfg, g, data_axis, sc._peer_key(k1, dp), cfg.use_pallas)
    return sc.faithful_ring_mean(cfg, g, pod_axes, k2, cfg.use_pallas)


def _sync_buckets(ts: TrainStepConfig, vals: list, key: jax.Array, dp: tuple,
                  ef=None, tstate=None, live=None):
    """Bucketed sync of a flat leaf list.
    Returns (mean_leaves, resid_buckets, new_telemetry, mean_buckets,
    metric_sums) — ``metric_sums`` is the pre-psum
    ``repro.obs.metrics.local_sums`` pair under
    ``ts.metrics_compression`` (else ``None``); the caller reduces it over
    the model axes together with the gnorm scalar.

    The bucket plan is derived at trace time from the *local* (post-shard)
    leaf sizes; each phase of the selected mode moves one fused wire tensor
    for the whole bucket list, so the per-step collective count is bounded
    by the mode (1-3), not by the leaf or bucket count — including under a
    heterogeneous ``bits_plan``.

    The encode side is one-pass: each gradient bucket is read once by the
    fused EF-correct→stats pass (``adaptive.telemetry.correct_stats``),
    which adds the bucket-resident EF residual ``ef`` and emits the
    corrected bucket plus all statistics the codec's
    ``plan_from_stats`` codebook fit *and* the telemetry EMA consume — no
    separate telemetry sweep, no sort/quantile inside ``plan``.  The new
    residual comes back from the fused encode itself (bucket-resident, so
    the caller carries it to the next step without a ``bucket_split``);
    ``bucket_split`` runs once, on the final mean.  The flat mean buckets
    are also returned so the caller can derive ``gnorm`` without
    re-reducing the leaf pytree.

    ``live`` (elastic partial participation) is the replicated (n,) 0/1
    float mask over the dp peers; see the elastic block in
    ``dist.sharded_codec`` for the masking/renormalization semantics.
    """
    cfg = ts.compressor
    bp = compressors.plan_buckets([v.size for v in vals], ts.bucket_elements)
    buckets = compressors.bucket_concat(vals, bp)
    compressed = not (ts.sync == "dsgd" or cfg.method == "dsgd")
    # The size-adaptive fp16 tier rewrites the per-bucket plan before any
    # geometry (EF row split, wire offsets) is derived from it.
    bits = size_adaptive_plan(cfg, ts.bits_plan, bp.sizes, ts.fp16_threshold)
    # Split each bucket's EF row into the residual prefix and the codec-
    # opaque aux tail (``state_extra``; quantizer rows pass through whole,
    # keeping those graphs unchanged).
    cfgs = sc._bucket_cfgs(cfg, bp.n_buckets, bits)
    extras = [sc.get_codec(c.method).state_extra(c, g.size)
              for c, g in zip(cfgs, buckets)]
    aux = None
    if ef is not None and any(extras):
        aux = [ef[b][g.size:] if x else None
               for b, (g, x) in enumerate(zip(buckets, extras))]
        ef = [ef[b][:g.size] if x else ef[b]
              for b, (g, x) in enumerate(zip(buckets, extras))]
    stats = None
    if compressed or tstate is not None:
        corrected, stats = [], []
        for b, g in enumerate(buckets):
            c, st = adaptive_telemetry.correct_stats(
                g, ef[b] if ef is not None else None, use_pallas=cfg.use_pallas)
            corrected.append(c)
            stats.append(st)
        buckets = corrected
    new_t = None
    if tstate is not None:
        new_t = adaptive_telemetry.update_telemetry(
            tstate, buckets, decay=ts.adaptive.ema, use_pallas=cfg.use_pallas,
            stats=stats)
    if not compressed:
        if live is None:
            means = [jax.lax.pmean(b, dp) for b in buckets]
        else:
            # Uncompressed elastic dsgd: zero dead contributions inside the
            # same per-bucket pmean, renormalize over the live count — the
            # collective count (one pmean per bucket) is unchanged.
            n = compat.axis_size(dp)
            self_live = live[compat.flat_axis_index(dp)]
            scale = jnp.float32(n) / jnp.maximum(jnp.sum(live), jnp.float32(1.0))
            means = [jax.lax.pmean(b * self_live, dp) * scale for b in buckets]
        resids = None
    elif ts.sync == "faithful":
        means, resids = sc.bucketed_faithful_ring_mean(cfg, buckets, dp, key,
                                                       cfg.use_pallas, bits, stats, aux,
                                                       live)
    elif ts.sync == "two_phase" or len(dp) == 1:
        means, resids = sc.bucketed_two_phase_mean(cfg, buckets, dp, key,
                                                   cfg.use_pallas, bits, stats, aux,
                                                   live)
    else:
        means, resids = sc.bucketed_hierarchical_mean(cfg, buckets, dp, key,
                                                      cfg.use_pallas, bits, stats, aux,
                                                      live)
    shapes = [v.shape for v in vals]
    mean_leaves = compressors.bucket_split(means, bp, shapes)
    cm = None
    if ts.metrics_compression:
        # The fused encode's residual IS the realized quantization error of
        # this peer's own transmission, so the metric sums cost no extra
        # collective — the model-axis reduction is fused with the gnorm
        # psum by the caller.
        cm = obs_metrics.local_sums(ts, cfgs, buckets, stats, resids, ef,
                                    compressed)
    if not ts.error_feedback:
        resids = None
    return mean_leaves, resids, new_t, means, cm


def ef_bucket_spec(mesh) -> P:
    """PartitionSpec of one bucket-resident EF state array.

    Axis 0 is the client (data/pod) axis; axis 1 concatenates the model
    shards' local buckets (each shard round-trips its own row segment
    through the sync region, so the cross-shard element order is opaque and
    never reinterpreted leaf-wise).
    """
    dp = sharding.manual_axes(mesh)
    rest = tuple(a for a in mesh.axis_names if a not in dp)
    return P(dp if dp else None, rest if rest else None)


def _make_sync_fn(ts: TrainStepConfig, mesh, pspecs: Any, grads_like: Any):
    """Fully-manual shard_map averaging stacked per-client grads.

    Input leaves are (n_dp, *param_shape), client axis over the data/pod
    axes; output leaves are the synced mean with the param's model sharding,
    replicated over data/pod (every mode leaves all peers with identical
    bytes, so the unchecked replication in ``out_specs`` is sound).

    With ``ts.error_feedback`` the callable takes and returns the
    **bucket-resident** EF state (a tuple of stacked per-client bucket
    arrays, :func:`init_ef_state`) alongside the grads; with ``ts.adaptive``
    the stacked per-client telemetry state follows it; with
    ``ts.metrics_gnorm`` the global gradient norm (computed from the flat
    mean buckets, ``psum`` over the model axes) follows; with
    ``ts.metrics_compression`` the per-bucket
    :class:`~repro.obs.metrics.CompressionMetrics` pytree (leaves stacked
    per data peer) is the last output:
    ``sync_fn(grads, key[, live][, ef][, tstate]) ->
    (mean[, new_ef][, new_tstate][, gnorm][, metrics])``.

    Migration note (elastic): with ``ts.elastic`` set the replicated
    ``(n_dp,)`` float live mask is a positional input directly after the
    key — callers holding the raw sync fn must thread it like the key
    (``make_train_step`` computes it in-graph from the step counter).

    Collective accounting: the compression metrics share ONE vectorized
    ``psum`` over the model axes with the gnorm scalar, so enabling them
    never changes the traced collective count; with both metrics off the
    sync body is byte-identical to the metrics-free graph.
    """
    dp = sharding.manual_axes(mesh)
    model_axes = tuple(a for a in mesh.axis_names if a not in dp)
    n_model = 1
    for a in model_axes:
        n_model *= mesh.shape[a]

    def in_spec(x, spec):
        return P(dp, *_auto_only_entries(spec, mesh))

    def out_spec(x, spec):
        return P(*_auto_only_entries(spec, mesh))

    g_in = _tree_map_with_specs(in_spec, grads_like, pspecs)
    g_out = _tree_map_with_specs(out_spec, grads_like, pspecs)

    def sync(stacked, key, *extras):
        idx = 0
        ef = tstate = live = None
        if ts.elastic is not None:
            # the replicated (n_dp,) live mask rides the signature like the
            # key: computed in-graph by the caller (_step), no collective
            live, idx = extras[idx], idx + 1
        if ts.error_feedback:
            ef, idx = extras[idx], idx + 1
        if ts.adaptive is not None:
            tstate = extras[idx]
        leaves, treedef = jax.tree.flatten(stacked)
        vals = [g[0] for g in leaves]
        if ts.bucket_mb > 0:
            t_in = None if tstate is None else jax.tree.map(lambda x: x[0], tstate)
            ef_in = None if ef is None else [e[0] for e in ef]
            out, resid, new_t, gsrc, cm = _sync_buckets(ts, vals, key, dp, ef_in, t_in,
                                                        live)
        else:
            out = [_sync_leaf(ts, g, jax.random.fold_in(key, i), dp)
                   for i, g in enumerate(vals)]
            resid, new_t, gsrc, cm = None, None, out, None
        result = [jax.tree.unflatten(treedef, out)]
        if ts.error_feedback:
            result.append(tuple(r[None] for r in resid))
        if ts.adaptive is not None:
            result.append(jax.tree.map(lambda x: x[None], new_t))
        gsq = None
        if ts.metrics_gnorm:
            gsq = sum(jnp.sum(jnp.square(m.astype(jnp.float32))) for m in gsrc)
        if cm is not None:
            # One fused model-axis psum for the metric sums AND the gnorm
            # scalar: the collective count matches the metrics-off graph.
            sums, static = cm
            vec = sums.reshape(-1)
            if gsq is not None:
                vec = jnp.concatenate([vec, gsq[None]])
            if model_axes:
                vec = jax.lax.psum(vec, model_axes)
            if gsq is not None:
                gsq, vec = vec[-1], vec[:-1]
            cm = obs_metrics.finalize(vec.reshape(sums.shape), static, n_model)
        elif gsq is not None and model_axes:
            gsq = jax.lax.psum(gsq, model_axes)
        if gsq is not None:
            result.append(jnp.sqrt(gsq))
        if cm is not None:
            result.append(jax.tree.map(lambda x: x[None], cm))
        return tuple(result) if len(result) > 1 else result[0]

    in_specs = [g_in, P()]
    out_specs = [g_out]
    if ts.elastic is not None:
        in_specs.append(P())  # the replicated live mask
    if ts.error_feedback:
        in_specs.append(ef_bucket_spec(mesh))
        out_specs.append(ef_bucket_spec(mesh))
    if ts.adaptive is not None:
        t_spec = jax.tree.map(lambda _: P(dp), adaptive_telemetry.init_telemetry(1))
        in_specs.append(t_spec)
        out_specs.append(t_spec)
    if ts.metrics_gnorm:
        out_specs.append(P())
    if ts.metrics_compression:
        out_specs.append(obs_metrics.CompressionMetrics(
            *(P(dp) for _ in obs_metrics.CompressionMetrics._fields)))
    return jax.shard_map(
        sync, mesh=mesh, in_specs=tuple(in_specs),
        out_specs=tuple(out_specs) if len(out_specs) > 1 else out_specs[0],
        axis_names=set(mesh.axis_names), check_vma=False,
    )


# ---------------------------------------------------------------------------
# Streamed (per-unit) backward schedule
# ---------------------------------------------------------------------------


def _streamed_loss_and_grads(cfg, full_params, batch):
    """Layer-streamed loss + grads: forward scan saving unit inputs, reverse
    scan of per-unit VJPs.  Same math as ``grad(loss_fn)``, but only one
    unit's backward graph is live at a time."""
    outer = transformer.outer_params(full_params)
    positions = transformer._positions_for(cfg, batch)

    h0, embed_vjp = jax.vjp(lambda o: transformer.embed_fn(cfg, o, batch), outer)

    def fwd(h, p_u):
        h2, aux = transformer.unit_fn(cfg, p_u, h, positions)
        return h2, (h, aux)

    h_final, (h_ins, auxs) = jax.lax.scan(fwd, h0, full_params["blocks"])
    aux_total = jnp.sum(auxs)

    xent, head_vjp = jax.vjp(lambda o, h: transformer.head_fn(cfg, o, h, batch), outer, h_final)
    loss = xent + transformer.AUX_LOSS_WEIGHT * aux_total
    g_outer_head, g_h = head_vjp(jnp.float32(1.0))

    def bwd(g_h_c, inp):
        p_u, h_in = inp
        _, unit_vjp = jax.vjp(lambda p, h: transformer.unit_fn(cfg, p, h, positions), p_u, h_in)
        g_p, g_h_in = unit_vjp((g_h_c, jnp.float32(transformer.AUX_LOSS_WEIGHT)))
        return g_h_in, g_p

    g_h0, g_blocks = jax.lax.scan(bwd, g_h, (full_params["blocks"], h_ins), reverse=True)
    (g_outer_embed,) = embed_vjp(g_h0)
    g_outer = jax.tree.map(jnp.add, g_outer_head, g_outer_embed)
    grads = dict(g_outer)
    grads["blocks"] = g_blocks
    return loss, grads


# ---------------------------------------------------------------------------
# make_train_step
# ---------------------------------------------------------------------------


def _client_batch(batch: Any, n_clients: int) -> tuple[Any, Any]:
    """Split the global batch into per-client slices + the vmap in_axes."""

    def split(x, batch_dim: int):
        if x is None:
            return None
        b = x.shape[batch_dim]
        return x.reshape(x.shape[:batch_dim] + (n_clients, b // n_clients) + x.shape[batch_dim + 1:])

    pos_dim = 1 if (batch.positions is not None and batch.positions.ndim == 3) else 0
    split_batch = transformer.Batch(
        tokens=split(batch.tokens, 0),
        labels=split(batch.labels, 0),
        positions=split(batch.positions, pos_dim),
        patches=split(batch.patches, 0),
        frames=split(batch.frames, 0),
    )
    axes = transformer.Batch(
        tokens=0, labels=0,
        positions=(pos_dim if batch.positions is not None else None),
        patches=0 if batch.patches is not None else None,
        frames=0 if batch.frames is not None else None,
    )
    return split_batch, axes


def make_train_step(
    cfg,
    mesh,
    logical: Any,
    opt: Optimizer,
    ts: TrainStepConfig,
    batch: Any,
    opt_state_like: Any = None,
    params_like: Any = None,
):
    """Build ``(step_fn, pspecs)`` for one training configuration.

    ``step_fn(params, opt_state, batch, step) -> (params, opt_state, metrics)``
    with ``metrics = {"loss": (n_dp,), "gnorm": (n_dp,)}`` (global values,
    replicated per data shard; ``gnorm`` is computed from the flat mean
    buckets inside the sync region and omitted under
    ``ts.metrics_gnorm=False``).  ``pspecs`` is the parameter PartitionSpec
    tree the caller uses for ``device_put``.

    The state arguments (params, optimizer state and, when present, the EF
    and telemetry states) are donated: callers rebind them to the step's
    outputs and never read the passed-in arrays again.

    Metrics contract (pinned by ``tests/test_obs.py``): ``metrics["loss"]``
    is ALWAYS shape ``(max(n_dp, 1),)`` float32, under every sync mode;
    ``metrics["gnorm"]`` has the same shape/dtype and is present iff
    ``ts.metrics_gnorm``.  With ``ts.metrics_compression`` (and a mesh with
    data axes) ``metrics["compression"]`` is a
    :class:`repro.obs.metrics.CompressionMetrics` pytree with
    ``(n_dp, n_buckets)`` leaves — row ``j`` is data peer ``j``'s own
    encode, model-shard reduced inside the sync region at zero extra
    collective cost (the reduction shares the gnorm psum).

    With ``ts.error_feedback`` the bucket-resident EF residual is an
    explicit extra pytree in the step signature — ``step_fn(params,
    opt_state, ef_state, batch, step) -> (params, opt_state, ef_state,
    metrics)`` — initialized with :func:`init_ef_state(params_like, mesh,
    pspecs, ts)`.  With ``ts.adaptive`` the telemetry state is one
    more explicit pytree in the slot after the EF residual (or in its place
    when EF is off) — ``step_fn(params, opt_state[, ef_state], tstate,
    batch, step) -> (params, opt_state[, ef_state], tstate, metrics)`` —
    initialized with :func:`init_telemetry_state`.
    """
    if params_like is None:
        # repro: allow REPRO204 (eval_shape aval-only trace; value never used)
        params_like = jax.eval_shape(lambda: transformer.init_lm(jax.random.key(0), cfg)[0])
    if opt_state_like is None:
        opt_state_like = jax.eval_shape(opt.init, params_like)

    pspecs = sharding.param_pspecs(logical, mesh, cfg.fsdp, params_like)
    dp = sharding.manual_axes(mesh)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    B = batch.tokens.shape[0]
    if dp and B % n_dp:
        raise ValueError(
            f"global batch {B} must be divisible by the {n_dp} data shards of mesh "
            f"{dict(zip(mesh.axis_names, mesh.devices.shape))}")
    n_clients = n_dp if dp else 1

    rules = sharding.activation_rules(mesh, manual_data=True)
    o_specs = _opt_specs(opt_state_like, params_like, pspecs)
    grads_like = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((n_clients,) + x.shape, jnp.float32), params_like)
    if ts.error_feedback and not dp:
        raise ValueError("error_feedback needs data-parallel mesh axes (the sync path)")
    sync_fn = _make_sync_fn(ts, mesh, pspecs, grads_like) if dp else None
    streamed = ts.streamed and not cfg.enc_dec

    def constrain(tree, spec_tree):
        return _tree_map_with_specs(
            lambda x, s: jax.lax.with_sharding_constraint(x, NamedSharding(mesh, s)),
            tree, spec_tree)

    def constrain_client_grads(grads):
        # One client per data shard on axis 0; keep each leaf's model-parallel
        # sharding (same entries the sync shard_map's in_specs use) so the
        # codec quantizes model-local slices without a pre-sync all-gather.
        def one(g, spec):
            entries = _auto_only_entries(spec, mesh)
            return jax.lax.with_sharding_constraint(
                g, NamedSharding(mesh, P(dp if dp else None, *entries)))

        return _tree_map_with_specs(one, grads, pspecs)

    def constrain_ef(ef):
        # Bucket-resident EF state: every bucket array shares one spec (the
        # sync shard_map's in/out spec), pinned so the residual stays put
        # between steps.
        sh = NamedSharding(mesh, ef_bucket_spec(mesh))
        return tuple(jax.lax.with_sharding_constraint(e, sh) for e in ef)

    adaptive = ts.adaptive is not None
    if adaptive and not dp:
        raise ValueError("adaptive telemetry needs data-parallel mesh axes (the sync path)")

    def _step(params, opt_state, ef_state, tstate, batch_g, step):
        with sharding.axis_rules(mesh, rules):
            cbatch, caxes = _client_batch(batch_g, n_clients)

            def one_client(p, b):
                if streamed:
                    return _streamed_loss_and_grads(cfg, p, b)
                return jax.value_and_grad(lambda q: transformer.loss_fn(cfg, q, b))(p)

            losses, grads = jax.vmap(one_client, in_axes=(None, caxes))(params, cbatch)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
            # pin one client per data shard before the manual sync region
            grads = constrain_client_grads(grads)
            key = jax.random.fold_in(jax.random.key(_KEY_SEED), step)
            new_ef, new_t, gnorm, cmetrics = ef_state, tstate, None, None
            live = None
            if ts.elastic is not None and sync_fn is not None:
                live = live_mask(ts.elastic, step, n_dp)
            if sync_fn is not None:
                args = [grads, key]
                if live is not None:
                    args.append(live)
                if ts.error_feedback:
                    # bucket-resident EF state rides straight into the sync
                    # shard_map — no leaf-spec constraint round-trip
                    args.append(ef_state)
                if adaptive:
                    args.append(tstate)
                res = sync_fn(*args)
                n_extra = (int(ts.error_feedback) + int(adaptive)
                           + int(ts.metrics_gnorm) + int(ts.metrics_compression))
                if n_extra:
                    res = list(res)
                    g_mean = res.pop(0)
                    if ts.error_feedback:
                        new_ef = constrain_ef(res.pop(0))
                    if adaptive:
                        new_t = res.pop(0)
                    if ts.metrics_gnorm:
                        gnorm = res.pop(0)
                    if ts.metrics_compression:
                        cmetrics = res.pop(0)
                else:
                    g_mean = res
            else:
                g_mean = jax.tree.map(lambda g: jnp.mean(g, axis=0), grads)
            if ts.metrics_gnorm and gnorm is None:
                gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(g_mean)))
            with jax.named_scope("obs.optimizer"):
                new_params, new_opt = opt.update(params, g_mean, opt_state, step)
            new_params = constrain(new_params, pspecs)
            new_opt = constrain(new_opt, o_specs)
        loss = jnp.mean(losses)
        metrics = {"loss": jnp.full((max(n_dp, 1),), loss, jnp.float32)}
        if ts.metrics_gnorm:
            metrics["gnorm"] = jnp.full((max(n_dp, 1),), gnorm, jnp.float32)
        if cmetrics is not None:
            metrics["compression"] = cmetrics
        if live is not None:
            metrics["live"] = live
            metrics["live_count"] = jnp.full((max(n_dp, 1),), jnp.sum(live), jnp.float32)
        return new_params, new_opt, new_ef, new_t, metrics

    # The state arguments (params, optimizer state, EF, telemetry) are
    # donated: each step's outputs reuse their buffers, so the step holds
    # one copy of the training state rather than two.
    if ts.error_feedback and adaptive:
        @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
        def step_fn(params, opt_state, ef_state, tstate, batch_g, step):
            return _step(params, opt_state, ef_state, tstate, batch_g, step)
    elif ts.error_feedback:
        @partial(jax.jit, donate_argnums=(0, 1, 2))
        def step_fn(params, opt_state, ef_state, batch_g, step):
            p, o, e, _, m = _step(params, opt_state, ef_state, None, batch_g, step)
            return p, o, e, m
    elif adaptive:
        @partial(jax.jit, donate_argnums=(0, 1, 2))
        def step_fn(params, opt_state, tstate, batch_g, step):
            p, o, _, t, m = _step(params, opt_state, None, tstate, batch_g, step)
            return p, o, t, m
    else:
        @partial(jax.jit, donate_argnums=(0, 1))
        def step_fn(params, opt_state, batch_g, step):
            p, o, _, _, m = _step(params, opt_state, None, None, batch_g, step)
            return p, o, m

    return step_fn, pspecs


def init_ef_state(params_like: Any, mesh, pspecs: Any, ts: TrainStepConfig) -> Any:
    """Zero **bucket-resident** EF residual.

    One stacked fp32 array per codec bucket: axis 0 is the client (data/pod
    shard) row, axis 1 concatenates the model shards' local buckets
    (:func:`local_bucket_sizes` each).  This is exactly the layout the
    fused encode's residual comes back in, so the state round-trips the
    step signature with zero per-step reshaping — the pre-refactor leaf
    pytree (``init_ef_state(params_like, mesh)``) required a
    ``bucket_concat``/``bucket_split`` plus a leaf-spec constraint round
    trip every step.  Callers migrating from that layout now pass the
    ``pspecs`` returned by :func:`make_train_step` and the step's
    ``TrainStepConfig`` (mirroring :func:`init_telemetry_state`).
    """
    sizes = local_bucket_sizes(params_like, mesh, pspecs, ts)
    # Rank-based codecs carry extra per-shard state (e.g. the warm-started
    # powersgd Q factor) appended after the residual; quantizer buckets keep
    # their exact pre-registry row width.  The fp16 tier rewrite must be
    # applied here too so the rows match what the sync region splits off.
    plan = size_adaptive_plan(ts.compressor, ts.bits_plan, sizes, ts.fp16_threshold)
    state_sizes = sc.bucket_state_sizes(ts.compressor, sizes, plan)
    dp = sharding.manual_axes(mesh)
    n = 1
    for a in dp:
        n *= mesh.shape[a]
    n_model = 1
    for a in mesh.axis_names:
        if a not in dp:
            n_model *= mesh.shape[a]
    return tuple(jnp.zeros((max(n, 1), n_model * s), jnp.float32) for s in state_sizes)


def local_bucket_sizes(params_like: Any, mesh, pspecs: Any, ts: TrainStepConfig) -> tuple[int, ...]:
    """Element counts of the codec's buckets as seen *inside* the sync region.

    Reproduces the trace-time bucket plan of :func:`_sync_buckets`: each
    gradient leaf is shrunk to its model-parallel local shard (the manual
    data/pod axes are the peer axis, not a size divisor) and the local sizes
    are coalesced by ``core.compressors.plan_buckets``.  The adaptive
    controller sizes its telemetry state and bit plans from this.
    """
    leaves = jax.tree.leaves(params_like)
    specs = jax.tree.leaves(pspecs, is_leaf=lambda s: isinstance(s, P))
    sizes = []
    for x, spec in zip(leaves, specs):
        entries = _auto_only_entries(spec, mesh)
        size = 1
        for d, dim in enumerate(tuple(x.shape)):
            axes = entries[d] if d < len(entries) else None
            axes = axes if isinstance(axes, tuple) else (axes,) if axes is not None else ()
            div = 1
            for a in axes:
                div *= mesh.shape[a]
            size *= dim // div
        sizes.append(size)
    return compressors.plan_buckets(sizes, ts.bucket_elements).sizes


def init_telemetry_state(params_like: Any, mesh, pspecs: Any, ts: TrainStepConfig) -> Any:
    """Zero telemetry: one stacked row per client over the bucket-plan-sized
    :class:`repro.adaptive.TelemetryState` (mirrors :func:`init_ef_state`)."""
    n_buckets = len(local_bucket_sizes(params_like, mesh, pspecs, ts))
    dp = sharding.manual_axes(mesh)
    n = 1
    for a in dp:
        n *= mesh.shape[a]
    t0 = adaptive_telemetry.init_telemetry(n_buckets)
    return jax.tree.map(lambda x: jnp.tile(x[None], (max(n, 1),) + (1,) * x.ndim), t0)
