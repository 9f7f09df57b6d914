"""Streaming per-bucket gradient statistics for the adaptive controller.

``TelemetryState`` is an explicit state pytree threaded through
``make_train_step`` exactly like the EF residual: one stacked row per data
shard, updated inside the manual sync region from the same coalesced buckets
the codec quantizes (post error-feedback correction), with **zero extra
collectives** — peers accumulate their own statistics and the controller
merges the rows on the host at replan time (:func:`aggregate_peers`).

Per bucket the state carries an EMA of the fused one-pass statistics from
``kernels.stats`` (|g| histogram on fixed log2-spaced bins, per-bin sums of
ln|g|, a decayed max envelope, and first/second moments).  Because γ, ρ and
the quantile are ratios of co-scaled accumulators, the EMA debiasing factor
cancels and :func:`estimate_tails` needs no step correction.

The tail estimate snaps ``g_min`` to a histogram bin edge: the Hill sum over
the bins above that edge is then *exact* with respect to the histogram
(``Σ ln g_j − n_tail ln g_min``), trading ≤ one bin of quantile resolution
(0.25 octave at the default 128 bins) for an O(n) pass instead of the
full sort ``jnp.quantile`` runs in the offline fit.
"""
from __future__ import annotations

from collections.abc import Sequence
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.distributions import (
    EmpiricalDensity,
    PowerLawTail,
    density_from_histogram,
    tail_from_histogram,
)
from repro.kernels import stats as kstats

NUM_BINS = kstats.NUM_BINS


class TelemetryState(NamedTuple):
    """Per-bucket streaming statistics.  Leaves are stacked over buckets
    (leading axis B); the train step stacks one more leading axis per data
    shard, mirroring the EF residual layout."""

    counts: jax.Array    # (B, NUM_BINS) EMA histogram counts of |g|
    log_sums: jax.Array  # (B, NUM_BINS) EMA per-bin sums of ln|g|
    g_max: jax.Array     # (B,) decayed max-|g| envelope
    mean: jax.Array      # (B,) EMA of the bucket mean
    msq: jax.Array       # (B,) EMA of the bucket second moment
    steps: jax.Array     # () number of updates folded in


def init_telemetry(n_buckets: int) -> TelemetryState:
    return TelemetryState(
        counts=jnp.zeros((n_buckets, NUM_BINS), jnp.float32),
        log_sums=jnp.zeros((n_buckets, NUM_BINS), jnp.float32),
        g_max=jnp.zeros((n_buckets,), jnp.float32),
        mean=jnp.zeros((n_buckets,), jnp.float32),
        msq=jnp.zeros((n_buckets,), jnp.float32),
        steps=jnp.zeros((), jnp.float32),
    )


def _stats_jnp(g: jax.Array):
    """Vectorized single-pass jnp fallback for the fused kernel.

    Scatter-add histogram instead of the kernel's per-bin masked sums
    (``kernels.ref.bucket_stats_scatter``): safe under shard_map and
    O(n).  Counts/max are identical to the kernel;
    float sums may differ in the last bits (reduction order), which the EMA
    telemetry does not care about — the bit-exact contract is pinned
    between ``kernels.ops.bucket_stats`` and ``kernels.ref``.
    """
    from repro.kernels.ref import bucket_stats_scatter

    return bucket_stats_scatter(g)


def bucket_statistics(g: jax.Array, *, use_pallas: bool = False):
    """(counts, log_sums, g_max, g_sum, g_sumsq) for one flat bucket."""
    if use_pallas:
        from repro.kernels import ops as kops

        s = kops.bucket_stats(g)
        return s.counts, s.log_sums, s.g_max, s.g_sum, s.g_sumsq
    return _stats_jnp(g)


def correct_stats(g: jax.Array, e=None, *, use_pallas: bool = False):
    """One-pass EF correction + statistics of one flat gradient bucket.

    Returns ``(corrected, (counts, log_sums, g_max, g_sum, g_sumsq))`` with
    ``corrected = g + e`` (``g`` itself when ``e`` is None) and the stats of
    the *corrected* bucket — everything ``compressors.plan_from_stats`` and
    the telemetry EMA consume.  ``use_pallas`` selects the fused
    ``kernels.ops.ef_correct_stats`` VMEM pass; the fallback is the
    shard_map-safe scatter-add pass over ``g + e``.
    """
    if use_pallas:
        from repro.kernels import ops as kops

        if e is None:
            s = kops.bucket_stats(g)
            return g, (s.counts, s.log_sums, s.g_max, s.g_sum, s.g_sumsq)
        c, s = kops.ef_correct_stats(g, e)
        return c, (s.counts, s.log_sums, s.g_max, s.g_sum, s.g_sumsq)
    c = g if e is None else g + e
    return c, _stats_jnp(c)


def update_telemetry(
    state: TelemetryState,
    buckets: Sequence[jax.Array],
    *,
    decay: float = 0.9,
    use_pallas: bool = False,
    stats: Sequence | None = None,
) -> TelemetryState:
    """Fold one step's buckets into the EMA state (B must match).

    ``stats`` (one :func:`correct_stats`-shaped tuple per bucket) skips the
    statistics pass entirely — the train step hands over the stats the
    fused EF-correct kernel already produced, so the telemetry update costs
    zero extra HBM sweeps.
    """
    if len(buckets) != state.counts.shape[0]:
        raise ValueError(
            f"telemetry state has {state.counts.shape[0]} buckets, got {len(buckets)}")
    d = jnp.float32(decay)
    counts, log_sums, gmaxs, means, msqs = [], [], [], [], []
    for b, g in enumerate(buckets):
        c, ls, gm, gs, gq = (stats[b] if stats is not None
                             else bucket_statistics(g, use_pallas=use_pallas))
        n = jnp.float32(max(g.size, 1))
        counts.append(d * state.counts[b] + (1.0 - d) * c)
        log_sums.append(d * state.log_sums[b] + (1.0 - d) * ls)
        gmaxs.append(jnp.maximum(gm, d * state.g_max[b]))
        means.append(d * state.mean[b] + (1.0 - d) * gs / n)
        msqs.append(d * state.msq[b] + (1.0 - d) * gq / n)
    return TelemetryState(
        counts=jnp.stack(counts),
        log_sums=jnp.stack(log_sums),
        g_max=jnp.stack(gmaxs),
        mean=jnp.stack(means),
        msq=jnp.stack(msqs),
        steps=state.steps + 1.0,
    )


def aggregate_peers(state: TelemetryState) -> TelemetryState:
    """Merge the per-data-shard stacked rows (leading axis) into one state.

    Counts/log-sums add across peers, the max envelope joins with max, and
    the moments average — all on whatever backing the arrays have (device or
    host); called by the controller at replan time, never inside the step.
    """
    return TelemetryState(
        counts=jnp.sum(state.counts, axis=0),
        log_sums=jnp.sum(state.log_sums, axis=0),
        g_max=jnp.max(state.g_max, axis=0),
        mean=jnp.mean(state.mean, axis=0),
        msq=jnp.mean(state.msq, axis=0),
        steps=jnp.max(state.steps, axis=0),
    )


def estimate_densities(state: TelemetryState) -> list[EmpiricalDensity]:
    """Per-bucket piecewise-constant |g| densities from the EMA histogram.

    The same ``core.distributions.EmpiricalDensity`` contract the offline
    ``fit_empirical_density`` produces (two-sided density over |g| bins, here
    the telemetry's log2-spaced edges — ``_cum_integral`` handles non-uniform
    widths), so the ``core.optimal`` non-uniform α solvers and the
    ``core.theory`` Q_N error model run straight off telemetry.
    """
    edges = kstats.bin_edges()
    return [density_from_histogram(state.counts[b], edges)
            for b in range(state.counts.shape[0])]


def estimate_tails(state: TelemetryState, *, gmin_quantile: float = 0.9) -> PowerLawTail:
    """Histogram-based power-law tail fit per bucket (stacked PowerLawTail).

    ``g_min`` is the upper edge of the bin where the |g| CDF crosses
    ``gmin_quantile``; γ is the Hill estimator over the whole bins above it
    (suffix count / suffix ln-sum of the EMA accumulators), ρ the matching
    one-sided tail mass.  Mirrors ``core.distributions.fit_power_law_tail``
    without touching the raw gradients.
    """
    edges = kstats.bin_edges()
    return jax.vmap(
        lambda c, ls, gm: tail_from_histogram(c, ls, gm, edges,
                                              gmin_quantile=gmin_quantile)
    )(state.counts, state.log_sums, state.g_max)
