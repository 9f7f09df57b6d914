"""Observability-layer tests: in-graph compression metrics vs the reference
replay, collective-count neutrality, the metrics shape contract, and the
host-side sinks/spans/drift/report machinery.

Multi-device cases run in subprocesses (fake host devices) so the main
pytest process keeps a single CPU device, mirroring ``test_dist.py``.
"""
import json

import numpy as np
import pytest
from multidevice import run_with_devices


# ---------------------------------------------------------------------------
# In-graph metrics: bitwise pinning against the reference replay
# ---------------------------------------------------------------------------


def test_metrics_bitwise_vs_reference():
    """The CompressionMetrics pytree a (2,2) pod×data mesh emits must be
    bit-identical to the ``dist.reference`` replay (EF + adaptive telemetry
    on, so every metric input — stats, residuals, incoming EF — is live)."""
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P, AxisType
from repro.adaptive.controller import AdaptiveConfig
from repro.adaptive.telemetry import init_telemetry
from repro.core.compressors import CompressorConfig, plan_buckets
from repro.dist import reference, sharded_codec as sc, sharding
from repro.dist.train_step import TrainStepConfig, _make_sync_fn

mesh = jax.make_mesh((2, 2), ("pod", "data"), axis_types=(AxisType.Auto,) * 2)
dp = sharding.manual_axes(mesh)
dp_sizes = tuple(mesh.shape[a] for a in dp)
n = 4

ts = TrainStepConfig(sync="faithful", bucket_mb=1.0 / 64.0,
                     compressor=CompressorConfig(method="tnqsgd", bits=3),
                     error_feedback=True, adaptive=AdaptiveConfig(ema=0.9),
                     metrics_gnorm=True, metrics_compression=True)

leaf_shapes = [(64, 48), (37, 61), (2048,), (999,)]
key0 = jax.random.key(5)
leaves = [
    (jax.random.normal(jax.random.fold_in(key0, i), (n,) + s) * 0.05 * (i + 1)
     ).astype(jnp.float32)
    for i, s in enumerate(leaf_shapes)
]
BP = plan_buckets([int(np.prod(s)) for s in leaf_shapes], 4096)
st_sizes = sc.bucket_state_sizes(ts.compressor, BP.sizes, ts.bits_plan)
ef = [
    (jax.random.normal(jax.random.fold_in(key0, 100 + b), (n, st)) * 0.01
     ).astype(jnp.float32)
    for b, st in enumerate(st_sizes)
]
t0 = jax.tree.map(lambda x: jnp.tile(x[None], (n,) + (1,) * x.ndim),
                  init_telemetry(BP.n_buckets))
skey = jax.random.key(123)

pspecs = [P() for _ in leaves]
sync_fn = _make_sync_fn(ts, mesh, pspecs, list(leaves))
mean, new_ef, new_t, gnorm, cm = jax.jit(sync_fn)(list(leaves), skey, tuple(ef), t0)

w_mean, w_ef, w_t, w_cm = jax.jit(
    lambda key, t, ls, e: reference.reference_sync_state(
        ts, list(ls), dp_sizes, key, ef=list(e), tstate=t)
)(skey, t0, tuple(leaves), tuple(ef))

for f, got, want in zip(cm._fields, cm, w_cm):
    got = np.asarray(got)
    assert got.shape == (n, BP.n_buckets), (f, got.shape)
    np.testing.assert_array_equal(got, np.asarray(want), err_msg=f"metric {f}")
    assert np.all(np.isfinite(got)), (f, got)
# sanity semantics: realized >= 0, clip fraction in [0,1], positive wire
assert np.all(np.asarray(cm.realized_mse) >= 0.0)
assert np.all((np.asarray(cm.clip_frac) >= 0.0) & (np.asarray(cm.clip_frac) <= 1.0))
assert np.all(np.asarray(cm.wire_bytes) > 0.0)
assert np.all(np.asarray(cm.predicted_mse) > 0.0)
# the metrics ride-along must not perturb the sync outputs themselves
for g, w in zip(mean, w_mean):
    np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
print("ALL_OK")
""", n=4)
    assert "ALL_OK" in out


def test_collective_count_unchanged():
    """Enabling ``metrics_compression`` must not change the traced collective
    count on a model-sharded mesh: the metric sums share the one gnorm psum.
    (With ``metrics_gnorm=False`` there is no psum to fuse with; the metrics
    then cost exactly one — pinned here so it cannot silently grow.)"""
    out = run_with_devices("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, AxisType
from repro.analysis.jaxpr_lint import count_collectives
from repro.core.compressors import CompressorConfig
from repro.dist.train_step import TrainStepConfig, _make_sync_fn

mesh = jax.make_mesh((2, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
leaf_shapes = [(64, 48), (2048,), (999,)]
leaves = [jnp.ones((2,) + s, jnp.float32) for s in leaf_shapes]
pspecs = [P() for _ in leaves]
skey = jax.random.key(0)


def counts(sync, metrics_compression, metrics_gnorm=True):
    ts = TrainStepConfig(sync=sync, bucket_mb=1.0 / 64.0,
                         compressor=CompressorConfig(method="tnqsgd", bits=3),
                         metrics_gnorm=metrics_gnorm,
                         metrics_compression=metrics_compression)
    fn = _make_sync_fn(ts, mesh, pspecs, list(leaves))
    return count_collectives(jax.make_jaxpr(fn)(list(leaves), skey))


for sync in ("dsgd", "two_phase", "hierarchical", "faithful"):
    off, on = counts(sync, False), counts(sync, True)
    assert on == off, (sync, dict(off), dict(on))
    off_ng, on_ng = counts(sync, False, metrics_gnorm=False), counts(sync, True, metrics_gnorm=False)
    delta = on_ng - off_ng
    assert dict(delta) in ({}, {"psum": 1}), (sync, dict(delta))
print("ALL_OK")
""", n=4)
    assert "ALL_OK" in out


def test_metrics_contract_shapes():
    """Pin the ``make_train_step`` metrics contract (documented in its
    docstring): ``metrics["loss"]`` is always ``(n_dp,)`` float32 under every
    sync mode; ``gnorm`` matches it and appears iff ``metrics_gnorm``;
    compression leaves are ``(n_dp, n_buckets)``."""
    out = run_with_devices("""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, AxisType
from repro.configs import get_config, reduced
from repro.models import init_lm
from repro.data.synthetic import lm_batch
from repro.optim.optimizers import momentum_sgd
from repro.dist.train_step import make_train_step, TrainStepConfig
from repro.core.compressors import CompressorConfig

mesh = jax.make_mesh((2, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
cfg = reduced(get_config("llama3.2-1b")).replace(fsdp=True)
params0, logical = init_lm(jax.random.key(0), cfg)
opt = momentum_sgd(lr=0.05)
batch = lm_batch(cfg, jnp.uint32(0), 8, 128)
n_dp = 2

cases = [("dsgd", True, False), ("two_phase", True, False),
         ("hierarchical", False, False), ("faithful", True, True)]
for sync, gnorm, comp in cases:
    ts = TrainStepConfig(sync=sync, compressor=CompressorConfig(method="tnqsgd", bits=4),
                         metrics_gnorm=gnorm, metrics_compression=comp)
    step_fn, pspecs = make_train_step(cfg, mesh, logical, opt, ts, batch)
    sh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs, is_leaf=lambda x: isinstance(x, P))
    p = jax.device_put(jax.tree.map(jnp.copy, params0), sh)
    o = jax.tree.map(jnp.zeros_like, p)
    p, o, m = step_fn(p, o, batch, jnp.uint32(0))
    assert m["loss"].shape == (n_dp,) and m["loss"].dtype == jnp.float32, (sync, m["loss"])
    assert ("gnorm" in m) == gnorm, (sync, sorted(m))
    if gnorm:
        assert m["gnorm"].shape == (n_dp,) and m["gnorm"].dtype == jnp.float32
    assert ("compression" in m) == comp, (sync, sorted(m))
    if comp:
        cm = m["compression"]
        B = cm.bits.shape[-1]
        assert B >= 1
        for f, leaf in zip(cm._fields, cm):
            assert leaf.shape == (n_dp, B), (sync, f, leaf.shape)
    print("OK", sync)
print("ALL_OK")
""", n=4)
    assert "ALL_OK" in out


# ---------------------------------------------------------------------------
# Predicted-vs-realized calibration (no devices: reference replay)
# ---------------------------------------------------------------------------


def test_qsgd_realized_tracks_predicted():
    """On Gaussian gradients the realized qsgd quantization MSE must be
    non-negative and within a small constant factor of the predicted E_TQ
    (both ≈ α²/s² scalings; the band absorbs the histogram tail fit)."""
    import jax
    import jax.numpy as jnp

    from repro.core.compressors import CompressorConfig
    from repro.dist.reference import reference_sync_state
    from repro.dist.train_step import TrainStepConfig

    ts = TrainStepConfig(sync="faithful", bucket_mb=1.0 / 64.0,
                         compressor=CompressorConfig(method="qsgd", bits=4),
                         error_feedback=True, metrics_compression=True)
    n = 2
    key0 = jax.random.key(7)
    leaves = [(jax.random.normal(jax.random.fold_in(key0, i), (n, 4096)) * 0.1
               ).astype(jnp.float32) for i in range(2)]
    _, _, _, cm = jax.jit(lambda k, ls: reference_sync_state(ts, list(ls), (n,), k)
                          )(jax.random.key(3), tuple(leaves))
    realized = np.asarray(cm.realized_mse)
    predicted = np.asarray(cm.predicted_mse)
    assert np.all(realized >= 0.0)
    assert np.all(predicted > 0.0)
    ratio = realized / predicted
    assert np.all((ratio > 0.2) & (ratio < 5.0)), ratio


# ---------------------------------------------------------------------------
# Host-side machinery: sink, spans, drift, report
# ---------------------------------------------------------------------------


def _fake_metrics(bits=4, realized=1.0, predicted=1.0):
    from repro.obs import CompressionMetrics
    mk = lambda v, dt=np.float32: np.asarray([[v]], dt)
    return CompressionMetrics(
        bits=mk(bits, np.int32), rank=mk(0, np.int32), alpha=mk(0.5),
        clip_frac=mk(0.01), ef_norm=mk(0.2), wire_bytes=mk(128.0),
        realized_mse=mk(realized), predicted_mse=mk(predicted))


def test_jsonl_sink_roundtrip_and_warnings(tmp_path, capsys):
    from repro.obs import JsonlSink, metrics_event, read_events

    path = tmp_path / "events.jsonl"
    with JsonlSink(path, flush_every=2) as sink:
        for step in range(3):
            sink.write(metrics_event(step, _fake_metrics(realized=float(step))))
    assert sink.n_written == 3
    # corrupt the log: one malformed line, one schema-versioned stranger
    with path.open("a") as fh:
        fh.write("{not json\n")
        fh.write(json.dumps({"v": 99, "kind": "metrics"}) + "\n")
    events = read_events(tmp_path)
    err = capsys.readouterr().err
    assert len(events) == 3
    assert "malformed" in err and str(path) in err
    assert "unknown schema" in err
    assert events[0]["buckets"][0]["bits"] == 4
    assert events[2]["buckets"][0]["realized_mse"] == 2.0


def test_ema_and_csv_export(tmp_path):
    from repro.obs import EmaAggregator, export_csv, metrics_event

    events = [metrics_event(i, _fake_metrics(realized=float(i))) for i in range(4)]
    ema = EmaAggregator(decay=0.5)
    for ev in events:
        ema.update(ev)
    rows = ema.summary()
    assert len(rows) == 1 and rows[0]["bucket"] == 0
    # EMA of 0,1,2,3 at decay .5: 0, .5, 1.25, 2.125
    assert rows[0]["realized_mse"] == pytest.approx(2.125)
    csv_path = tmp_path / "metrics.csv"
    assert export_csv(events, csv_path) == 4
    lines = csv_path.read_text().strip().splitlines()
    assert lines[0].startswith("step,bucket,bits,")
    assert len(lines) == 5


def test_span_recorder(tmp_path):
    from repro.obs import JsonlSink, SpanRecorder, read_events

    ticks = iter([0.0, 1.0, 2.0, 2.25])
    sink = JsonlSink(tmp_path / "spans.jsonl", flush_every=1)
    rec = SpanRecorder(sink=sink, clock=lambda: next(ticks))
    with rec.span("train.step", step=0):
        pass
    with rec.span("train.step", step=1):
        pass
    s = rec.summary()["train.step"]
    assert s["count"] == 2
    assert s["total_s"] == pytest.approx(1.25)
    assert s["max_s"] == pytest.approx(1.0)
    evs = read_events(tmp_path / "spans.jsonl")
    assert [e["kind"] for e in evs] == ["span", "span"]
    assert evs[1]["dur_s"] == pytest.approx(0.25) and evs[1]["step"] == 1


def test_drift_monitor_warns(tmp_path):
    from repro.core.distributions import GAMMA_MAX, GAMMA_MIN
    from repro.obs import DriftMonitor, JsonlSink, ObsDriftWarning, read_events

    sink = JsonlSink(tmp_path / "drift.jsonl", flush_every=1)
    mon = DriftMonitor(sink=sink, ratio_threshold=4.0)
    with pytest.warns(ObsDriftWarning, match="railed outside the power-law"):
        evs = mon.check_tails([GAMMA_MIN, 4.0, GAMMA_MAX], step=10)
    assert [e.bucket for e in evs] == [0, 2]
    with pytest.warns(ObsDriftWarning, match="ratio"):
        evs = mon.check_ratio([10.0, 1.0, 5.0], [1.0, 1.0, 0.0], step=11)
    assert [e.bucket for e in evs] == [0]  # bucket 2 has no prediction: skipped
    assert len(mon.events) == 3
    assert [e["drift"] for e in read_events(tmp_path / "drift.jsonl")] == [
        "tail_regime", "tail_regime", "error_ratio"]
    quiet = DriftMonitor(warn=False)
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        quiet.check_tails([GAMMA_MIN])
    assert len(quiet.events) == 1


def test_report_summarize_and_cli(tmp_path, capsys):
    from repro.obs import JsonlSink, metrics_event, span_event
    from repro.obs.report import bucket_table, main, phase_table, summarize

    with JsonlSink(tmp_path / "events.jsonl") as sink:
        for i in range(3):
            sink.write(metrics_event(i, _fake_metrics(realized=5.0, predicted=1.0)))
            sink.write(span_event("train.step", 0.0, 0.1, step=i))
    events_dir = str(tmp_path)
    rc = main(["--dir", events_dir, "--json", str(tmp_path / "OBS.json"),
               "--csv", str(tmp_path / "rows.csv")])
    out = capsys.readouterr().out
    assert rc == 0
    assert "DRIFT" in out  # ratio 5 > default threshold 2
    assert "Phase breakdown" in out
    summary = json.loads((tmp_path / "OBS.json").read_text())
    assert summary["version"] == 1 and summary["n_steps"] == 3
    assert summary["flagged"] == [0]
    assert summary["phases"][0]["name"] == "train.step"
    assert summary["phases"][0]["count"] == 3
    assert (tmp_path / "rows.csv").exists()
    # rendered tables are well-formed markdown with one row per bucket/phase
    assert bucket_table(summary).count("\n") == 2
    assert phase_table(summary).count("\n") == 2
    assert main(["--dir", str(tmp_path / "empty")]) == 1


def test_launch_report_load_warns_on_malformed(tmp_path, capsys):
    """Satellite: ``launch.report.load`` must name unreadable records instead
    of silently swallowing them."""
    from repro.launch.report import load

    good = tmp_path / "a__train_4k__16x16.json"
    good.write_text(json.dumps({"arch": "a"}))
    bad = tmp_path / "b__train_4k__16x16.json"
    bad.write_text("{broken")
    recs = load(tmp_path)
    err = capsys.readouterr().err
    assert recs == [{"arch": "a"}]
    assert "warning" in err and str(bad) in err
