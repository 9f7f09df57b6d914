"""Observability overhead: in-graph metrics cost + collective neutrality.
CSV rows: obs,<case>,<us>,<derived>.

Two properties of ``TrainStepConfig.metrics_compression`` are measured:

- **wall-clock overhead** of the metric computation itself, timed through
  :func:`repro.dist.reference.reference_sync_state` (the single-device
  replica of the mesh sync, so the numbers isolate the codec + metric math
  from dispatch noise).  ``sync_metrics_on``'s derived column is the
  on/off time ratio — expected close to 1: the metric sums reuse the
  encode's residual and stats, and the α/E_TQ recomputation CSEs with the
  encode's own plan.
- **collective neutrality**, counted on a real (2,2) data×model mesh in a
  fake-device subprocess (mirroring ``adaptive_bench``): per sync mode the
  traced collective count with metrics on minus metrics off, asserted and
  reported as the derived column (must be 0 — the metric sums share the
  gnorm psum).

A third row times the host-side report pipeline (JSONL round-trip +
EMA summarize) over the synthetic event stream the first case produced.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp

from benchmarks.common import time_us
from repro.core.compressors import CompressorConfig
from repro.dist.reference import reference_sync_state
from repro.dist.train_step import TrainStepConfig
from repro.obs import metrics_event
from repro.obs.report import summarize

ROOT = pathlib.Path(__file__).resolve().parents[1]

LEAF_SHAPES = [(64, 48), (37, 61), (2048,), (999,)]
N_CLIENTS = 4

_COUNT_DEMO = """
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, AxisType
from repro.analysis.jaxpr_lint import count_collectives
from repro.core.compressors import CompressorConfig
from repro.dist.train_step import TrainStepConfig, _make_sync_fn

mesh = jax.make_mesh((2, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
leaves = [jnp.ones((2,) + s, jnp.float32) for s in [(64, 48), (2048,), (999,)]]
pspecs = [P() for _ in leaves]
key = jax.random.key(0)
for sync in ("dsgd", "two_phase", "hierarchical", "faithful"):
    n = {}
    for comp in (False, True):
        ts = TrainStepConfig(sync=sync, bucket_mb=1.0 / 64.0,
                             compressor=CompressorConfig(method="tnqsgd", bits=3),
                             metrics_compression=comp)
        fn = _make_sync_fn(ts, mesh, pspecs, list(leaves))
        n[comp] = sum(count_collectives(jax.make_jaxpr(fn)(list(leaves), key)).values())
    delta = n[True] - n[False]
    assert delta == 0, (sync, n)
    print(f"obs,{sync}_metrics_collective_delta,0,{delta}")
"""


def _collective_rows() -> list[str]:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"  # fake host devices; never contend for a chip
    env["PYTHONPATH"] = str(ROOT / "src") + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(_COUNT_DEMO)],
                       capture_output=True, text=True, timeout=1200, env=env)
    if r.returncode != 0:  # pragma: no cover - surfaced as a bench row
        tail = (r.stderr.strip().splitlines() or ["?"])[-1][:80]
        return [f"obs,collectives_demo_error,0,{tail}"]
    return [line for line in r.stdout.splitlines() if line.startswith("obs,")]


def _ts(metrics: bool) -> TrainStepConfig:
    return TrainStepConfig(sync="faithful", bucket_mb=1.0 / 64.0,
                           compressor=CompressorConfig(method="tnqsgd", bits=3),
                           error_feedback=True, metrics_compression=metrics)


def _grads(key) -> tuple:
    return tuple(
        (jax.random.normal(jax.random.fold_in(key, i), (N_CLIENTS,) + s) * 0.05
         ).astype(jnp.float32)
        for i, s in enumerate(LEAF_SHAPES))


def main(quick: bool = False) -> list[str]:
    rows = []
    rounds = 20 if quick else 100
    key = jax.random.key(11)
    leaves = _grads(key)
    skey = jax.random.key(3)

    fns = {}
    for metrics in (False, True):
        ts = _ts(metrics)
        fn = jax.jit(lambda k, ls, ts=ts: reference_sync_state(ts, list(ls),
                                                               (N_CLIENTS,), k))
        fn(skey, leaves)  # compile
        fns[metrics] = fn
    us_off = time_us(lambda: fns[False](skey, leaves), repeats=rounds)
    us_on = time_us(lambda: fns[True](skey, leaves), repeats=rounds)
    rows.append(f"obs,sync_metrics_off,{us_off:.0f},")
    rows.append(f"obs,sync_metrics_on,{us_on:.0f},{us_on / us_off:.3f}")

    # host-side pipeline: events -> JSONL text -> parse -> EMA summary
    cm = jax.device_get(fns[True](skey, leaves)[3])
    events = [metrics_event(i, cm) for i in range(16)]
    text = "\n".join(json.dumps(ev) for ev in events)

    def pipeline():
        evs = [json.loads(line) for line in text.splitlines()]
        return summarize(evs)

    n_buckets = len(pipeline()["buckets"])
    us_rep = time_us(pipeline, repeats=rounds)
    rows.append(f"obs,report_pipeline_16ev,{us_rep:.0f},{n_buckets}")

    rows.extend(_collective_rows())
    return rows
