"""Adaptive wire-budget allocation vs fixed uniform bits, at equal bytes.

Two demonstrations, reported as CSV rows (``adaptive,<case>,0,<derived>``):

1. **Sync error at equal wire bytes** (in-process): heterogeneous synthetic
   buckets (mixed tail indices γ, scales, masses) are quantized (a) with the
   fixed uniform 3-bit plan and (b) with the bit plan the controller
   water-fills from *telemetry-estimated* tails under the fixed plan's byte
   budget.  The adaptive plan must spend no more bytes and achieve a lower
   mean-squared error.
2. **Collective counts** (subprocess, 4 fake devices): tracing the bucketed
   sync with a heterogeneous bit plan *and* telemetry threading must issue
   exactly the PR 2 collective counts — 1 for faithful, 2 for two_phase,
   3 for hierarchical — telemetry adds zero collectives.
"""
from __future__ import annotations

import dataclasses
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp

from repro.adaptive import telemetry as T
from repro.adaptive.controller import allocate_bits
from repro.core import sample_power_law
from repro.core.compressors import CompressorConfig, compress_decompress, wire_bytes

ROOT = pathlib.Path(__file__).resolve().parents[1]

# (size_exp, gamma, g_min, rho): heavy + thin tails across scales, the
# regime where a uniform bit width provably misallocates resolution.
SPECS = [
    (17, 3.2, 0.02, 0.20),
    (17, 5.0, 0.001, 0.05),
    (16, 3.6, 0.01, 0.15),
    (16, 4.8, 0.002, 0.05),
]

_COUNT_DEMO = """
import jax, jax.numpy as jnp
from jax.sharding import AxisType
from repro.analysis.jaxpr_lint import check_budget, count_collectives
from repro.configs import get_config, reduced
from repro.core.codecs import get_codec
from repro.core.compressors import CompressorConfig
from repro.dist import sharding
from repro.dist.train_step import (TrainStepConfig, _make_sync_fn, init_telemetry_state,
                                   local_bucket_sizes)
from repro.adaptive.controller import AdaptiveConfig
from repro.models import init_lm

cfg = reduced(get_config("llama3.2-1b")).replace(fsdp=False)
params0, logical = init_lm(jax.random.key(0), cfg)
key = jax.random.key(3)
for sync, axes in [("faithful", ("data",)), ("two_phase", ("data",)),
                   ("hierarchical", ("pod", "data"))]:
    shape = (4,) if len(axes) == 1 else (2, 2)
    mesh = jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
    pspecs = sharding.param_pspecs(logical, mesh, False, params0)
    ts0 = TrainStepConfig(sync=sync, compressor=CompressorConfig(method="tqsgd", bits=3,
                                                                 approx_gmin=True),
                          bucket_mb=1.0, adaptive=AdaptiveConfig())
    nb = len(local_bucket_sizes(params0, mesh, pspecs, ts0))
    bits = tuple(2 + (i % 3) for i in range(nb))          # heterogeneous plan
    ts = TrainStepConfig(sync=sync, compressor=ts0.compressor, bucket_mb=1.0,
                         adaptive=AdaptiveConfig(), bits_plan=bits)
    grads = jax.tree.map(lambda x: jnp.zeros((4,) + x.shape, jnp.float32), params0)
    grads_like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), grads)
    tstate = init_telemetry_state(params0, mesh, pspecs, ts)
    jfn = jax.jit(_make_sync_fn(ts, mesh, pspecs, grads_like))
    closed = jfn.trace(grads, key, tstate).jaxpr
    # the registry-declared budget is the want: 1/2/3 for faithful/
    # two_phase/hierarchical, telemetry + heterogeneous bits add nothing
    budget = get_codec("tqsgd").collective_budget(sync, nb)
    n = sum(count_collectives(closed).values())
    print(f"adaptive,{sync}_hetero_n_collectives,0,{n}")
    assert not check_budget(closed, budget, sync), (sync, n, budget)
    assert n == budget, (sync, n, budget)
print("adaptive,collectives_unchanged,0,OK")
"""


def _count_rows() -> list[str]:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"  # fake host devices; never contend for a chip
    env["PYTHONPATH"] = str(ROOT / "src") + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(_COUNT_DEMO)],
                       capture_output=True, text=True, timeout=1200, env=env)
    if r.returncode != 0:  # pragma: no cover - surfaced as a bench row
        tail = (r.stderr.strip().splitlines() or ["?"])[-1][:80]
        return [f"adaptive,collectives_demo_error,0,{tail}"]
    return [line for line in r.stdout.splitlines() if line.startswith("adaptive,")]


def main(quick: bool = False):
    rows = []
    shrink = 2 if quick else 0
    keys = jax.random.split(jax.random.key(0), len(SPECS))
    buckets = [sample_power_law(k, (1 << (e - shrink),), gamma=ga, g_min=gm, rho=r)
               for k, (e, ga, gm, r) in zip(keys, SPECS)]
    sizes = [b.size for b in buckets]

    st = T.init_telemetry(len(buckets))
    for _ in range(3):
        st = T.update_telemetry(st, buckets, decay=0.9)
    tails = T.estimate_tails(st)
    for b, (_, ga, _, _) in enumerate(SPECS):
        rows.append(f"adaptive,telemetry_gamma_b{b},0,"
                    f"{float(tails.gamma[b]):.2f}(true {ga})")

    ccfg = CompressorConfig(method="tqsgd", bits=3)
    budget = wire_bytes(ccfg, sizes)
    plan = allocate_bits(tails, sizes, budget, ccfg)
    rows.append(f"adaptive,bits_plan,0,{'/'.join(map(str, plan.bits))}")
    rows.append(f"adaptive,wire_bytes_fixed3,0,{budget}")
    rows.append(f"adaptive,wire_bytes_adaptive,0,{plan.spend_bytes}")
    assert plan.spend_bytes <= budget, (plan.spend_bytes, budget)

    def total_mse(bits_list):
        tot, n = 0.0, 0
        for b, (g, k) in enumerate(zip(buckets, bits_list)):
            c = compress_decompress(dataclasses.replace(ccfg, bits=k), g,
                                    jax.random.fold_in(jax.random.key(9), b))
            tot += float(jnp.sum((c - g) ** 2))
            n += g.size
        return tot / n

    mse_fixed = total_mse([ccfg.bits] * len(buckets))
    mse_adapt = total_mse(plan.bits)
    rows.append(f"adaptive,mse_fixed3,0,{mse_fixed:.4e}")
    rows.append(f"adaptive,mse_adaptive,0,{mse_adapt:.4e}")
    rows.append(f"adaptive,mse_ratio_fixed_over_adaptive,0,{mse_fixed / mse_adapt:.3f}")
    # the acceptance property: lower error at no more wire bytes
    assert mse_adapt < mse_fixed, (mse_adapt, mse_fixed)
    rows.append("adaptive,beats_fixed_at_equal_bytes,0,OK")

    rows.extend(_count_rows())
    return rows


if __name__ == "__main__":
    print("\n".join(main()))
