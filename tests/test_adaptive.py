"""repro.adaptive: telemetry estimation, wire-budget allocation, and the
end-to-end adaptive train step (subprocess host mesh)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from multidevice import run_with_devices

from repro.adaptive import telemetry as T
from repro.adaptive.controller import AdaptiveConfig, allocate_bits, budget_bytes
from repro.core import sample_power_law
from repro.core.compressors import CompressorConfig, wire_bytes


def _tails_for(gammas, *, n=120_000, updates=4, decay=0.9, g_mins=None, rhos=None):
    g_mins = g_mins or [0.01] * len(gammas)
    rhos = rhos or [0.15] * len(gammas)
    st = T.init_telemetry(len(gammas))
    for i in range(updates):
        bks = [sample_power_law(jax.random.key(1000 * b + i), (n,), gamma=ga,
                                g_min=gm, rho=r)
               for b, (ga, gm, r) in enumerate(zip(gammas, g_mins, rhos))]
        st = T.update_telemetry(st, bks, decay=decay)
    return st, T.estimate_tails(st)


def test_telemetry_recovers_tail_index():
    gammas = (3.3, 4.0, 4.7)
    _, tails = _tails_for(gammas)
    got = np.asarray(tails.gamma)
    for b, ga in enumerate(gammas):
        assert abs(got[b] - ga) < 0.4, (b, ga, got[b])
    # heavier tail -> smaller estimated gamma, strictly ordered
    assert got[0] < got[1] < got[2]


def test_telemetry_state_is_scale_invariant_in_ratios():
    """EMA debiasing cancels: doubling the number of updates must not move
    the estimated (gamma, rho) materially."""
    _, t_few = _tails_for((3.6,), updates=2)
    _, t_many = _tails_for((3.6,), updates=8)
    assert abs(float(t_few.gamma[0]) - float(t_many.gamma[0])) < 0.25
    assert abs(float(t_few.rho[0]) - float(t_many.rho[0])) < 0.05


def test_aggregate_peers_merges_rows():
    st, _ = _tails_for((3.5, 4.5), updates=2)
    stacked = jax.tree.map(lambda x: jnp.stack([x, x]), st)
    merged = T.aggregate_peers(stacked)
    np.testing.assert_allclose(np.asarray(merged.counts), 2 * np.asarray(st.counts))
    np.testing.assert_allclose(np.asarray(merged.g_max), np.asarray(st.g_max))
    np.testing.assert_allclose(np.asarray(merged.mean), np.asarray(st.mean), rtol=1e-6)
    # identical peers -> identical tails after merging
    ta, tb = T.estimate_tails(merged), T.estimate_tails(st)
    np.testing.assert_allclose(np.asarray(ta.gamma), np.asarray(tb.gamma), rtol=1e-5)


def test_allocate_bits_respects_budget_and_bounds():
    _, tails = _tails_for((3.2, 4.9), g_mins=[0.02, 0.002], rhos=[0.2, 0.05])
    ccfg = CompressorConfig(method="tqsgd", bits=3)
    sizes = [1 << 16, 1 << 16]
    budget = wire_bytes(ccfg, sizes)
    plan = allocate_bits(tails, sizes, budget, ccfg, min_bits=2, max_bits=8)
    assert plan.spend_bytes <= budget == plan.budget_bytes
    assert all(2 <= b <= 8 for b in plan.bits)
    assert len(plan.alphas) == len(sizes)
    # the heavy/large-scale bucket is never starved below the thin one
    assert plan.bits[0] >= plan.bits[1]


def test_allocate_bits_monotone_in_budget():
    _, tails = _tails_for((3.3, 3.9, 4.6))
    ccfg = CompressorConfig(method="tqsgd", bits=3)
    sizes = [1 << 15] * 3
    base = wire_bytes(ccfg, sizes)
    totals = []
    for f in (0.7, 1.0, 1.6):
        plan = allocate_bits(tails, sizes, int(base * f), ccfg)
        assert plan.spend_bytes <= int(base * f)
        totals.append(sum(plan.bits))
    assert totals[0] <= totals[1] <= totals[2]
    # an effectively unlimited budget saturates max_bits
    plan = allocate_bits(tails, sizes, 10 * base, ccfg, max_bits=8)
    assert plan.bits == (8, 8, 8)


def test_allocate_bits_method_dispatch():
    """The error model follows the compressor method: tnqsgd's α comes from
    the non-uniform solver over the telemetry density (what the codec's
    plan actually solves), untruncated qsgd/nqsgd pin α = max|g|."""
    from repro.core import optimal

    st, tails = _tails_for((3.4,), updates=3)
    dens = T.estimate_densities(st)
    sizes = [1 << 15]
    row = jax.tree.map(lambda x: x[0], tails)
    for bits in (2, 4):
        plan = allocate_bits(tails, sizes, 10**9, CompressorConfig(method="tnqsgd", bits=3),
                             dens=dens, min_bits=bits, max_bits=bits)
        want = float(optimal.solve_alpha_nonuniform(row, dens[0], bits))
        assert plan.alphas[0] == pytest.approx(want, rel=1e-5), (bits, plan.alphas, want)
        uni = allocate_bits(tails, sizes, 10**9, CompressorConfig(method="tqsgd", bits=3),
                            dens=dens, min_bits=bits, max_bits=bits)
        assert uni.alphas[0] == pytest.approx(
            float(optimal.solve_alpha_uniform(row, bits)), rel=1e-5)
    for method in ("qsgd", "nqsgd"):
        plan = allocate_bits(tails, sizes, 10**9, CompressorConfig(method=method, bits=3),
                             dens=dens, min_bits=3, max_bits=3)
        assert plan.alphas[0] == pytest.approx(float(row.g_max), rel=1e-6)


def test_predicted_error_monotone_in_bits():
    from repro.adaptive.controller import predicted_error

    st, tails = _tails_for((3.5,), updates=3)
    dens = T.estimate_densities(st)
    for method in ("tqsgd", "tnqsgd"):
        ccfg = CompressorConfig(method=method, bits=3)
        errs = [predicted_error(tails, [1 << 14], [b], ccfg, dens=dens)
                for b in (2, 3, 5, 8)]
        assert errs == sorted(errs, reverse=True), (method, errs)
    # BitPlan.err matches predicted_error at the solved bits
    ccfg = CompressorConfig(method="tnqsgd", bits=3)
    plan = allocate_bits(tails, [1 << 14], 10**9, ccfg, dens=dens, max_bits=6)
    assert plan.err == pytest.approx(
        predicted_error(tails, [1 << 14], plan.bits, ccfg, dens=dens), rel=1e-6)


def test_budget_bytes_default_matches_fixed_plan():
    ccfg = CompressorConfig(method="tqsgd", bits=3)
    sizes = [1000, 2000]
    assert budget_bytes(AdaptiveConfig(), ccfg, sizes) == wire_bytes(ccfg, sizes)
    mb = AdaptiveConfig(wire_budget_mb=2.5)
    assert budget_bytes(mb, ccfg, sizes) == int(2.5 * (1 << 20))


def test_adaptive_config_validation():
    with pytest.raises(ValueError):
        AdaptiveConfig(min_bits=5, max_bits=4)
    with pytest.raises(ValueError):
        AdaptiveConfig(replan_every=0)
    with pytest.raises(ValueError):
        AdaptiveConfig(ema=1.5)
    from repro.dist.train_step import TrainStepConfig

    with pytest.raises(ValueError):
        TrainStepConfig(sync="dsgd", adaptive=AdaptiveConfig())
    with pytest.raises(ValueError):
        TrainStepConfig(sync="faithful", bucket_mb=0.0, adaptive=AdaptiveConfig())
    with pytest.raises(ValueError):
        TrainStepConfig(sync="faithful", bits_plan=(0, 3))


def test_adaptive_beats_fixed_at_equal_bytes():
    """The acceptance property, in miniature (the benchmark runs it at
    scale): telemetry-driven allocation under the fixed-3-bit budget yields
    strictly lower total MSE on heterogeneous buckets."""
    import dataclasses

    specs = [(3.2, 0.02, 0.2), (5.0, 0.001, 0.05), (3.6, 0.01, 0.15)]
    keys = jax.random.split(jax.random.key(0), len(specs))
    bks = [sample_power_law(k, (1 << 15,), gamma=ga, g_min=gm, rho=r)
           for k, (ga, gm, r) in zip(keys, specs)]
    st = T.init_telemetry(len(bks))
    for _ in range(3):
        st = T.update_telemetry(st, bks, decay=0.9)
    tails = T.estimate_tails(st)
    ccfg = CompressorConfig(method="tqsgd", bits=3)
    sizes = [b.size for b in bks]
    plan = allocate_bits(tails, sizes, wire_bytes(ccfg, sizes), ccfg)

    def mse(bits):
        from repro.core.compressors import compress_decompress

        tot = sum(float(jnp.sum((compress_decompress(
            dataclasses.replace(ccfg, bits=k), g, jax.random.fold_in(jax.random.key(9), b))
            - g) ** 2)) for b, (g, k) in enumerate(zip(bks, bits)))
        return tot / sum(sizes)

    assert plan.spend_bytes <= plan.budget_bytes
    assert mse(plan.bits) < mse([3] * len(bks))


def test_stepper_cache_bound_and_hysteresis():
    """The compiled-step cache is LRU-bounded at max_cached_steps and a
    replan whose predicted gain is under switch_threshold keeps the current
    plan (no compile).  Exercised on a stepper shell with a stubbed
    builder, so no mesh/compile is involved."""
    import collections

    from repro.adaptive.controller import BitPlan
    from repro.adaptive.runtime import AdaptiveStepper
    from repro.dist.train_step import TrainStepConfig

    st, _ = _tails_for((3.4, 4.6), updates=3)
    stacked = jax.tree.map(lambda x: x[None], st)   # one peer row

    s = AdaptiveStepper.__new__(AdaptiveStepper)
    s.ts = TrainStepConfig(
        sync="faithful", compressor=CompressorConfig(method="tqsgd", bits=3),
        bucket_mb=1.0,
        adaptive=AdaptiveConfig(warmup_steps=1, max_cached_steps=2,
                                switch_threshold=0.02))
    s.sizes = (1 << 15, 1 << 15)
    s.bits = (3, 3)
    s.plan = None
    s.tails = None
    built = []
    s._build = lambda bits: (("step", bits), None)
    s._cache = collections.OrderedDict()

    # LRU bound: three distinct plans, cache keeps the last two
    for bits in [(2, 2), (3, 3), (4, 4), (3, 3)]:
        fn = s._step_for(bits)
        assert fn == ("step", bits)
        built.append(bits)
    assert len(s._cache) == 2 and (2, 2) not in s._cache
    assert list(s._cache) == [(4, 4), (3, 3)]

    # First replan away from the bootstrap always adopts (plan is None)
    p1 = s.replan(stacked)
    assert s.bits == p1.bits and s.plan is p1
    assert p1.spend_bytes <= p1.budget_bytes
    # Current plan = starved (2,2): the solved plan's predicted error is far
    # lower, so hysteresis must ADOPT the switch.
    s.bits, s.plan = (2, 2), BitPlan((2, 2), (), 0, 0, err=0.0)
    p2 = s.replan(stacked)
    assert p2.bits != (2, 2) and s.bits == p2.bits
    # With a prohibitive threshold, a perturbed current plan is KEPT even
    # though the solver disagrees with it (no compile is worth <95% gain).
    s.ts = dataclasses_replace_adaptive(s.ts, switch_threshold=0.95)
    perturbed = tuple(max(2, b - 1) for b in p2.bits)
    assert perturbed != p2.bits
    s.bits, s.plan = perturbed, BitPlan(perturbed, (), 0, 0, err=p2.err)
    kept = s.replan(stacked)
    assert kept.bits == perturbed and s.bits == perturbed


def dataclasses_replace_adaptive(ts, **kw):
    import dataclasses

    return dataclasses.replace(ts, adaptive=dataclasses.replace(ts.adaptive, **kw))


def test_adaptive_train_step_end_to_end():
    """4-device host mesh: telemetry threads through the jitted step, the
    replan switches to a cached heterogeneous-bits step, loss decreases."""
    code = """
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, AxisType
from repro.configs import get_config, reduced
from repro.models import init_lm
from repro.data.synthetic import lm_batch
from repro.optim.optimizers import momentum_sgd
from repro.dist.train_step import TrainStepConfig
from repro.core.compressors import CompressorConfig
from repro.adaptive.controller import AdaptiveConfig
from repro.adaptive.runtime import AdaptiveStepper

mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
cfg = reduced(get_config("llama3.2-1b")).replace(fsdp=False)
params, logical = init_lm(jax.random.key(0), cfg)
opt = momentum_sgd(lr=0.05)
acfg = AdaptiveConfig(replan_every=2, warmup_steps=1, ema=0.8, min_bits=2, max_bits=6)
ts = TrainStepConfig(sync="faithful", compressor=CompressorConfig(method="tqsgd", bits=3),
                     bucket_mb=1.0, adaptive=acfg)
batch0 = lm_batch(cfg, jnp.uint32(0), 8, 64)
opt_state = opt.init(params)
stepper = AdaptiveStepper(cfg, mesh, logical, opt, ts, batch0,
                          opt_state_like=jax.eval_shape(lambda: opt_state),
                          params_like=params)
assert len(stepper.sizes) > 1, stepper.sizes
sh = jax.tree.map(lambda s: NamedSharding(mesh, s), stepper.pspecs,
                  is_leaf=lambda x: isinstance(x, P))
p = jax.device_put(params, sh)
o = jax.tree.map(jnp.zeros_like, p)
tstate = stepper.init_telemetry()
assert jax.tree.leaves(tstate)[0].shape[0] == 4  # one telemetry row per peer
losses = []
for i in range(5):
    p, o, _, tstate, m = stepper.step(p, o, None, tstate,
                                      lm_batch(cfg, jnp.uint32(i), 8, 64), i)
    losses.append(float(m["loss"][0]))
assert losses[-1] < losses[0], losses
plan = stepper.plan
assert plan is not None and len(plan.bits) == len(stepper.sizes)
assert plan.spend_bytes <= plan.budget_bytes
assert all(2 <= b <= 6 for b in plan.bits)
# steps counter advanced once per step on every peer
steps = jax.tree.leaves(tstate)[-1]
assert float(jnp.min(steps)) == 5.0, steps
# a changed plan gets its own compiled step in the cache
uniform = (3,) * len(stepper.sizes)
assert len(stepper._cache) == (2 if plan.bits != uniform else 1), stepper._cache.keys()
print("OK", losses, plan.bits)
"""
    assert "OK" in run_with_devices(code, n=4)
