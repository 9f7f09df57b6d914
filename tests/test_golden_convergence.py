"""Golden convergence regression: fixed-seed 30-step smallnet runs per sync
method, with pinned final-loss windows.

Gradients are synced through :func:`repro.dist.reference.reference_sync` —
the single-device replica of the mesh codec that shares every local
plan/encode/decode helper with ``dist.sharded_codec`` (and is itself pinned
bit-for-bit against the mesh in ``test_mesh_invariance``) — so a codec
refactor that silently biases the synced mean (a dropped 1/n, a truncation
bias, a decode off-by-one) moves these losses far outside their windows and
fails tier-1 instead of only drifting a benchmark curve.

The windows are generous against ulp-level platform noise (runs are fully
deterministic on the pinned CPU toolchain) but far tighter than the gap to
a broken codec: the task converges from ≈5.87 to ≲0.1 in 30 steps, and a
mean-perturbing bug such as a dropped 1/n stalls that decay orders of
magnitude above the pinned values (``test_golden_window_rejects_biased_mean``).

The pins are taken on jax 0.9.0's random stream (``jax_threefry_partitionable``
on, the default since jax 0.5); the stream of jax 0.4.37 drew other templates
and initial weights (first-step loss 6.4895) and converged to other values.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core.compressors import CompressorConfig, plan_buckets
from repro.dist import sharded_codec as sc
from repro.data.synthetic import client_batches, make_templates
from repro.dist.reference import reference_sync, reference_sync_state
from repro.dist.train_step import TrainStepConfig
from repro.models.smallnet import init_smallnet, smallnet_loss
from repro.optim.optimizers import momentum_sgd

N_CLIENTS = 8
BATCH = 32
STEPS = 30

# case -> (sync, method, ef, (pods, data) layout, pinned final loss,
# tolerance).  The synthetic shapes task converges hard in 30 steps
# (first-step loss ≈ 5.87); a codec bias that perturbs the synced mean
# stalls convergence orders of magnitude above these windows.  The
# powersgd case runs with error feedback — biased low-rank compression
# needs the residual (and its warm-started Q rides the same EF row), so
# the window also pins the bucket-resident aux-state threading.
GOLDEN = {
    "dsgd": ("dsgd", "tnqsgd", False, (8,), 0.0256, 0.02),
    "two_phase": ("two_phase", "tnqsgd", False, (8,), 0.0451, 0.05),
    "hierarchical": ("hierarchical", "tnqsgd", False, (2, 4), 0.0917, 0.05),
    "faithful": ("faithful", "tnqsgd", False, (8,), 0.0766, 0.05),
    "powersgd": ("faithful", "powersgd", True, (8,), 0.0567, 0.05),
}


def _run(sync: str, method: str, ef_on: bool, dp: tuple, elastic=None,
         bias: float = 1.0) -> list:
    """Loss per step; ``bias`` scales the synced mean (a deliberately broken
    codec for the window's negative check)."""
    ts = TrainStepConfig(sync=sync, error_feedback=ef_on, elastic=elastic,
                         compressor=CompressorConfig(method=method, bits=3, rank=4))
    templates = make_templates(jax.random.key(42))
    params = init_smallnet(jax.random.key(0))
    opt = momentum_sgd(lr=0.01, momentum=0.9, weight_decay=5e-4)
    state = opt.init(params)
    ef = None
    if ef_on:
        bp = plan_buckets([x.size for x in jax.tree.leaves(params)],
                          int(4.0 * (1 << 20) / 4))
        st_sizes = sc.bucket_state_sizes(ts.compressor, bp.sizes, ts.bits_plan)
        ef = [jnp.zeros((N_CLIENTS, s), jnp.float32) for s in st_sizes]

    @jax.jit
    def step(p, s, ef, i):
        imgs, labels = client_batches(templates, i, N_CLIENTS, BATCH)
        losses, grads = jax.vmap(
            lambda im, lb: jax.value_and_grad(smallnet_loss)(p, im, lb))(imgs, labels)
        leaves, treedef = jax.tree.flatten(grads)
        key = jax.random.fold_in(jax.random.key(0x5EED), i)
        live = None
        if elastic is not None:
            from repro.elastic import live_mask

            live = live_mask(elastic, i, N_CLIENTS)
        if ef_on:
            mean, ef2, _, _ = reference_sync_state(ts, leaves, dp, key, ef=ef,
                                                   live=live)
        else:
            mean, ef2 = reference_sync(ts, leaves, dp, key, live=live), None
        mean = [m * bias for m in mean]
        p2, s2 = opt.update(p, jax.tree.unflatten(treedef, mean), s, i)
        return p2, s2, ef2, jnp.mean(losses)

    hist = []
    p, s = params, state
    for i in range(STEPS):
        p, s, ef, loss = step(p, s, ef, jnp.uint32(i))
        hist.append(float(loss))
    return hist


@pytest.mark.parametrize("case", sorted(GOLDEN))
def test_golden_final_loss(case):
    sync, method, ef_on, dp, pinned, tol = GOLDEN[case]
    hist = _run(sync, method, ef_on, dp)
    assert hist[-1] == pytest.approx(pinned, abs=tol), (case, hist)
    # and training actually converged (quantization noise notwithstanding)
    assert hist[-1] < hist[0] - 5.0, (case, hist)


def test_golden_window_rejects_biased_mean():
    """A codec that drops the 1/n of the mean (ships the peer sum) must land
    outside every pinned window: the windows catch a biased sync."""
    hist = _run("faithful", "tnqsgd", False, (8,), bias=float(N_CLIENTS))
    for case, (*_, pinned, tol) in GOLDEN.items():
        assert hist[-1] > pinned + tol, (case, hist)


def test_golden_elastic_dropout():
    """20%% scheduled dropout (deterministic counter hash, EF on): stale-EF
    recovery keeps the run converging into a pinned window — the elastic
    analogue of the full-participation faithful case above."""
    from repro.elastic import ElasticConfig

    hist = _run("faithful", "tnqsgd", True, (8,),
                elastic=ElasticConfig(rate=0.2, seed=0x17E))
    # pinned from the deterministic run (first-step loss 5.8680); dropout
    # noise keeps the late-step losses bouncing in [0.03, 0.36], so the
    # window is wider than the full-participation cases'
    assert hist[-1] == pytest.approx(0.1192, abs=0.07), hist
    assert hist[-1] < hist[0] - 5.0, hist
