"""Fused telemetry kernel vs the pure-jnp oracle: bit-exact histogram
counts, per-bin ln-sums (the Hill tail sums), and max — plus the moment
rows and semantic checks against numpy on the same bin edges."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sample_power_law
from repro.kernels import ops, ref
from repro.kernels import stats as S

SHAPES = [(64,), (1000,), (128, 128), (3, 777), (4, 7, 33), (10_000,)]


@pytest.mark.parametrize("shape", SHAPES)
def test_bucket_stats_matches_ref_bitwise(shape):
    g = sample_power_law(jax.random.key(1), shape, gamma=3.6, g_min=0.01, rho=0.15)
    got = ops.bucket_stats(g)
    want = ref.bucket_stats(g)
    np.testing.assert_array_equal(np.asarray(got.counts), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got.log_sums), np.asarray(want[1]))
    np.testing.assert_array_equal(np.asarray(got.g_max), np.asarray(want[2, 0]))
    np.testing.assert_array_equal(np.asarray(got.g_sum), np.asarray(want[3, 0]))
    np.testing.assert_array_equal(np.asarray(got.g_sumsq), np.asarray(want[4, 0]))


def test_bucket_stats_semantics_vs_numpy():
    """Counts are the |g| histogram on the module's edges; log-sums are the
    per-bin sums of ln|g|; max/moments match the direct reductions."""
    g = sample_power_law(jax.random.key(2), (50_000,), gamma=4.0, g_min=0.01, rho=0.1)
    got = ops.bucket_stats(g)
    ga = np.abs(np.asarray(g, np.float64))
    edges = np.asarray(S.bin_edges(), np.float64)
    edges_open = np.concatenate([edges[:-1], [np.inf]])   # top bin catches overflow
    counts, _ = np.histogram(ga, bins=edges_open)
    np.testing.assert_array_equal(np.asarray(got.counts), counts.astype(np.float32))
    assert float(jnp.sum(got.counts)) == g.size
    idx = np.clip(np.digitize(ga, edges_open) - 1, 0, S.NUM_BINS - 1)
    want_ls = np.zeros(S.NUM_BINS)
    np.add.at(want_ls, idx, np.log(np.maximum(ga, 1e-30)))
    np.testing.assert_allclose(np.asarray(got.log_sums), want_ls, rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(float(got.g_max), ga.max(), rtol=1e-6)
    np.testing.assert_allclose(float(got.g_sum), np.asarray(g, np.float64).sum(),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(float(got.g_sumsq), (ga ** 2).sum(), rtol=1e-3)


def test_bucket_stats_zeros_and_padding():
    """All-zero buckets land entirely in bin 0; padding contributes nothing."""
    g = jnp.zeros((100,), jnp.float32)
    got = ops.bucket_stats(g)
    assert float(got.counts[0]) == 100.0
    assert float(jnp.sum(got.counts)) == 100.0
    assert float(got.g_max) == 0.0
    # a single element: everything else is padding
    one = ops.bucket_stats(jnp.asarray([0.5], jnp.float32))
    assert float(jnp.sum(one.counts)) == 1.0
    assert float(one.g_max) == 0.5


def test_jnp_fallback_agrees_with_kernel():
    """The shard_map-safe scatter-add fallback used inside the train step
    produces the same counts/max exactly and the same sums numerically."""
    from repro.adaptive.telemetry import _stats_jnp

    g = sample_power_law(jax.random.key(3), (20_000,), gamma=3.4, g_min=0.02, rho=0.2)
    got = ops.bucket_stats(g)
    c, ls, gm, gs, gq = _stats_jnp(g)
    np.testing.assert_array_equal(np.asarray(got.counts), np.asarray(c))
    np.testing.assert_array_equal(np.asarray(got.g_max), np.asarray(gm))
    np.testing.assert_allclose(np.asarray(got.log_sums), np.asarray(ls), rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(float(got.g_sum), float(gs), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(float(got.g_sumsq), float(gq), rtol=1e-4)


def test_bin_index_is_exact_at_edges():
    """Bins come from the fp32 bits, not from ``log``: every edge value lands
    in its own bin and the float just below it in the bin beneath, so the
    kernel (Mosaic) and the oracles (XLA) cannot disagree near an edge."""
    edges = S.bin_edges()[1:-1]
    want = np.arange(1, S.NUM_BINS, dtype=np.float32)
    np.testing.assert_array_equal(np.asarray(S.bin_index(edges)), want)
    below = jnp.nextafter(edges, jnp.float32(0.0))
    np.testing.assert_array_equal(np.asarray(S.bin_index(below)), want - 1)
    extremes = jnp.asarray([0.0, 1e-30, 256.0, 1e30, jnp.inf], jnp.float32)
    np.testing.assert_array_equal(np.asarray(S.bin_index(extremes)),
                                  [0, 0, S.NUM_BINS - 1, S.NUM_BINS - 1, S.NUM_BINS - 1])


def test_block_stats_ignore_what_lies_past_the_bucket():
    """A partial last block reads past the array on the chip, where the
    buffer may hold NaN or inf: the masked elements must not reach any sum."""
    g = sample_power_law(jax.random.key(4), (S.BLOCK_ROWS, S.LANES), gamma=3.6,
                         g_min=0.01, rho=0.15)
    valid = jnp.arange(g.size).reshape(g.shape) < 1000
    block = jax.jit(S._block_stats)
    clean = block(jnp.where(valid, g, 0.0), valid, *S.accumulators())
    for junk in (jnp.nan, jnp.inf, -jnp.inf):
        got = block(jnp.where(valid, g, junk), valid, *S.accumulators())
        for a, b in zip(got, clean):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("n", [300_000, 3 * S.BLOCK_ROWS * S.LANES + 77])
def test_bucket_stats_counts_over_many_blocks(n):
    """Several grid blocks ending in a partial one, with one bin holding
    more than 2^16 elements: the lane-parallel counts, reduced once at the
    last block, equal ``np.histogram`` exactly, sum to n, and the padding
    past n lands in no bin."""
    g = sample_power_law(jax.random.key(5), (n,), gamma=3.6, g_min=0.01, rho=0.15)
    g = g.at[: 70_000].set(0.75)           # one bin past 2^16 elements
    got = np.asarray(ops.bucket_stats(g).counts)
    edges = np.asarray(S.bin_edges(), np.float64)
    want, _ = np.histogram(np.abs(np.asarray(g, np.float64)),
                           bins=np.concatenate([edges[:-1], [np.inf]]))
    np.testing.assert_array_equal(got, want.astype(np.float32))
    assert got.max() > 2**16
    assert got.sum() == n
