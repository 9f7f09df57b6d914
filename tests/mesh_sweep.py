"""Cross-mesh invariance sweep for the compressed gradient collectives.

Run as a script on one mesh: ``python tests/mesh_sweep.py data4`` (with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``; the tests go through
``multidevice.run_with_devices``).  For every mesh shape × sync method
combination it asserts that

(a) all peers of the collective hold **bitwise-identical** synced gradients
    (the peer-agreement contract every mode promises), and
(b) the synced gradient equals the **single-device reference**
    (``repro.dist.reference``) — bit-for-bit for the codebook-method modes,
    which share every local codec helper with the mesh path, and within
    tight float tolerance for ``dsgd`` (the partitioner owns the all-reduce
    order) and the uniform-method decode (ulp-level FMA-contraction
    discretion, see ``test_decode_kernels``).

Each mesh sweeps the sync modes, a uniform-codebook method, a heterogeneous
per-bucket ``bits_plan``, the per-leaf (``bucket_mb=0``) codec, EF + adaptive
state, the low-rank codec, and k-of-n elastic live masks.  It prints one
``OK <case>`` line per case and ``ALL_OK`` last.  ``REPRO_TEST_USE_PALLAS=1``
(the CI ``--interpret`` job) runs the same sweep through the Pallas
decode/encode kernels instead of the jnp fallbacks.

The mesh function and the reference are jitted once per ``TrainStepConfig``
(and per state layout); the live mask, the gradients and the EF rows are
traced arguments, so the elastic masks and the straggler's poked run reuse
one compile.  ``python tests/mesh_sweep.py counts`` instead checks that the
elastic mask adds no collective (on four devices).
"""
import dataclasses
import functools
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P

from repro.adaptive.controller import AdaptiveConfig
from repro.adaptive.telemetry import init_telemetry
from repro.core.compressors import CompressorConfig, plan_buckets
from repro.dist import reference, sharding
from repro.dist import sharded_codec as sc
from repro.dist.train_step import TrainStepConfig, _sync_buckets, _sync_leaf

MESHES = {
    "data1": ((1,), ("data",)),
    "data2": ((2,), ("data",)),
    "data4": ((4,), ("data",)),
    "pod2x2": ((2, 2), ("pod", "data")),
    "pod2x2x2": ((2, 2, 2), ("pod", "data", "model")),
}

# Sizes chosen so plan_buckets at bucket_mb=1/64 MB (4096 elements) coalesces
# them into three buckets of (3072, 2257, 3047) elements — mixed ragged tails.
LEAF_SHAPES = [(64, 48), (37, 61), (2048,), (999,)]


def run_case(mesh_id: str) -> None:
    """Test-side entry: run the sweep for ``mesh_id`` in a child process."""
    from multidevice import run_with_devices

    n = 4 if mesh_id == "counts" else math.prod(MESHES[mesh_id][0])
    out = run_with_devices([__file__, mesh_id], n=n)
    assert "ALL_OK" in out, out


def masks_for(n):
    ks = sorted({1, max(n // 2, 1), max(n - 1, 1)})
    out = []
    for k in ks:
        m = [1.0] * k + [0.0] * (n - k)
        out.append(tuple(m))
        if k < n:  # a non-prefix subset too — liveness is not positional
            out.append(tuple(reversed(m)))
    return sorted(set(out))


def sweep(mesh_id: str) -> None:
    shape, axes = MESHES[mesh_id]
    use_pallas = os.environ.get("REPRO_TEST_USE_PALLAS", "0") not in ("", "0")
    mesh = jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
    dp = sharding.manual_axes(mesh)
    dp_sizes = tuple(mesh.shape[a] for a in dp)
    n = math.prod(dp_sizes)

    key0 = jax.random.key(5)
    leaves = [
        (jax.random.normal(jax.random.fold_in(key0, i), (n,) + s) * 0.05 * (i + 1)
         ).astype(jnp.float32)
        for i, s in enumerate(LEAF_SHAPES)
    ]
    n_leaves = len(leaves)
    skey = jax.random.key(123)
    bp = plan_buckets([math.prod(s) for s in LEAF_SHAPES], 4096)
    # bucket-resident EF state: one stacked (n, m_b) array per codec bucket
    ef0 = [
        (jax.random.normal(jax.random.fold_in(key0, 100 + b), (n, m)) * 0.01
         ).astype(jnp.float32)
        for b, m in enumerate(bp.sizes)
    ]
    t0 = jax.tree.map(lambda x: jnp.tile(x[None], (n,) + (1,) * x.ndim),
                      init_telemetry(bp.n_buckets))
    t_spec = jax.tree.map(lambda _: P(dp), t0)
    all_live = jnp.ones((n,), jnp.float32)

    def ts_for(sync, method="tnqsgd", bits=3, bucket_mb=1.0 / 64.0, bits_plan=None,
               rank=4, **kw):
        return TrainStepConfig(
            sync=sync, bucket_mb=bucket_mb, bits_plan=bits_plan,
            compressor=CompressorConfig(method=method, bits=bits, rank=rank,
                                        use_pallas=use_pallas),
            **kw)

    # -- compiled once per (config, elastic): the live mask is a traced
    # argument, ignored (live=None in the sync) where ``elastic`` is off.

    @functools.cache
    def mesh_mean(ts, elastic):
        def body(key, lv, *stacked):
            vals = [x[0] for x in stacked]
            if ts.bucket_mb > 0:
                out = _sync_buckets(ts, vals, key, dp, live=lv if elastic else None)[0]
            else:
                out = [_sync_leaf(ts, g, jax.random.fold_in(key, i), dp)
                       for i, g in enumerate(vals)]
            return tuple(o[None] for o in out)

        return jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P(), P()) + (P(dp),) * n_leaves,
            out_specs=tuple(P(dp) for _ in leaves),
            axis_names=set(mesh.axis_names), check_vma=False))

    @functools.cache
    def ref_mean(ts, elastic):
        return jax.jit(lambda key, lv, *ls: tuple(reference.reference_sync(
            ts, list(ls), dp_sizes, key, live=lv if elastic else None)))

    @functools.cache
    def mesh_state(ts, elastic, n_ef):
        def body(key, lv, tstate, *stacked_and_ef):
            stacked, ef = stacked_and_ef[:n_leaves], stacked_and_ef[n_leaves:]
            vals = [x[0] for x in stacked]
            t_in = jax.tree.map(lambda x: x[0], tstate)
            out, resid, new_t, _, _ = _sync_buckets(ts, vals, key, dp, [e[0] for e in ef],
                                                    t_in, lv if elastic else None)
            return (tuple(o[None] for o in out), tuple(r[None] for r in resid),
                    jax.tree.map(lambda x: x[None], new_t))

        return jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(), t_spec) + (P(dp),) * (n_leaves + n_ef),
            out_specs=(tuple(P(dp) for _ in leaves), tuple(P(dp) for _ in range(n_ef)),
                       t_spec),
            axis_names=set(mesh.axis_names), check_vma=False))

    @functools.cache
    def ref_state(ts, elastic):
        return jax.jit(lambda key, lv, t, ls, e: reference.reference_sync_state(
            ts, list(ls), dp_sizes, key, ef=list(e), tstate=t,
            live=lv if elastic else None))

    def ef_rows(ts):
        # Rank-based codec buckets carry a codec-opaque aux tail after the
        # residual (state_extra); a random non-zero tail exercises the
        # warm-started power iteration on both sides.  Quantizer buckets
        # reuse the exact ef0 arrays.
        st_sizes = sc.bucket_state_sizes(ts.compressor, bp.sizes, ts.bits_plan)
        return [ef0[b] if st == bp.sizes[b] else
                (jax.random.normal(jax.random.fold_in(key0, 200 + b), (n, st)) * 0.01
                 ).astype(jnp.float32)
                for b, st in enumerate(st_sizes)]

    def assert_close(got, want, exact, msg):
        if exact:
            np.testing.assert_array_equal(got, want, err_msg=msg)
        else:
            np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-6, err_msg=msg)

    def assert_peer_rows(name, leaf_i, g, w, exact):
        g = np.asarray(g)
        # (a) every peer decoded identical bytes to identical values
        for peer in range(1, n):
            np.testing.assert_array_equal(
                g[0], g[peer], err_msg=f"{name}: peer {peer} diverges on leaf {leaf_i}")
        # (b) the mesh result is the single-device reference
        assert_close(g[0], np.asarray(w), exact, f"{name}: reference mismatch on leaf {leaf_i}")

    def check(name, ts, exact, live=None):
        elastic = live is not None
        lv = jnp.asarray(live, jnp.float32) if elastic else all_live
        got = mesh_mean(ts, elastic)(skey, lv, *leaves)
        want = ref_mean(ts, elastic)(skey, lv, *leaves)
        for leaf_i, (g, w) in enumerate(zip(got, want)):
            assert_peer_rows(name, leaf_i, g, w, exact)
        print("OK", name)

    def check_state(name, ts, exact, live=None):
        # EF + adaptive over the bucket-resident state layout: the mesh body
        # threads the stacked EF bucket arrays and the telemetry rows exactly
        # as _make_sync_fn does; means must agree bitwise across peers, and
        # the per-peer residual rows must equal the reference's.  Under a
        # live mask, dropped peers' residual rows must accumulate the whole
        # corrected bucket (stale-EF) and live peers' rows must match
        # full-participation semantics; without one, the telemetry rows must
        # match the reference's too.
        elastic = live is not None
        lv = jnp.asarray(live, jnp.float32) if elastic else all_live
        ef = ef_rows(ts)
        means, resids, new_t = mesh_state(ts, elastic, len(ef))(skey, lv, t0, *leaves, *ef)
        w_means, w_resids, w_t, _ = ref_state(ts, elastic)(skey, lv, t0, tuple(leaves),
                                                            tuple(ef))
        for leaf_i, (g, w) in enumerate(zip(means, w_means)):
            assert_peer_rows(name, leaf_i, g, w, exact)
        for b, (r, w) in enumerate(zip(resids, w_resids)):
            assert_close(np.asarray(r), np.asarray(w), exact, f"{name}: resid bucket {b}")
        if not elastic:
            for got_leaf, want_leaf in zip(jax.tree.leaves(new_t), jax.tree.leaves(w_t)):
                np.testing.assert_allclose(np.asarray(got_leaf), np.asarray(want_leaf),
                                           atol=1e-5, rtol=1e-5,
                                           err_msg=f"{name}: telemetry rows diverge")
        print("OK", name)

    # Every mesh runs the four sync modes; the auxiliary surfaces (uniform-
    # codebook decode, heterogeneous bits_plan, per-leaf codec) get their full
    # sweep on the 2-peer mesh and one targeted case elsewhere.
    full = n == 2

    for sync in ("dsgd", "two_phase", "hierarchical", "faithful"):
        check(f"bucketed/{sync}/tnqsgd", ts_for(sync), exact=sync != "dsgd")

    # uniform-codebook decode branch (alpha-formula dequant; near-exact — the
    # dequant multiply-add's FMA contraction is compiler-discretionary between
    # the mesh and reference graphs, see tests/test_decode_kernels.py)
    for sync in ("two_phase", "faithful") if full or n == 4 and len(dp_sizes) == 1 else ():
        check(f"bucketed/{sync}/tqsgd", ts_for(sync, method="tqsgd", bits=4), exact=False)

    # heterogeneous per-bucket wire widths through the fused decode path
    het = ("two_phase", "hierarchical", "faithful") if full else (
        ("hierarchical",) if len(dp_sizes) > 1 else ())
    for sync in het:
        check(f"bucketed/{sync}/bits_plan", ts_for(sync, bits_plan=(2, 4, 3)), exact=True)

    # per-leaf codec (bucket_mb=0): ring-mean / all-gather decode sites
    per_leaf = ("two_phase", "hierarchical", "faithful") if full else (
        ("hierarchical",) if len(dp_sizes) > 1 and shape[-1] > 1 else
        ("faithful",) if n == 1 else ())
    for sync in per_leaf:
        check(f"per_leaf/{sync}/tnqsgd", ts_for(sync, bucket_mb=0.0), exact=True)

    # EF + adaptive over the bucket-resident state layout (residual + telemetry
    # ride the sync exactly as in _make_sync_fn); full sweep on the 2-peer
    # mesh, one hierarchical case on the pod meshes.
    adaptive = AdaptiveConfig(ema=0.9)
    ef_sweep = ("faithful", "two_phase") if full else (
        ("hierarchical",) if len(dp_sizes) > 1 else ())
    for sync in ef_sweep:
        check_state(f"bucketed_state/{sync}/tnqsgd",
                    ts_for(sync, error_feedback=True, adaptive=adaptive), exact=True)
    if full:
        check_state("bucketed_state/faithful/bits_plan",
                    ts_for("faithful", bits_plan=(2, 4, 3), error_feedback=True,
                           adaptive=adaptive), exact=True)

    # powersgd through the registry: the non-chunkable wire rides every sync
    # mode (two_phase tiles the full factor pair into each all-to-all row).
    # Peer agreement stays bitwise (assert_peer_rows part (a)); the reference
    # comparison is allclose — the factor matmuls' FMA contraction is
    # compiler-discretionary between the mesh and reference graphs.
    psgd = ("two_phase", "faithful") if full else (
        ("hierarchical",) if len(dp_sizes) > 1 else ())
    for sync in psgd:
        check(f"bucketed/{sync}/powersgd", ts_for(sync, method="powersgd", rank=4),
              exact=False)
    # mixed per-bucket method plan: quantized buckets next to a low-rank one
    # in the same fused wire (plan entries resolve through the codec registry)
    mixed_plan = (3, ("powersgd", 4), 2)
    mixed = ("faithful", "two_phase") if full else (
        ("hierarchical",) if len(dp_sizes) > 1 and shape[-1] > 1 else ())
    for sync in mixed:
        check(f"bucketed/{sync}/mixed_plan", ts_for(sync, bits_plan=mixed_plan),
              exact=False)
    # EF + adaptive with the rank-based codec: the state rows grow the
    # codec-opaque aux tail (warm-started Q), threaded by _sync_buckets.
    if full:
        check_state("bucketed_state/faithful/powersgd",
                    ts_for("faithful", method="powersgd", error_feedback=True,
                           adaptive=adaptive), exact=False)
        check_state("bucketed_state/two_phase/mixed_plan",
                    ts_for("two_phase", bits_plan=mixed_plan, error_feedback=True,
                           adaptive=adaptive), exact=False)

    # --- elastic: k-of-n live subsets must replay bit-for-bit under the same
    # mask, and a dead peer's gradient must be unable to move the mean (the
    # straggler contract: its encode runs, its wire contribution is zeroed).
    # Full k-of-n sweep (k in {1, n/2, n-1}, prefix + reversed subsets) on
    # the 2-peer mesh and the 1-D 4-peer mesh; the pod meshes (also n=4) run
    # the first two sorted masks, which include a fully-dead pod — the
    # hierarchical-specific renormalization case.
    elastic_masks = masks_for(n) if (full or (n == 4 and len(dp_sizes) == 1)) else (
        masks_for(n)[:2] if n > 1 else [])
    for sync in ("dsgd", "two_phase", "hierarchical", "faithful"):
        for mask in elastic_masks:
            k = int(sum(mask))
            check(f"elastic/{sync}/live{k}of{n}/{mask}", ts_for(sync), exact=sync != "dsgd",
                  live=mask)

    # EF + adaptive under the mask (2-peer mesh full, pods one hierarchical)
    el_state = ("faithful", "two_phase") if full else (
        ("hierarchical",) if len(dp_sizes) > 1 else ())
    for sync in el_state:
        for mask in elastic_masks[:3]:
            k = int(sum(mask))
            check_state(f"elastic_state/{sync}/live{k}of{n}",
                        ts_for(sync, error_feedback=True, adaptive=adaptive),
                        exact=True, live=mask)

    # the fp16 size-adaptive tier rides the same contract: the smallest bucket
    # (2257 elements) ships raw half precision on both sides
    if full:
        for sync in ("two_phase", "faithful"):
            check(f"elastic/{sync}/fp16_tier", ts_for(sync, fp16_threshold=2500),
                  exact=True, live=(1.0, 0.0))
            check(f"bucketed/{sync}/fp16_tier", ts_for(sync, fp16_threshold=2500),
                  exact=True)

    # straggler pin: perturbing a dead peer's gradient cannot move the mean —
    # its encode still runs (side-effect-free), its wire row is zeroed.
    if n > 1:
        lv = jnp.asarray((1.0,) * (n - 1) + (0.0,), jnp.float32)
        poked = [x.at[n - 1].mul(-3.7) for x in leaves]
        for sync in ("two_phase", "faithful"):
            fn = mesh_mean(ts_for(sync), True)
            base = fn(skey, lv, *leaves)
            got = fn(skey, lv, *poked)
            for leaf_i, (a, b) in enumerate(zip(base, got)):
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b),
                    err_msg=f"straggler/{sync}: dead peer moved the mean (leaf {leaf_i})")
            print("OK", f"straggler/{sync}")

    print("ALL_OK")


def counts() -> None:
    """The live mask is a replicated in-graph value: enabling elastic must
    not add (or remove) a single traced collective in any sync mode."""
    from repro.analysis.jaxpr_lint import count_collectives
    from repro.dist.train_step import _make_sync_fn
    from repro.elastic import ElasticConfig

    mesh = jax.make_mesh((2, 2), ("pod", "data"), axis_types=(AxisType.Auto, AxisType.Auto))
    grads_like = [jax.ShapeDtypeStruct((4,) + s, jnp.float32) for s in LEAF_SHAPES]
    pspecs = [P() for _ in LEAF_SHAPES]
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype)
    live = jax.ShapeDtypeStruct((4,), jnp.float32)

    for sync in ("dsgd", "two_phase", "hierarchical", "faithful"):
        base = TrainStepConfig(
            sync=sync, bucket_mb=1.0 / 64.0,
            compressor=CompressorConfig(method="tnqsgd", bits=3))
        el = dataclasses.replace(base, elastic=ElasticConfig(rate=0.3))
        fn_off = _make_sync_fn(base, mesh, pspecs, grads_like)
        fn_on = _make_sync_fn(el, mesh, pspecs, grads_like)
        c_off = count_collectives(jax.make_jaxpr(fn_off)(grads_like, key))
        c_on = count_collectives(jax.make_jaxpr(fn_on)(grads_like, key, live))
        assert c_on == c_off, (sync, dict(c_on), dict(c_off))
        print("OK", sync, dict(c_on))
    print("ALL_OK")


if __name__ == "__main__":
    if sys.argv[1] == "counts":
        counts()
    else:
        sweep(sys.argv[1])
