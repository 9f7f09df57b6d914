"""Smoke test of the compressed-sync trainer on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips of one host: the sync modes

One chip.  Trains ``llama3.2-1b`` at its full published width (16 layers,
d_model 2048, vocab 128256, bf16; random weights from a fixed seed) for a
few steps through the normal entry point, ``repro.launch.train.main``:
``two_phase`` sync, ``tnqsgd`` at 3 bits, error feedback, the default 4 MB
bucket target.  On a TPU the launcher puts the Pallas codec kernels in the
step.  The losses must be finite and the compiled step must hold Pallas
kernels.  Then the kernels are checked against their jnp oracles
(``use_pallas=False``) on the first and the last gradient bucket of the
same model — one real gradient per client, two clients — with the bounds
of the kernel tests: the one-pass bucket statistics, a four-peer
``faithful`` sync (``repro.dist.reference``) and the per-peer words-only
encode + decode, for a codebook (``tnqsgd``: bit-exact) and a uniform
(``tqsgd``: words exact, values within 4 ulp) method.  Plans are built once
from the kernel statistics and shared by both sides, so what is compared is
the kernels and nothing else.

Four chips.  Four clients' gradients of the same model are computed (on
the first chip) and their first and last buckets placed one client per
chip; the two buckets then go through the mesh sync
(``train_step._sync_buckets`` inside a fully manual ``shard_map``, kernels
on) for ``dsgd``, ``two_phase`` and ``faithful`` on mesh ``4`` and
``hierarchical`` on mesh ``2x2x1`` (pod x data x model).  Every chip must
hold the same synced mean, and it must equal the single-device
``repro.dist.reference`` replay: bit-exact for the compressed modes, within
1e-6 for ``dsgd`` (the all-reduce order is the partitioner's).  The trainer
also runs two ``two_phase`` steps on the four chips.

Exits non-zero, printing no result, unless the first device JAX finds is a
TPU.  Any failed phase exits non-zero.  The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import os
import re
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "llama3.2-1b"
SEQ = 1024
# Largest batch at SEQ whose step fits one v5e chip's 16 GiB with error
# feedback on: batch 8 asks the compiler for 18.3 GiB, batch 4 for 15.2.
BATCH = 4
STEPS = 3
CLIENTS = 4                     # peers of the four-chip comparison
# Peers of the one-chip kernel check: the first bucket holds 2^28 elements
# (a stacked-layer leaf), and its lane-padded word tensors make a four-peer
# check ask one chip for more than 16 GiB.
CHECK_PEERS = 2
BITS = 3
FOUR_CHIP_MODES = (("dsgd", "4"), ("two_phase", "4"), ("faithful", "4"),
                   ("hierarchical", "2x2x1"))


def fail(msg: str):
    sys.exit(f"chip_smoke: FAILED: {msg}")


def tpu_devices(count: int) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"JAX finds no TPU (first device: {devices[0].platform}); nothing was run")
    if len(devices) < count:
        fail(f"{count} chips asked for, JAX finds {len(devices)}")
    return devices


class _Tee(io.TextIOBase):
    """Echo the launcher's output while keeping a copy to parse."""

    def __init__(self, *streams):
        self._streams = streams

    def write(self, s: str) -> int:
        for st in self._streams:
            st.write(s)
        return len(s)

    def flush(self) -> None:
        for st in self._streams:
            st.flush()


def run_trainer(argv: list[str], steps: int) -> tuple[float, int, list[float]]:
    """``repro.launch.train.main(argv)`` in this process; returns (compile
    seconds, Pallas kernel calls in the compiled step, per-step losses)."""
    from repro.launch import train

    buf = io.StringIO()
    with contextlib.redirect_stdout(_Tee(sys.stdout, buf)):
        rc = train.main(argv)
    out = buf.getvalue()
    if rc != 0:
        fail(f"trainer exited {rc}")
    m = re.search(r"compiled step in (\S+) s \((\d+) Pallas kernel calls\)", out)
    if m is None:
        fail("trainer printed no compile line")
    losses = [float(x) for x in re.findall(r"^step\s+\d+ loss (\S+)", out, re.M)]
    if len(losses) != steps or not all(math.isfinite(x) for x in losses):
        fail(f"expected {steps} finite losses, got {losses}")
    if int(m.group(2)) == 0:
        fail("the compiled step holds no Pallas kernel")
    return float(m.group(1)), int(m.group(2)), losses


def bucket_rows(cfg, n_clients: int, seq: int, device):
    """First and last gradient bucket of ``cfg``, one fp32 row per client.

    The weights are random from seed 0; client j differentiates the loss
    of its own one-sample batch.  The buckets are those of the train
    step's plan (``plan_buckets`` at the default bucket target).  The
    clients run one after another on ``device``, one compiled program for
    all (a program holding several full-width backward passes compiles for
    minutes).  Returns ``((n, m_first), (n, m_last))`` on ``device``.
    """
    import jax
    import jax.numpy as jnp

    from repro.core.compressors import plan_buckets
    from repro.data.synthetic import lm_batch
    from repro.dist.train_step import TrainStepConfig
    from repro.models import init_lm, transformer

    params, _ = init_lm(jax.random.key(0), cfg)
    params = jax.device_put(params, device)
    bp = plan_buckets([x.size for x in jax.tree.leaves(params)],
                      TrainStepConfig().bucket_elements)
    picks = (bp.ranges[0], bp.ranges[-1])

    @jax.jit
    def first_last(p, batch):
        g = jax.tree.leaves(jax.grad(lambda q: transformer.loss_fn(cfg, q, batch))(p))
        return tuple(jnp.concatenate([g[i].reshape(-1).astype(jnp.float32)
                                      for i in range(a, b)]) for a, b in picks)

    rows = [first_last(params, jax.device_put(lm_batch(cfg, jnp.uint32(1000 + j), 1, seq),
                                              device))
            for j in range(n_clients)]
    del params
    return tuple(jnp.stack(r) for r in zip(*rows))


def _stats_checks(g, e) -> dict:
    """Fused EF-correct + statistics kernel vs its blockwise oracle.

    The corrected bucket, the histogram counts (bins come from the fp32
    bits) and the max are exact.  The float sums go through ``log`` and
    reductions that Mosaic and XLA evaluate differently, so they are held to
    1e-6 of their scale: the total ln-mass for the per-bin ln-sums,
    ``sqrt(n · Σg²)`` (a bound on Σ|g|) for Σg, Σg² itself for Σg².
    """
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    c_k, s_k = ops.ef_correct_stats(g, e)
    c_o, tile = ref.ef_correct_stats(g, e)
    scale_sum = jnp.sqrt(g.size * tile[4, 0])
    return {
        "corrected_mismatches": (jnp.sum(c_k != c_o), 0),
        "count_mismatches": (jnp.sum(s_k.counts != tile[0]), 0),
        "max_mismatch": (jnp.sum(s_k.g_max != tile[2, 0]), 0),
        "log_sums_diff_over_ln_mass": (
            jnp.max(jnp.abs(s_k.log_sums - tile[1])) / jnp.sum(jnp.abs(tile[1])), 1e-6),
        "sum_diff_over_scale": (jnp.abs(s_k.g_sum - tile[3, 0]) / scale_sum, 1e-6),
        "sumsq_rel_diff": (jnp.abs(s_k.g_sumsq - tile[4, 0]) / tile[4, 0], 1e-6),
    }


def _faithful_mean(key, sb, stats, method: str, use_pallas: bool):
    """One bucket's n-peer faithful sync (``repro.dist.reference``)."""
    from repro.core.compressors import CompressorConfig
    from repro.dist import reference

    cfg = CompressorConfig(method=method, bits=BITS)
    means, _ = reference.bucketed_faithful_ring_mean(cfg, [sb], key, use_pallas,
                                                     stats=[[st] for st in stats])
    return means[0]


def _encode(key, g, stat, method: str, use_pallas: bool):
    """Plan from ``stat``, words-only encode: (words, levels, alpha)."""
    from repro.core import codecs
    from repro.core.compressors import CompressorConfig, plan_from_stats

    cfg = CompressorConfig(method=method, bits=BITS)
    meta = plan_from_stats(cfg, *stat[:3])
    return codecs.encode_pack(cfg, g, meta, key, use_pallas), meta.levels, meta.alpha


def _decode_rows(words, levels, m: int, method: str, use_pallas: bool):
    from repro.core import codecs
    from repro.core.compressors import CompressorConfig

    return codecs.decode_rows(CompressorConfig(method=method, bits=BITS), words, levels, m,
                              use_pallas)


def codec_checks(rows, key) -> dict:
    """Kernel vs jnp oracle on stacked (n, m) gradient buckets.

    Per bucket: the fused EF-correct + statistics pass of every peer, then
    for a codebook (``tnqsgd``) and a uniform (``tqsgd``) method an n-peer
    ``faithful`` sync, each peer's words-only encode and the per-peer
    decode — each through the kernels and through the oracles, planned from
    the same (kernel) statistics.  Returns ``{name: (measured, limit)}``; a
    check passes iff ``measured <= limit``.  Limits are those of the kernel
    tests: words, codebook values and the exact statistics rows bit-exact,
    uniform values within 4 ulp of the alpha scale; the statistics' float
    sums, which the tests hold exact only in interpret mode, within 1e-6 of
    their scale (see :func:`_stats_checks`).  Each piece is its own
    program, so at most one bucket's working set is live on the device.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    stats_fn = jax.jit(_stats_checks)
    faithful = jax.jit(_faithful_mean, static_argnums=(3, 4))
    encode = jax.jit(_encode, static_argnums=(3, 4))
    decode_rows = jax.jit(_decode_rows, static_argnums=(2, 3, 4))
    max_diff = jax.jit(lambda a, b: jnp.max(jnp.abs(a - b)))
    mismatches = jax.jit(lambda a, b: jnp.sum(a != b))

    def ulps4(x) -> float:
        return float(4 * np.spacing(np.float32(x)))

    out = {}
    for b, sb in enumerate(rows):
        n, m = sb.shape
        stats = []
        for i in range(n):
            g, e = sb[i], 0.125 * sb[(i + 1) % n]   # an EF residual of gradient scale
            for name, v in jax.device_get(stats_fn(g, e)).items():
                out[f"bucket{b}.peer{i}.ef_correct_stats.{name}"] = v
            stats.append(tuple(ops.bucket_stats(g)))
            del g, e
        kb = jax.random.fold_in(key, b)
        for method in ("tnqsgd", "tqsgd"):
            exact = method == "tnqsgd"
            pre = f"bucket{b}.{method}"
            words, levels, alphas = [], [], []
            for i in range(n):
                ki = jax.random.fold_in(kb, i)
                w_o, lv, al = encode(ki, sb[i], stats[i], method, False)
                w_k, _, _ = encode(ki, sb[i], stats[i], method, True)
                out[f"{pre}.peer{i}.encode_pack_word_mismatches"] = (
                    int(mismatches(w_k, w_o)), 0)
                words.append(w_o)
                levels.append(lv)
                alphas.append(abs(float(al)))
                del w_k
            words, levels = jnp.stack(words), jnp.stack(levels)
            got = max_diff(decode_rows(words, levels, m, method, True),
                           decode_rows(words, levels, m, method, False))
            out[f"{pre}.decode_rows_max_diff"] = (
                float(got), 0.0 if exact else ulps4(max(alphas)))
            del words
            mean_k = faithful(kb, sb, stats, method, True)
            mean_o = faithful(kb, sb, stats, method, False)
            out[f"{pre}.faithful_mean_max_diff"] = (
                float(max_diff(mean_k, mean_o)), 0.0 if exact else ulps4(sum(alphas)))
            del mean_k, mean_o
    return jax.device_get(out)


def report(checks: dict) -> None:
    """Print the worst reading of each check over peers; fail on any breach."""
    worst: dict = {}
    for name, (got, limit) in checks.items():
        kind = re.sub(r"\.peer\d+", "", name)
        got, limit = float(got), float(limit)
        prev = worst.get(kind, (got, limit))
        # NaN is the worst reading there is
        worse = math.isnan(got) or (not math.isnan(prev[0]) and got > prev[0])
        worst[kind] = (got if worse else prev[0], min(prev[1], limit))
    for kind, (got, limit) in sorted(worst.items()):
        print(f"  {kind}: {got!r} (limit {limit:.3g})")
    bad = sorted(k for k, (got, limit) in checks.items() if not float(got) <= float(limit))
    if bad:
        fail(f"{len(bad)} kernel-vs-oracle checks out of bounds: {bad[:8]}")


def one_chip(devices) -> None:
    import jax

    from repro.configs import get_config

    argv = ["--arch", ARCH, "--mesh", "1", "--sync", "two_phase", "--method", "tnqsgd",
            "--bits", str(BITS), "--ef", "--steps", str(STEPS), "--batch", str(BATCH),
            "--seq", str(SEQ)]
    print(f"phase train: {' '.join(argv)}", flush=True)
    compile_s, kernels, losses = run_trainer(argv, STEPS)
    peak = (devices[0].memory_stats() or {}).get("peak_bytes_in_use")
    print(f"train: ok, compile {compile_s} s, {kernels} Pallas kernel calls in the step, "
          f"losses {losses}, peak_bytes_in_use {peak}", flush=True)

    rows = bucket_rows(get_config(ARCH), CHECK_PEERS, SEQ, devices[0])
    print(f"phase kernels-vs-oracle: {CHECK_PEERS} client gradients, buckets of "
          f"{rows[0].shape[1]} and {rows[1].shape[1]} elements", flush=True)
    checks = codec_checks(rows, jax.random.key(7))
    report(checks)
    print(f"kernels-vs-oracle: ok, {len(checks)} checks", flush=True)


def four_chips(devices) -> None:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.core.compressors import CompressorConfig
    from repro.dist import reference, sharding
    from repro.dist.train_step import TrainStepConfig, _sync_buckets
    from repro.launch.mesh import make_mesh_from_spec

    steps = 2
    argv = ["--arch", ARCH, "--mesh", "4", "--sync", "two_phase", "--method", "tnqsgd",
            "--bits", str(BITS), "--ef", "--steps", str(steps), "--batch", str(2 * CLIENTS),
            "--seq", str(SEQ)]
    print(f"phase train (4 chips): {' '.join(argv)}", flush=True)
    compile_s, kernels, losses = run_trainer(argv, steps)
    print(f"train: ok, compile {compile_s} s, {kernels} Pallas kernel calls in the step, "
          f"losses {losses}", flush=True)

    rows4 = bucket_rows(get_config(ARCH), CLIENTS, SEQ, devices[0])
    print(f"phase sync-vs-reference: buckets of {rows4[0].shape[1]} and "
          f"{rows4[1].shape[1]} elements", flush=True)
    key = jax.random.key(11)
    for sync, spec in FOUR_CHIP_MODES:
        mesh = make_mesh_from_spec(spec)
        ids = {d.id for d in mesh.devices.flat}
        if len(ids) != 4:
            fail(f"mesh {spec} spans devices {sorted(ids)}, not 4 distinct chips")
        dp = sharding.manual_axes(mesh)
        dp_sizes = tuple(mesh.shape[a] for a in dp)
        rows = [jax.device_put(r, NamedSharding(mesh, P(dp))) for r in rows4]
        if {s.device.id for r in rows for s in r.addressable_shards} != ids:
            fail(f"{sync}: gradient rows are not spread over the 4 chips")
        ts = TrainStepConfig(sync=sync, compressor=CompressorConfig(
            method="tnqsgd", bits=BITS, use_pallas=True))

        def body(key, *stacked, ts=ts, dp=dp):
            out, _, _, _, _ = _sync_buckets(ts, [x[0] for x in stacked], key, dp)
            return tuple(o[None] for o in out)

        got = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P(),) + (P(dp),) * len(rows),
            out_specs=tuple(P(dp) for _ in rows),
            axis_names=set(mesh.axis_names), check_vma=False))(key, *rows)
        # to the host, so the replay has the first chip's memory to itself
        per_chip = [{s.device.id: np.asarray(s.data)[0] for s in g.addressable_shards}
                    for g in got]
        del got, rows
        want = jax.jit(lambda key, *ls, ts=ts, dp_sizes=dp_sizes: tuple(
            reference.reference_sync(ts, list(ls), dp_sizes, key)))(key, *rows4)
        for b, (shards, w) in enumerate(zip(per_chip, want)):
            if set(shards) != ids:
                fail(f"{sync}: synced bucket {b} is not on the 4 chips")
            first = shards[min(shards)]
            for dev, x in shards.items():
                if not np.array_equal(x, first):
                    fail(f"{sync}: chip {dev} holds a different synced mean (bucket {b})")
            w = np.asarray(w)
            diff = float(np.max(np.abs(first - w)))
            tol = 0.0 if sync != "dsgd" else 1e-6 * (1.0 + float(np.max(np.abs(w))))
            print(f"  {sync} mesh {spec} bucket {b} ({w.size} elements): 4 chips agree, "
                  f"max |mesh - reference| {diff!r} (limit {tol!r})", flush=True)
            if not diff <= tol:
                fail(f"{sync}: mesh mean differs from the reference replay on bucket {b}")
        del want, per_chip
    print("sync-vs-reference: ok", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip sync phase")
    args = ap.parse_args(argv)
    devices = tpu_devices(args.chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    print(f"device: {devices[0].device_kind} x{len(devices)}; compile cache "
          f"{enable_compile_cache()}", flush=True)
    if args.chips == 4:
        four_chips(devices)
    else:
        one_chip(devices)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {"platform": d.platform, "kind": d.device_kind,
                                             "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
