"""Where the codec's first synced gradient departs from its input, on the chip.

    python3 bench/codec_look.py --workload <cell> --seeds 1,2 [--out file.json]

For a cell that syncs through a quantizing codec with error feedback on
one data peer (the faithful ring's single-peer path), this runs the first
step of the timed path and splits its state back into leaves: the synced
gradient ``d`` the optimizer received (momentum - wd * p0), the EF
residual ``e`` and the codec's input ``c = d + e``.  Per bucket it plans
``c`` again with the program's own statistics and plan (truncation level
``alpha``, the tail's ``g_min`` and ``g_max``); per leaf it prints the
norms of ``c``, of ``clip(c, -alpha, alpha)`` and of ``d``, and the share
of elements beyond ``alpha``.  Where ``|d|`` follows ``|clip(c)|`` and
not ``|c|``, the synced gradient's departure from the exact one is the
codec's truncation.

On the first seed it also polls the device's ``bytes_in_use`` while that
step runs, against the compiler's plan for the step.  The benchmark's own
runs never run this.  Prints one JSON object (also written to ``--out``).
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
os.environ.setdefault("TPU_LOG_DIR", "disabled")
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def memory_during_step(program, state, batch, step_arg) -> tuple[list, dict]:
    """One step dispatched, ``bytes_in_use`` polled until its loss is ready."""
    dev = next(iter(program.mesh.devices.flat))
    before = dev.memory_stats() or {}
    out = program.compiled(*state, batch, step_arg)
    seen, polls = int(before.get("bytes_in_use", 0)), 0
    loss = out[-1]["loss"]
    while not loss.is_ready():
        seen = max(seen, int((dev.memory_stats() or {}).get("bytes_in_use", 0)))
        polls += 1
    after = dev.memory_stats() or {}
    ma = program.compiled.memory_analysis()
    reading = {
        "bytes_in_use_before": int(before.get("bytes_in_use", 0)),
        "bytes_in_use_max_while_running": seen, "polls": polls,
        "bytes_in_use_after": int(after.get("bytes_in_use", 0)),
        "peak_bytes_in_use_after": int(after.get("peak_bytes_in_use", 0)),
        "stats_keys": sorted(after),
        "compiler": {"arguments": ma.argument_size_in_bytes,
                     "outputs": ma.output_size_in_bytes,
                     "aliased": ma.alias_size_in_bytes,
                     "temporaries": ma.temp_size_in_bytes}}
    return list(out[:-1]), reading


def look(program, state, seed: int) -> list[dict]:
    import jax
    import jax.numpy as jnp

    from repro.core import codecs, distributions
    from repro.kernels.stats import bin_edges

    cfg = program.ts.compressor
    wd = float(program.workload["optimizer"].get("weight_decay", 0.0))
    paths = ["/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
             for path, _ in jax.tree_util.tree_flatten_with_path(program.shapes)[0]]
    mom, ef = jax.tree.leaves(state[1]), state[2]
    p0 = list(program._initial_leaves(seed))

    @jax.jit
    def bucket_plan(flat):
        stat = codecs._bucket_stats(flat, cfg.use_pallas)
        tail = distributions.tail_from_histogram(stat[0], stat[1], stat[2], bin_edges(),
                                                 gmin_quantile=cfg.gmin_quantile)
        pln = codecs.plan_from_stats(cfg, stat[0], stat[1], stat[2])
        return pln.alpha, tail.g_min, tail.g_max, tail.gamma

    @jax.jit
    def leaf_split(m, p, e, c_wd):
        d = m.astype(jnp.float32) - c_wd * p.astype(jnp.float32)
        return d.reshape(-1), d.reshape(-1) + e

    @jax.jit
    def leaf_norms(d, c, alpha):
        nrm = lambda x: jnp.sqrt(jnp.sum(jnp.square(x)))
        return (nrm(c), nrm(jnp.clip(c, -alpha, alpha)), nrm(d),
                jnp.mean((jnp.abs(c) > alpha).astype(jnp.float32)))

    rows, leaf, b = [], 0, 0
    while leaf < len(mom):
        e_b = jnp.mean(ef[b], axis=0)
        parts, off = [], 0
        while leaf < len(mom) and off + math.prod(mom[leaf].shape) <= e_b.shape[0]:
            n = math.prod(mom[leaf].shape)
            c_wd = jnp.float32(wd if p0[leaf].ndim > 1 else 0.0)
            d, c = leaf_split(mom[leaf], p0[leaf], jax.lax.slice(e_b, (off,), (off + n,)), c_wd)
            parts.append((leaf, d, c))
            off, leaf = off + n, leaf + 1
        if off != e_b.shape[0]:
            raise ValueError(f"bucket {b} holds {e_b.shape[0]} elements, its leaves {off}")
        alpha, g_min, g_max, gamma = bucket_plan(jnp.concatenate([c for _, _, c in parts]))
        for i, d, c in parts:
            nc, nclip, nd, share = jax.device_get(leaf_norms(d, c, alpha))
            rows.append({"leaf": paths[i], "bucket": b, "elements": int(d.shape[0]),
                         "norm_input": float(nc), "norm_clipped_input": float(nclip),
                         "norm_synced": float(nd), "share_beyond_alpha": float(share),
                         "alpha": float(alpha), "g_min": float(g_min), "g_max": float(g_max),
                         "gamma": float(gamma)})
        b += 1
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]

    import numpy as np

    from bench import harness, inputs
    from bench.system import Program

    config, workload = harness.load_cell(args.workload)
    harness.enable_compile_cache()
    devices = harness.chip_devices(int(workload["chips"]))
    program = Program(config, workload, devices)
    if not program.ef or program.peers != 1:
        raise SystemExit("the look needs a cell with error feedback on one data peer")
    t = time.perf_counter()
    program.compile()
    out = {"workload": args.workload, "compile_s": time.perf_counter() - t, "seeds": []}
    for k, seed in enumerate(seeds):
        state = program.init_state(seed)
        batch = program.batch_fn()(inputs.key_words(seed), np.uint32(0))
        if k == 0:
            state, out["memory"] = memory_during_step(program, state, batch,
                                                      program.step_arg(0))
            print(json.dumps({"memory": out["memory"]}), file=sys.stderr, flush=True)
        else:
            state, _ = program.step(state, batch, 0)
        rows = look(program, state, seed)
        out["seeds"].append({"seed": seed, "leaves": rows})
        for r in rows:
            print(json.dumps({"seed": seed, **r}), file=sys.stderr, flush=True)
        del state
    text = json.dumps(out)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
