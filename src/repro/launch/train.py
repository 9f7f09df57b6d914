"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs on whatever devices exist (CPU: mesh 1x1 by default).  Use
``--fake-devices N`` to exercise the distributed path on a host mesh.  On a
TPU backend the gradient codec runs the Pallas kernels (``use_pallas``);
elsewhere it runs their jnp oracles.  The step is compiled ahead of the
loop, and the compile time and the number of Pallas kernel calls in the
compiled step are printed.
"""
import argparse
import os
import sys
import time


def _early_args(argv):
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--fake-devices", type=int, default=0)
    ns, _ = ap.parse_known_args(argv)
    return ns


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    early = _early_args(argv)
    if early.fake_devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={early.fake_devices}"
        ).strip()

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.checkpoint.io import load_checkpoint, latest_step, save_checkpoint
    from repro.configs import get_config, list_archs, reduced as make_reduced
    from repro.core.codecs import known_methods
    from repro.core.compressors import CompressorConfig
    from repro.data.synthetic import lm_batch
    from repro.dist.train_step import SYNC_MODES, TrainStepConfig, make_train_step
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_mesh_from_spec
    from repro.models import init_lm
    from repro.optim.optimizers import get_optimizer

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--reduced", action="store_true", help="reduced config (CPU-trainable)")
    ap.add_argument("--mesh", default=None, help="e.g. 4x2; default all devices data-parallel")
    ap.add_argument("--fake-devices", type=int, default=0)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--sync", default="two_phase", choices=SYNC_MODES)
    ap.add_argument("--method", default="tnqsgd", choices=known_methods())
    ap.add_argument("--bits", type=int, default=3)
    ap.add_argument("--rank", type=int, default=4,
                    help="factor rank for rank-based codecs (powersgd)")
    ap.add_argument("--bucket-mb", type=float, default=4.0,
                    help="bucketed-codec target bucket size; 0 = per-leaf codec")
    ap.add_argument("--ef", action="store_true",
                    help="error feedback on the worker-side compressor (not checkpointed)")
    ap.add_argument("--dropout-rate", type=float, default=0.0,
                    help="elastic: per-(step,peer) dropout probability under "
                         "the deterministic counter-hash schedule (0 = full "
                         "participation)")
    ap.add_argument("--chaos-trace", default=None,
                    help="elastic: scripted live-mask JSON trace "
                         "(repro.elastic.save_trace format); overrides "
                         "--dropout-rate")
    ap.add_argument("--fp16-threshold", type=int, default=0,
                    help="buckets of at most this many local elements ship "
                         "raw fp16 instead of the quantizer (0 = off)")
    ap.add_argument("--adaptive", action="store_true",
                    help="online tail telemetry + wire-budget bit allocation per bucket")
    ap.add_argument("--wire-budget-mb", type=float, default=0.0,
                    help="adaptive wire budget (bytes/step, MB); 0 = match the "
                         "fixed --bits allocation")
    ap.add_argument("--replan-every", type=int, default=10,
                    help="steps between adaptive bit replans")
    ap.add_argument("--optimizer", default="momentum_sgd")
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=1)
    ap.add_argument("--obs-dir", default=None,
                    help="write observability JSONL here; turns on in-graph "
                         "per-bucket compression metrics (see repro.obs)")
    ap.add_argument("--obs-every", type=int, default=1,
                    help="steps between compression-metric events")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = make_reduced(cfg)
    mesh = make_mesh_from_spec(args.mesh or str(len(jax.devices())))
    # the kernels on the chip; their jnp oracles everywhere else
    use_pallas = jax.default_backend() == "tpu"
    print(f"arch={cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab} batch={args.batch} seq={args.seq} "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"sync={args.sync} method={args.method} bits={args.bits} "
          f"codec={'pallas' if use_pallas else 'jnp'}")

    # repro: allow REPRO204 (CLI entry point: the reproducible demo seed)
    params, logical = init_lm(jax.random.key(0), cfg)
    opt = get_optimizer(args.optimizer, lr=args.lr) if args.optimizer == "momentum_sgd" else get_optimizer(args.optimizer)
    acfg = None
    if args.adaptive:
        from repro.adaptive.controller import AdaptiveConfig

        acfg = AdaptiveConfig(wire_budget_mb=args.wire_budget_mb,
                              replan_every=args.replan_every)
    ecfg = None
    if args.chaos_trace:
        from repro.elastic import load_trace

        ecfg = load_trace(args.chaos_trace).elastic()
        print(f"elastic: chaos trace {args.chaos_trace} "
              f"({len(ecfg.trace)} steps, wraps modulo length)")
    elif args.dropout_rate > 0.0:
        from repro.elastic import ElasticConfig

        ecfg = ElasticConfig(rate=args.dropout_rate)
        print(f"elastic: scheduled dropout rate {args.dropout_rate}")
    if ecfg is not None and args.bucket_mb <= 0:
        ap.error("--dropout-rate/--chaos-trace require the bucketed codec "
                 "(--bucket-mb > 0)")
    obs_sink = obs_rec = drift_mon = None
    if args.obs_dir:
        from repro.obs import DriftMonitor, JsonlSink, SpanRecorder

        if args.bucket_mb <= 0:
            ap.error("--obs-dir requires the bucketed codec (--bucket-mb > 0)")
        obs_sink = JsonlSink(os.path.join(args.obs_dir, "events.jsonl"))
        obs_rec = SpanRecorder(sink=obs_sink)
        drift_mon = DriftMonitor(sink=obs_sink)
    ts = TrainStepConfig(sync=args.sync,
                         compressor=CompressorConfig(method=args.method, bits=args.bits,
                                                     rank=args.rank,
                                                     approx_gmin=args.adaptive,
                                                     use_pallas=use_pallas),
                         bucket_mb=args.bucket_mb, error_feedback=args.ef, adaptive=acfg,
                         elastic=ecfg, fp16_threshold=args.fp16_threshold,
                         metrics_compression=args.obs_dir is not None)
    batch0 = lm_batch(cfg, jnp.uint32(0), args.batch, args.seq)
    opt_state = opt.init(params)
    stepper = None
    if args.adaptive:
        from repro.adaptive.runtime import AdaptiveStepper

        stepper = AdaptiveStepper(cfg, mesh, logical, opt, ts, batch0,
                                  opt_state_like=jax.eval_shape(lambda: opt_state),
                                  params_like=params, obs=obs_rec, drift=drift_mon)
        pspecs = stepper.pspecs
        print(f"adaptive: {len(stepper.sizes)} buckets, wire budget "
              f"{stepper.budget/2**20:.2f} MB/step, replan every {acfg.replan_every}")
    else:
        step_fn, pspecs = make_train_step(cfg, mesh, logical, opt, ts, batch0,
                                          opt_state_like=jax.eval_shape(lambda: opt_state))

    sh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs, is_leaf=lambda x: isinstance(x, P))
    start = 0
    if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        (params, opt_state), start = load_checkpoint(args.ckpt_dir, (params, opt_state))
        print(f"resumed from step {start}")
    params = jax.device_put(params, sh)
    # optimizer state mirrors the param tree -> same shardings per leaf
    from repro.dist.train_step import _opt_specs, init_ef_state
    from jax.sharding import PartitionSpec as _P
    o_specs = _opt_specs(jax.eval_shape(lambda: opt_state), params, pspecs)
    opt_state = jax.device_put(opt_state, jax.tree.map(lambda s: NamedSharding(mesh, s), o_specs,
                                                       is_leaf=lambda x: isinstance(x, _P)))
    ef_state = init_ef_state(params, mesh, pspecs, ts) if args.ef else None
    tstate = stepper.init_telemetry() if stepper is not None else None
    if stepper is None:
        t0 = time.perf_counter()
        state = (params, opt_state) + ((ef_state,) if args.ef else ())
        step_fn = step_fn.lower(*state, batch0, jnp.uint32(start)).compile()
        n_kernels = step_fn.as_text().count('custom_call_target="tpu_custom_call"')
        print(f"compiled step in {time.perf_counter() - t0:.1f} s "
              f"({n_kernels} Pallas kernel calls)", flush=True)

    import contextlib

    for i in range(start, start + args.steps):
        b = lm_batch(cfg, jnp.uint32(i), args.batch, args.seq)
        span = obs_rec.span("train.step", step=i) if obs_rec is not None else contextlib.nullcontext()
        with span:
            if stepper is not None:
                prev_bits = stepper.bits
                params, opt_state, ef_state, tstate, m = stepper.step(
                    params, opt_state, ef_state, tstate, b, i)
                if stepper.bits != prev_bits:
                    from repro.launch.report import adaptive_table
                    plan, tails = stepper.plan, stepper.tails
                    print(f"step {i}: replanned bits -> {plan.bits} "
                          f"({plan.spend_bytes}/{plan.budget_bytes} B/step)")
                    print(adaptive_table(stepper.sizes, plan.bits, plan.alphas,
                                         gammas=None if tails is None else tails.gamma,
                                         rhos=None if tails is None else tails.rho))
            elif args.ef:
                params, opt_state, ef_state, m = step_fn(params, opt_state, ef_state, b, jnp.uint32(i))
            else:
                params, opt_state, m = step_fn(params, opt_state, b, jnp.uint32(i))
        if obs_sink is not None and "compression" in m and i % max(args.obs_every, 1) == 0:
            from repro.obs import metrics_event

            comp = jax.device_get(m["compression"])
            event = metrics_event(i, comp)
            obs_sink.write(event)
            if drift_mon is not None:
                drift_mon.check_ratio([row["realized_mse"] for row in event["buckets"]],
                                      [row["predicted_mse"] for row in event["buckets"]], step=i)
        lvs = ""
        if ecfg is not None and "live_count" in m:
            lv = jax.device_get(m["live"]).reshape(-1)
            n_peers, n_live = int(lv.shape[0]), int(round(float(m["live_count"][0])))
            lvs = f" live {n_live}/{n_peers}"
            if n_live < n_peers and obs_sink is not None:
                from repro.obs.sink import SCHEMA_VERSION

                obs_sink.write({"v": SCHEMA_VERSION, "kind": "dropout",
                                "step": i, "live": n_live, "n_peers": n_peers,
                                "dropped": [p for p in range(n_peers)
                                            if float(lv[p]) == 0.0]})
            if drift_mon is not None:
                drift_mon.check_participation(n_live / n_peers, step=i)
        if args.log_every and i % args.log_every == 0:
            gn = f" gnorm {float(m['gnorm'][0]):.3f}" if "gnorm" in m else ""
            print(f"step {i:5d} loss {float(m['loss'][0]):.4f}{gn}{lvs}", flush=True)
        if args.ckpt_every and args.ckpt_dir and (i + 1) % args.ckpt_every == 0:
            host_p = jax.tree.map(lambda x: jax.device_get(x), (params, opt_state))
            save_checkpoint(args.ckpt_dir, i + 1, host_p)
            print(f"checkpointed step {i+1}")
    if obs_sink is not None:
        obs_sink.close()
        print(f"obs: {obs_sink.n_written} events -> {obs_sink.path} "
              f"(render with `python -m repro.obs report --dir {args.obs_dir}`)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
