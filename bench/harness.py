"""One benchmark run: set-up, the first steps checked, the timed window, the result line.

Set-up (``setup_s``, from process start to the first timed dispatch):
imports, the mesh, the step compiled ahead of time (or loaded from the
persistent cache), the weights made on the device from ``--seed`` in one
jitted call, and the first ``CHECKED_STEPS`` steps through the same
compiled step and batch feed the window uses.  Those steps are also the
warm-up: every program the window calls has run once before it opens.

The window: one synchronous step after another from step
``CHECKED_STEPS`` on, each on a batch of its own made on the device from
the seed, the host blocking on every step's loss as ``repro.launch.train``
does, until ``--seconds`` have passed.  ``tokens_per_s`` counts every
position of every completed step (image-patch positions included) over
the window's wall time; compiles inside the window are counted and must
be none.  With ``--trace 1`` the profiler records the same window and the
per-layer metrics are read from its trace (:mod:`bench.trace`).

The device memory peak (``device.memory_peak_bytes``) is read as the
first step completes, before the comparison's own readings allocate
anything; the compiler's plan for the step is logged beside it.  After the window the
program's state is freed, and the plain reference (:mod:`bench.reference`) trains the same
first steps from the same seed; :mod:`bench.check` compares the two.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
CHECKED_STEPS = 3


class NoChip(RuntimeError):
    pass


def load_cell(name: str) -> tuple[dict, dict]:
    """``bench/workloads/<name>.json`` and the configuration file it names."""
    wpath = BENCH / "workloads" / f"{name}.json"
    if not wpath.is_file():
        raise FileNotFoundError(f"no workload file {wpath}")
    workload = json.loads(wpath.read_text())
    config = json.loads((BENCH / "configs" / f"{workload['config']}.json").read_text())
    return config, workload


def enable_compile_cache() -> str:
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = env or str(BENCH / ".jax_cache")
    if not env:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def chip_devices(chips: int) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (first device: {devices[0].platform})")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds {len(devices)}")
    return devices[:chips]


class CompileCounter:
    """Counts backend compiles reported through ``jax.monitoring``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1


def program_first_steps(program, seed: int, steps: int = CHECKED_STEPS):
    """Run the first ``steps`` steps; return (state, readings, batch fn, words).

    ``readings["peak_bytes"]`` is the device memory peak as the first step
    completes: the program's own (its weights, state and one step), read
    before the comparison's readings allocate anything on the device.  The
    window runs the same program on the same buffers."""
    from bench import inputs

    words = inputs.key_words(seed)
    make_batch = program.batch_fn()
    wd = float(program.workload["optimizer"].get("weight_decay", 0.0))
    state = program.init_state(seed)
    losses, grad_norms = [], None
    for i in range(steps):
        state, m = program.step(state, make_batch(words, np.uint32(i)), i)
        losses.append(float(np.asarray(m["loss"])[0]))
        if i == 0:
            peak = memory_peak(program.mesh.devices.flat)
            grad_norms = program.leaf_norms(program.grad_state(state[1]), seed, wd, 0.0)
            if program.ef:
                sync_in = program.sync_in_norms(program.grad_state(state[1]), state[2], seed, wd)
    update_norms = program.leaf_norms(state[0], seed, 1.0, 1.0)
    readings = {"losses": losses, "grad_norms": grad_norms, "update_norms": update_norms,
                "peak_bytes": peak}
    if program.ef:
        readings["sync_in_norms"] = sync_in
    if program.mesh.size > 1:
        readings["replica_gap"] = replica_gap(state[0])
    return state, readings, make_batch, words


def replica_gap(tree) -> float:
    """Largest difference between chips' copies of the same slice of a leaf."""
    import jax
    import jax.numpy as jnp

    worst = 0.0
    for x in jax.tree.leaves(tree):
        groups = {}
        for sh in x.addressable_shards:
            groups.setdefault(str(sh.index), []).append(sh.data)
        for copies in groups.values():
            first = copies[0]
            for other in copies[1:]:
                d = jnp.max(jnp.abs(first.astype(jnp.float32)
                                    - jax.device_put(other, first.devices().pop())
                                    .astype(jnp.float32)))
                worst = max(worst, float(d))
    return worst


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return max(peaks)


def per_layer_specs(cell: str) -> list[dict]:
    """``BENCHMARK.json``'s per-layer metrics that report in ``cell``."""
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    return [m for m in spec.get("per_layer", [])
            if "workloads" not in m or cell in m["workloads"]]


def load_metric(name: str):
    """The reader ``bench/metrics/<name>.py``, loaded by its file name."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name.replace('.', '_')}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run(argv=None, *, require_chip: bool = True, cell=None, program_hook=None) -> dict:
    """One run; returns the result dict (the caller prints it).

    ``require_chip=False`` (the CPU's devices and the kernels' jnp oracles),
    ``cell`` (a ``(config, workload)`` pair in place of the files) and
    ``program_hook`` (called with the built program, to plant a fault) are
    for the benchmark's own tests.
    """
    t0 = time.perf_counter()
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also copy the raw trace directory here")
    args = ap.parse_args(argv)

    config, workload = cell if cell is not None else load_cell(args.workload)
    import jax

    cache_dir = enable_compile_cache()
    devices = chip_devices(int(workload["chips"])) if require_chip else \
        jax.devices()[:int(workload["chips"])]

    from bench import check, reference
    from bench.system import Program

    counter = CompileCounter()
    program = Program(config, workload, devices, use_pallas=require_chip)
    if program_hook is not None:
        program_hook(program)
    t_c = time.perf_counter()
    compiled = program.compile()
    log(f"step compiled or loaded in {time.perf_counter() - t_c:.2f} s "
        f"(cache {cache_dir}); {program.kernel_calls()} Pallas kernel calls")
    ma = compiled.memory_analysis()
    if ma is not None:
        log("compiler memory per device: "
            f"arguments {ma.argument_size_in_bytes} B, outputs {ma.output_size_in_bytes} B, "
            f"aliased {ma.alias_size_in_bytes} B, temporaries {ma.temp_size_in_bytes} B")

    state, prog_readings, make_batch, words = program_first_steps(program, args.seed)
    log(f"first {CHECKED_STEPS} losses {prog_readings['losses']}")

    tracer = None
    if args.trace:
        from bench import trace as trace_mod

        tracer = trace_mod.Tracer(BENCH / ".trace" / f"{args.workload}-{args.seed}")
    rows, seq = program.rows, program.seq
    compiles_before = counter.count
    t_start = time.perf_counter()
    setup_s = t_start - t0
    if tracer is not None:
        tracer.start()
    i, steps, failed = CHECKED_STEPS, 0, 0
    loss = float("nan")
    while True:
        state, m = program.step(state, make_batch(words, np.uint32(i)), i)
        loss = float(np.asarray(m["loss"])[0])
        failed += not np.isfinite(loss)
        i += 1
        steps += 1
        if time.perf_counter() - t_start >= args.seconds:
            break
    t_end = time.perf_counter()
    if tracer is not None:
        tracer.stop()
    window_s = t_end - t_start
    window_compiles = counter.count - compiles_before
    tokens_per_s = steps * rows * seq / window_s
    peak = prog_readings["peak_bytes"]
    log(f"window: {steps} steps in {window_s:.3f} s, last loss {loss:.4f}, "
        f"{window_compiles} compiles inside; memory peak {peak} B after the first step, "
        f"{memory_peak(program.mesh.devices.flat)} B after the window and the readings")

    breakdown = None
    metrics = {}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(list(program.mesh.devices.flat)), "memory_peak_bytes": peak}
    if tracer is not None:
        from bench import flops, trace as trace_mod

        ctx = trace_mod.reduce(tracer.directory, program=program, steps=steps,
                               flops_per_step=flops.per_step(config, workload),
                               hlo_text=compiled.as_text())
        device["busy_s"] = ctx.busy_s
        device["window_s"] = ctx.window_s
        for spec in per_layer_specs(args.workload):
            value = load_metric(spec["name"]).read(ctx)
            if value is not None:
                metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
        breakdown = ctx.breakdown()
        if args.keep_trace:
            kept = Path(args.keep_trace) / f"{args.workload}-{args.seed}"
            shutil.copytree(tracer.directory, kept, dirs_exist_ok=True)
            (kept / "step.hlo.txt").write_text(compiled.as_text())
        tracer.remove()
    else:
        metrics["tokens_per_s"] = {"value": tokens_per_s, "unit": "tokens/s"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}

    # free the program's state before the reference runs on the chip
    del state, m
    program.compiled = None
    compiled = None
    gc.collect()

    t_r = time.perf_counter()
    ref = reference.run(config, workload, program.shapes, args.seed, steps=CHECKED_STEPS,
                        devices=devices)
    log(f"reference: {time.perf_counter() - t_r:.2f} s, losses {ref['losses']}")
    values = check.readings(prog_readings, ref)
    values["window_compiles"] = float(window_compiles)
    limits = dict(workload.get("limits", {}))
    correct, checks = check.judge(values, limits)
    checks["window_compiles"] = {"value": window_compiles, "limit": 0}
    correct = correct and window_compiles == 0
    log("readings " + json.dumps(values))
    for key in ("grad_norms", "update_norms"):
        for path, p, r, gap in check.worst_leaves(prog_readings, ref, ref["paths"], key):
            log(f"  {key} {path}: program {p:.6g} reference {r:.6g} gap {gap:.4g}")
    failed += sum(not np.isfinite(x) for x in prog_readings["losses"])
    result = {"correct": bool(correct) and failed == 0, "attempted": steps + CHECKED_STEPS,
              "failed": int(failed),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return result
