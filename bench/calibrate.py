"""Readings from which a cell's limits are set, on the chip, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 7,8,9] [--faults half_batch,no_exchange] [--out file.json]

For every seed of ``--seeds``: the program's first steps through the
timed path (the compiled step built once and reused), then the plain
reference from the same seed, and the three numbers of
:mod:`bench.check`.  For every seed of ``--control-seeds``: the control,
the reference computed with float8 matmuls in the program's place, and
each fault of ``--faults`` planted in the reference put in the program's
place, each read against the float32 reference.  Every row is also
judged by the cell's own ``limits`` that it has a reading for
(:func:`bench.check.judge`, under ``correct`` and ``checks``): the
program's rows must come out correct, the control's and each fault's not.  The benchmark's own runs never run this.
Prints one JSON object (also written to ``--out``).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
# the TPU runtime logs to /tmp/tpu_logs unless told otherwise; a run writes
# nothing outside its checkout and its HOME/TMPDIR
os.environ.setdefault("TPU_LOG_DIR", "disabled")
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    cseeds = [int(s) for s in args.control_seeds.split(",") if s]
    faults = [f for f in args.faults.split(",") if f]

    from bench import check, harness, reference
    from bench.system import Program

    config, workload = harness.load_cell(args.workload)
    harness.enable_compile_cache()
    devices = harness.chip_devices(int(workload["chips"]))
    program = Program(config, workload, devices)
    program.compile()
    out = {"workload": args.workload, "program": [], "control": [], "faults": []}
    refs = {}
    limits = workload.get("limits", {})

    def judged(values):
        # the reference put in the program's place holds one copy of each
        # leaf: it is judged by the numbers it has (no replica_gap)
        ok, checks = check.judge(values, {k: v for k, v in limits.items() if k in values})
        return {"correct": ok, "checks": checks,
                "not_read": sorted(k for k in limits if k not in values)}

    def ref_of(seed):
        if seed not in refs:
            t = time.perf_counter()
            refs[seed] = reference.run(config, workload, program.shapes, seed,
                                       devices=devices)
            refs[seed]["seconds"] = time.perf_counter() - t
        return refs[seed]

    for seed in seeds:
        state, prog, _, _ = harness.program_first_steps(program, seed)
        del state
        gc.collect()
        ref = ref_of(seed)
        values = check.readings(prog, ref)
        row = {"seed": seed, **values, **judged(values), "losses": prog["losses"],
               "ref_losses": ref["losses"], "ref_seconds": ref["seconds"],
               "worst_grad": check.worst_leaves(prog, ref, ref["paths"], "grad_norms"),
               "worst_update": check.worst_leaves(prog, ref, ref["paths"], "update_norms")}
        out["program"].append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)
    program.compiled = None
    gc.collect()
    for seed in cseeds:
        ref = ref_of(seed)
        ctl = reference.run(config, workload, program.shapes, seed, precision="fp8",
                            devices=devices)
        values = check.readings(ctl, ref)
        row = {"seed": seed, **values, **judged(values), "losses": ctl["losses"],
               "ref_losses": ref["losses"]}
        out["control"].append(row)
        print(json.dumps({"control": row}), file=sys.stderr, flush=True)
        for fault in faults:
            bad = reference.run(config, workload, program.shapes, seed, fault=fault,
                                devices=devices)
            values = check.readings(bad, ref)
            row = {"seed": seed, "fault": fault, **values, **judged(values),
                   "losses": bad["losses"], "ref_losses": ref["losses"]}
            out["faults"].append(row)
            print(json.dumps({"fault": row}), file=sys.stderr, flush=True)
    summary = {}
    for name in check.NUMBERS:
        summary[name] = {
            "program_max": max((r[name] for r in out["program"] if name in r), default=None),
            "control_min": min((r[name] for r in out["control"] if name in r), default=None)}
        for fault in faults:
            summary[name][f"{fault}_min"] = min(
                (r[name] for r in out["faults"] if r["fault"] == fault and name in r),
                default=None)
    out["summary"] = summary
    out["judged"] = {
        "program_correct": [r["correct"] for r in out["program"]],
        "control_correct": [r["correct"] for r in out["control"]],
        **{f"{f}_correct": [r["correct"] for r in out["faults"] if r["fault"] == f]
           for f in faults}}
    text = json.dumps(out)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
