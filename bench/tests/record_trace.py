"""Record the small chip trace that test_trace.py reads (run on a TPU).

    python3 bench/tests/record_trace.py

Runs the harness on one chip with the tiny Qwen2-VL-shaped test
configuration under ``faithful`` sync (one peer: statistics, encode and
decode kernels, no exchange) for a window of a few steps, with the
profiler on, and copies the trace's ``.xplane.pb``, the compiled step's
HLO text and the run's result line into ``bench/tests/data/``.
"""
import gzip
import json
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def main() -> int:
    from bench import harness

    config = json.loads((HERE / "data" / "tiny-qwen2-vl.json").read_text())
    workload = {"name": "tiny.1chip.faithful", "config": config["name"], "chips": 1,
                "mesh": "1", "sync": "faithful", "method": "tnqsgd", "bits": 3, "ef": True,
                "batch": 2, "seq": 256, "bucket_mb": 0.25,
                "optimizer": {"name": "momentum_sgd", "lr": 0.05, "momentum": 0.9,
                              "weight_decay": 5e-4},
                "limits": {"loss0_gap": 1.0}}
    keep = ROOT / "bench" / ".trace" / "kept"
    res = harness.run(["--workload", workload["name"], "--seed", "7", "--seconds", "0.02",
                       "--trace", "1", "--keep-trace", str(keep)], cell=(config, workload))
    src = keep / f"{workload['name']}-7"
    xplane = next(src.rglob("*.xplane.pb"))
    out = HERE / "data"
    with open(xplane, "rb") as f, gzip.open(out / "tiny_trace.xplane.pb.gz", "wb") as g:
        shutil.copyfileobj(f, g)
    with gzip.open(out / "tiny_trace.hlo.txt.gz", "wt") as g:
        g.write((src / "step.hlo.txt").read_text())
    (out / "tiny_trace.result.json").write_text(json.dumps(res, indent=1) + "\n")
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
