"""On-chip benchmark of the compressed-sync trainer.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell (``bench/workloads/<cell>.json``) on the TPU chips of this
machine and prints one JSON line last on standard output: ``correct``,
``attempted``, ``failed``, ``metrics`` (the end-to-end metrics with
``--trace 0``, the per-layer metrics read from the device trace with
``--trace 1``), ``device``, ``breakdown`` (traced runs) and ``checks``
(each number compared with its limit).  Exits 3, printing no result, when
JAX finds no TPU or fewer chips than the cell asks for.  See
``bench/harness.py`` for what is timed.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
# the TPU runtime logs to /tmp/tpu_logs unless told otherwise; a run writes
# nothing outside its checkout and its HOME/TMPDIR
os.environ.setdefault("TPU_LOG_DIR", "disabled")
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def main(argv=None) -> int:
    from bench import harness

    try:
        result = harness.run(argv)
    except harness.NoChip as e:
        print(f"bench/run.py: {e}; nothing was run", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
