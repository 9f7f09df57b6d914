"""Compile a cell's full-size step for a described TPU, without a chip.

    JAX_PLATFORMS=cpu python3 bench/compile_check.py <cell> [<cell> ...]

Builds the cell's step exactly as ``bench/run.py`` does, but on the
devices of a described ``v5e:2x2`` topology, compiles it with the TPU
compiler and prints the compiler's bytes per device (arguments, outputs,
aliased, temporaries).  The Pallas kernels are lowered for the chip, not
the interpreter.  Nothing runs, so this says whether the step fits and
compiles, never how fast it is.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def main(argv) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies

    from bench.harness import load_cell
    from bench.system import Program
    from repro.kernels import ops

    jax.config.update("jax_enable_compilation_cache", False)
    ops._use_interpret = lambda: False
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    for cell in argv:
        config, workload = load_cell(cell)
        program = Program(config, workload, topo.devices, use_pallas=True)
        ma = program.lower().compile().memory_analysis()
        gib = 2 ** 30
        live = ma.argument_size_in_bytes + ma.output_size_in_bytes - ma.alias_size_in_bytes \
            + ma.temp_size_in_bytes
        print(json.dumps({
            "cell": cell, "layers": program.cfg.num_layers,
            "arguments_gib": round(ma.argument_size_in_bytes / gib, 3),
            "outputs_gib": round(ma.output_size_in_bytes / gib, 3),
            "aliased_gib": round(ma.alias_size_in_bytes / gib, 3),
            "temporaries_gib": round(ma.temp_size_in_bytes / gib, 3),
            "total_gib": round(live / gib, 3)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
