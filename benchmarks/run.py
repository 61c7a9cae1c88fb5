#!/usr/bin/env python3
"""Run one cell of the benchmark once, in this process, on the chip.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Requires a TPU with as many chips as the cell asks for; without one it exits
non-zero and prints no result. Builds the configuration with weights made on
the device from ``--seed``, drives the cell's first steps through the
window's own call, measures for ``--seconds``, compares what that call
produced with the plain reference, and prints one JSON object as the last
line of standard output. The compile cache is the program's own policy:
``JAX_COMPILATION_CACHE_DIR`` where it is set, else ``.jax_cache/`` in the
checkout (``deeplearning4j_tpu.environment``); nothing here sets another.
"""

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks.harness.cell import Cell
    cell = Cell.load(args.workload)

    import deeplearning4j_tpu  # noqa: F401  (sets the compile cache policy)

    from benchmarks.harness import device, events, run_cell
    devices = device.require_tpu(cell.chips)
    result = run_cell.run(cell, args.seed, args.seconds, bool(args.trace),
                          devices, _T_START, events.CompileEvents())
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
