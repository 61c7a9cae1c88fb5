#!/usr/bin/env python3
"""The control of ``correct``: the plain reference put in the program's
place, one precision below what the configuration states (its
``control_precision``), or with a fault planted, read against the float32
reference at the cell's own size. Run by hand on the chip; the benchmark's
own runs never run it.

    python benchmarks/tools/control.py --workload <cell> --seeds 1 2 3
    python benchmarks/tools/control.py --workload <cell> --seeds 1 2 3 --fault half_batch

Prints one JSON line a seed with every number the comparison would read and
whether the cell's limits fail it, as they must.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def readings(cell, seed, precision="float32", fault=None, devices=None):
    """-> (numbers, correct, compared) of the stand-in against the float32
    reference on one seed. Only what the cell's limits name is compared: a
    cell whose entry point shows no optimizer state has no such limit."""
    from benchmarks.harness import compare, follow, traffic
    x, y = traffic.batches(seed, cell.config, cell.traffic)
    steps = traffic.split(x, y, cell.traffic)[:cell.traffic["follow_steps"]]
    ref = follow.follow(cell.reference(), cell.config, seed, steps,
                        cell.traffic["snapshots"], devices=devices)
    stand_in = follow.follow(cell.reference(), cell.config, seed, steps,
                             cell.traffic["snapshots"], precision=precision,
                             fault=fault, devices=devices)
    nums = compare.numbers(stand_in, ref)
    correct, compared = compare.judge(nums, cell.limits)
    return nums, correct, compared


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--precision", default=None)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args()
    from benchmarks.harness.cell import Cell
    cell = Cell.load(args.workload)
    precision = args.precision or (
        "float32" if args.fault else cell.config["control_precision"])
    import jax
    devices = jax.devices()[:cell.chips]
    for seed in args.seeds:
        nums, correct, _ = readings(cell, seed, precision, args.fault,
                                    devices)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "precision": precision, "fault": args.fault,
                          "numbers": nums, "correct": correct}), flush=True)


if __name__ == "__main__":
    main()
