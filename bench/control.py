#!/usr/bin/env python3
"""Run a cell with the control in the table's place (harness/control.py)
on several seeds, and print each run's compared numbers.

    python3 bench/control.py --workload ycsb_b.index_l --seeds 1,2,3 \
        --seconds 10

Every run has to come out not correct: that is what shows the
comparison can fail. The benchmark's own runs never run the control.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run as bench_run  # noqa: E402


def control_for(cell):
    from harness import control

    return (control.StaleReads if cell.traffic["loop"] == "open"
            else control.LostWrite)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    bench_run._environment()

    from harness import device
    from harness.cell import load_cell
    from harness.runner import run_cell

    cell = load_cell(args.workload)
    devices = device.require_accelerator(cell.chips)
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run_cell(cell, seed, args.seconds, False, time.perf_counter(),
                       devices, log=lambda s: None,
                       make_table=control_for(cell))
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "attempted": res["attempted"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
