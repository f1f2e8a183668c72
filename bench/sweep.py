#!/usr/bin/env python3
"""Find an open-loop cell's knee: pre-fill once, then offer each rate in
turn for ``--seconds`` in one process on the chip.

    python3 bench/sweep.py --workload ycsb_b.index_l --seed 7 \
        --rates 4000,8000,12000,16000 --seconds 8

Prints one JSON row per rate. The knee is the highest rate at which the
completed rate is within 2% of the offered one, the backlog at the
window's close is no deeper than one ``max_batch``, and no request was
shed. A cell's ``rate_ops_s`` is set to four fifths of it.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    bench_run._environment()

    import numpy as np

    from harness import device, loops, traffic
    from harness.cell import load_cell
    from harness.runner import (build_spec, check_plan, open_numbers,
                                serving_setup, setup_done)
    from repro import compat
    from repro.table_api import Table

    cell = load_cell(args.workload)
    devices = device.require_accelerator(cell.chips)
    meter = device.CompileMeter()
    spec, mesh = build_spec(cell.config, devices)
    check_plan(spec, cell.config, devices[0].platform)

    def log(line):
        print(line, file=sys.stderr, flush=True)

    rng = np.random.default_rng(args.seed)
    placed = contextlib.nullcontext() if mesh is None else \
        compat.set_mesh(mesh)
    with placed:
        router, rec_keys, _, _ = serving_setup(cell, spec, mesh,
                                               Table.create, rng, log)
        setup_done(meter, T_PROCESS, log)
        max_batch = cell.config["router"]["max_batch"]
        for rate in (float(r) for r in args.rates.split(",")):
            stream = traffic.open_stream(cell.traffic, rec_keys, rate,
                                         args.seconds, rng)
            c0 = meter.snapshot()["compiles"]
            rec = loops.open_loop(router, stream, args.seconds)
            c = rec.counters
            row = {"rate": rate, "offered_ops_s": len(stream) / args.seconds,
                   **open_numbers(rec, args.seconds),
                   "backlog_at_close": rec.backlog_at_close,
                   "shed": int(rec.shed.sum()),
                   "batch_ops": (c["write_ops"] + c["read_ops"])
                   / max(c["dispatches"], 1),
                   "dispatches": c["dispatches"],
                   "drain_s": rec.t_drained - rec.t_close,
                   "compiles": meter.snapshot()["compiles"] - c0}
            row["holds"] = (row["ops_per_s"] >= 0.98 * rate
                            and row["backlog_at_close"] <= max_batch
                            and row["shed"] == 0)
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
