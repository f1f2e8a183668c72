"""Tiny cells for CPU tests of the harness: the cells of BENCHMARK.json,
shrunk so that a whole run takes seconds on the CPU."""
from __future__ import annotations

import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from harness.cell import Cell, load_cell, load_json  # noqa: E402

# a sharded index over four chips, as a four-chip cell would configure it
SHARDED = {
    "name": "sharded", "records": 600,
    "table": {"dmax": 7, "bucket_size": 8, "pool_size": 128, "n_lanes": 16,
              "initial_depth": 2, "hash_name": "fmix32", "backend": "xla",
              "placement": "sharded", "shard_bits": 2},
    "resize_policy": None,
    "plan": {"fused_lookup": True, "fused_apply": True},
    "router": {"max_batch": 32, "max_delay_s": 0.002,
               "max_queue_per_shard": 8192},
    "mesh": {"shape": [1, 4], "axes": ["data", "model"]},
}


def sharded_cell(rate: float = 2000.0) -> Cell:
    """YCSB-B served by a tiny index sharded over four devices."""
    return Cell(name="ycsb_b.sharded", chips=4, config_name="sharded",
                traffic_name="ycsb_b", config=SHARDED,
                traffic=load_json(BENCH / "traffic" / "ycsb_b.json"),
                settings={"rate_ops_s": rate}, end_to_end=[], per_layer=[])


def tiny_cell(name: str, backend: str = "xla", rate: float = 400.0):
    cell = load_cell(name)
    t = cell.config["table"]
    if t["placement"] == "sharded":
        t.update(dmax=7, pool_size=128, initial_depth=2, n_lanes=16)
    else:
        t.update(dmax=11, pool_size=1024, initial_depth=2,
                 n_lanes=min(t["n_lanes"], 16))
    t["backend"] = backend
    cell.config["records"] = 600
    cell.config["router"]["max_batch"] = 32
    if cell.traffic["loop"] == "open":
        cell.settings["rate_ops_s"] = rate
    else:
        cell.traffic.update(cycle_keys=600, call_keys=64)
    return cell


def run_tiny(cell, seed: int = 2**31 + 11, seconds: float = 1.0,
             trace: bool = False, make_table=None) -> dict:
    import jax

    from harness.runner import run_cell

    return run_cell(cell, seed, seconds, trace, time.perf_counter(),
                    jax.devices()[:cell.chips], log=lambda s: None,
                    make_table=make_table)
