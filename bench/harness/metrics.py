"""Per-layer metrics: each is a reader of its own,
``bench/metrics/<metric name>.py``, with one function ``read(run)`` that
returns a number, or None where the run left it nothing to read (the
metric is then left out of the result line)."""
from __future__ import annotations

import importlib.util

from harness.cell import BENCH_DIR


def load_reader(name: str):
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_per_layer(cell, run) -> dict:
    out = {}
    for m in cell.per_layer:
        value = load_reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
