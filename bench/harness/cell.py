"""A cell, as ``BENCHMARK.json`` and the files it names define it.

A workload entry names a configuration and a traffic mix. The
configuration's file is the one its ``configs`` entry gives; the mix is
``bench/traffic/<traffic>.json``; settings of the cell alone (an open
loop's offered rate) are ``bench/cells/<workload>.json``; each per-layer
metric's reader is ``bench/metrics/<metric>.py``. A later cell adds files
and entries and edits none of these.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    settings: dict
    end_to_end: list
    per_layer: list


def metric_applies(metric: dict, cell: str, e2e_names: set) -> bool:
    """A metric with ``workloads`` applies to the cells listed; one
    without applies wherever the end-to-end metric it moves is reported."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(by_name)})")
    wl = by_name[name]
    cfg = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    bench_dir = root / "bench"
    settings_path = bench_dir / "cells" / f"{name}.json"
    settings = load_json(settings_path) if settings_path.exists() else {}
    e2e = [m for m in bench["end_to_end"]
           if metric_applies(m, name, set())]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if metric_applies(m, name, e2e_names)]
    return Cell(name=name, chips=wl["chips"], config_name=wl["config"],
                traffic_name=wl["traffic"],
                config=load_json(root / cfg["file"]),
                traffic=load_json(bench_dir / "traffic"
                                  / f"{wl['traffic']}.json"),
                settings=settings, end_to_end=e2e, per_layer=per_layer)
