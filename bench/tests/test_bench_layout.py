"""BENCHMARK.json and the files it names: every cell's configuration,
traffic and settings, and every per-layer metric's reader, found by name;
names and units drawn from the allowed characters; every cell reporting
``setup_s``, another end-to-end metric and a per-layer metric."""
import json
import re

import pytest

import _tiny  # noqa: F401  (puts bench/ and src/ on the path)
from harness.cell import BENCH_DIR, ROOT, load_cell
from harness.metrics import load_reader

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert (ROOT / "bench" / "run.py").exists()
    assert len((ROOT / "BENCHMARK.json").read_bytes()) < 64 * 1024


def test_names_and_units():
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in BENCH[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for c in BENCH["configs"]:
        for k in c["reduced"]:
            assert NAME.match(k), k
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_are_found_by_name(name):
    cell = load_cell(name)
    assert cell.config["name"] == cell.config_name
    assert cell.traffic["loop"] in ("open", "bulk")
    if cell.traffic["loop"] == "open":
        assert cell.settings["rate_ops_s"] > 0
    else:    # the loader loads the deployment's records, and drains them
        assert cell.traffic["cycle_keys"] == cell.config["records"]
    assert cell.chips in (1, 4)
    assert (cell.chips == 4) == (cell.config.get("mesh") is not None)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(load_reader(m["name"]))
        assert m["moves"] in e2e, (m["name"], m["moves"])


def test_every_config_and_metric_is_used():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).resolve().is_relative_to(BENCH_DIR)
    for m in BENCH["per_layer"]:
        assert (BENCH_DIR / "metrics" / f"{m['name']}.py").exists()
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for w in BENCH["workloads"]:
        assert (BENCH_DIR / "traffic" / f"{w['traffic']}.json").exists()
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 2)


def test_layers_are_named_alike():
    """Metrics of one layer give the same ``layer``, letter for letter."""
    by_prefix = {}
    for m in BENCH["per_layer"]:
        by_prefix.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in by_prefix.values()), by_prefix
