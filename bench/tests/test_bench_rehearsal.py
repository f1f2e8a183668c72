"""A whole run of each loop kind through the benchmark's entry point, on
the CPU at a tiny size with interpret-mode kernels: the last line has the
keys the result needs and reads correct."""
import json

import pytest

import _tiny  # noqa: F401  (puts bench/ and src/ on the path)
import run as bench_run
from harness import cell as cell_mod
from harness import device


@pytest.mark.parametrize("workload", ["ycsb_b.index_l",
                                      "load_drain.index_s"])
@pytest.mark.parametrize("trace", [0, 1])
def test_run_prints_a_correct_result_line(workload, trace, monkeypatch,
                                          capsys):
    import jax

    monkeypatch.setattr(bench_run, "_environment", lambda: None)
    monkeypatch.setattr(device, "require_accelerator",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(cell_mod, "load_cell",
                        lambda name: _tiny.tiny_cell(name, "interpret"))
    rc = bench_run.main(["--workload", workload, "--seed", str(2**31 + 3),
                         "--seconds", "1", "--trace", str(trace)])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(out)[-1] == "checks"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in out
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        out["device"])
    if trace:
        assert {"busy_s", "window_s"} <= set(out["device"])
    else:
        assert {"ops_per_s", "p50_ms", "hbm_peak_mib",
                "setup_s"} <= set(out["metrics"])
        assert all(m["value"] > 0 for k, m in out["metrics"].items()
                   if k != "hbm_peak_mib")   # the CPU keeps no HBM peak


def test_no_accelerator_means_no_result(capsys):
    """On the CPU the guard refuses: exit code 2 and no result line."""
    rc = bench_run.main(["--workload", "ycsb_b.index_l", "--seed", "1",
                         "--seconds", "1"])
    assert rc == 2
    assert capsys.readouterr().out == ""
