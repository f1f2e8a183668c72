"""chip_smoke.py off the chip: it must refuse a host without a TPU, and its
parity harness must pass (and catch a wrong answer) on small tables with
the kernels in interpret mode."""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cs():
    return _load()


@pytest.mark.subprocess
def test_refuses_a_host_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, SCRIPT], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    for line in proc.stdout.splitlines():
        assert not json.loads(line).get("ok"), line


def _interpret_spec(**geo):
    from repro.core.spec import TableSpec

    return TableSpec(bucket_size=4, backend="interpret", **geo)


@pytest.mark.parametrize("geo,fused", [
    (dict(dmax=11, pool_size=511, n_lanes=16, initial_depth=2), True),
    (dict(dmax=18, pool_size=1000, n_lanes=32, initial_depth=3), False),
])
def test_table_workload_parity_in_interpret_mode(cs, geo, fused):
    out = cs.table_workload(
        "rehearsal", _interpret_spec(**geo), 400, 0, cs.CompileMeter(),
        {"backend": "pallas", "interpret": True, "fused_apply": fused})
    assert out["mismatches"] == 0 and out["items"] == 300
    assert out["depth"] > geo["initial_depth"]       # grew by splits
    assert out["plan"]["fused_apply"] is fused


def test_table_workload_catches_a_wrong_answer(cs, monkeypatch):
    from repro.core.reference import StreamingOracle

    real = StreamingOracle.lookup_batch

    def off_by_one(self, keys):
        found, vals = real(self, keys)
        return found, np.where(found, vals + 1, vals)

    monkeypatch.setattr(StreamingOracle, "lookup_batch", off_by_one)
    with pytest.raises(AssertionError, match="value mismatches"):
        cs.table_workload(
            "rehearsal",
            _interpret_spec(dmax=11, pool_size=511, n_lanes=16),
            64, 0, cs.CompileMeter(), {"backend": "pallas"})


def test_served_workload_in_interpret_mode(cs):
    spec = _interpret_spec(dmax=11, pool_size=511, n_lanes=16,
                           initial_depth=2)
    out = cs.served_workload(spec, 4, 30, 2, cs.CompileMeter())
    assert out["ok"] and out["completed"] == 120 and out["dropped"] == 0


@pytest.mark.subprocess
def test_table_workload_sharded_on_four_host_devices():
    """The ``--chips 4`` harness at a small size: a sharded table on a
    (1, 4) mesh of forced host devices and its local twin agree with the
    oracle, and the state spans all four devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, os.path.abspath(__file__),
                           "--run-sharded"], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (proc.stdout[-3000:], proc.stderr[-3000:])
    assert "sharded workload OK" in proc.stdout


def _sharded_main() -> int:
    import jax

    from repro import compat

    cs = _load()
    mesh = compat.make_mesh((1, 4), ("data", "model"),
                            devices=jax.devices()[:4])
    sharded = _interpret_spec(dmax=10, pool_size=511, n_lanes=32,
                              initial_depth=2, placement="sharded",
                              shard_bits=2)
    twin = _interpret_spec(dmax=12, pool_size=2047, n_lanes=64,
                           initial_depth=3)
    out = cs.table_workload("sharded", sharded, 800, 3, cs.CompileMeter(),
                            {"backend": "pallas", "fused_apply": True},
                            mesh=mesh, twin_spec=twin)
    t = out.pop("tables")["table"]
    assert out["mismatches"] == 0 and out["items"] == 600
    assert out["overflow_statuses"] == 0
    assert "twin_insert_s" in out
    assert {s.device.id for s in t.state.keys.addressable_shards} == {
        0, 1, 2, 3}
    print("sharded workload OK")
    return 0


if __name__ == "__main__":
    assert sys.argv[1] == "--run-sharded", sys.argv
    sys.exit(_sharded_main())
