"""The comparison catches a broken timed path. Each test breaks the
facade underneath a whole tiny run on the CPU (the harness's look for a
chip is skipped) and sees ``correct`` come out false, once for each fault
a cell can have:

* a step that returns its state unchanged;
* half of the batch left out (every other lane dropped);
* an answer altered where it is produced;
* on four chips, the exchange between chips left out.

The control (the plain reference in the table's place, with one stated
guarantee broken) must come out not correct too.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import _tiny
from harness import control

OPEN, BULK = "ycsb_b.index_l", "load_drain.index_s"


def _stuck(orig):
    def step(self, *a, **k):
        _, res = orig(self, *a, **k)
        return self, res
    return step


def _half_apply(orig):
    def apply(self, kinds, keys, values=None):
        kinds = np.array(kinds)
        kinds[1::2] = 0
        return orig(self, kinds, keys, values)
    return apply


def _half_insert(orig):
    def insert(self, keys, values=None):
        keys = np.asarray(keys)
        t, res = orig(self, keys[0::2],
                      None if values is None else np.asarray(values)[0::2])
        status = np.ones(keys.size, np.int8)
        status[0::2] = np.asarray(res.status)
        return t, res._replace(status=status)
    return insert


def _half_lookup(orig):
    def lookup(self, keys):
        found, vals = orig(self, keys)
        found, vals = np.array(found), np.array(vals)
        found[1::2], vals[1::2] = False, -1
        return found, vals
    return lookup


def _altered_lookup(orig):
    def lookup(self, keys):
        found, vals = orig(self, keys)
        return found, np.array(vals) + np.array(found)
    return lookup


def _altered_insert(orig):
    def insert(self, keys, values=None):
        t, res = orig(self, keys, values)
        status = np.array(res.status)
        status[0] = 1 - status[0]
        return t, res._replace(status=status)
    return insert


FAULTS = {
    (OPEN, "state_unchanged"): {"apply": _stuck},
    (OPEN, "half_batch"): {"apply": _half_apply, "lookup": _half_lookup},
    (OPEN, "answer_altered"): {"lookup": _altered_lookup},
    (BULK, "state_unchanged"): {"insert": _stuck, "delete": _stuck},
    (BULK, "half_batch"): {"insert": _half_insert},
    (BULK, "answer_altered"): {"insert": _altered_insert},
}


@pytest.mark.parametrize("workload,fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(workload, fault, monkeypatch):
    from repro.table_api import Table

    cell = _tiny.tiny_cell(workload, rate=2000.0)
    res = _tiny.run_tiny(cell)          # sound first: the tables compile
    assert res["correct"], res["checks"]
    for method, breaker in FAULTS[(workload, fault)].items():
        monkeypatch.setattr(Table, method, breaker(getattr(Table, method)))
    res = _tiny.run_tiny(cell, seed=2**31 + 12)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("workload", [OPEN, BULK])
def test_the_control_is_not_correct(workload):
    # the cell's own mix (95/5 for the served cell); at this rate a few
    # hundred updates meet reads of their hot keys in one dispatch
    cell = _tiny.tiny_cell(workload, rate=4000.0)
    make = control.StaleReads if workload == OPEN else control.LostWrite
    res = _tiny.run_tiny(cell, seconds=2.0, make_table=make)
    assert res["correct"] is False, res["checks"]
    broken = [k for k, c in res["checks"].items() if c["value"] > c["limit"]]
    assert broken


def _sharded_main(fault: str) -> None:
    """Runs in a child with four CPU devices: one tiny four-chip run,
    sound or with the collectives' exchange left out."""
    import jax

    cell = _tiny.sharded_cell()
    if fault == "no_exchange":
        jax.lax.psum = lambda x, axis_name, **kw: x
    res = _tiny.run_tiny(cell)
    print(json.dumps({"correct": res["correct"], "checks": res["checks"]}))


@pytest.mark.parametrize("fault", ["sound", "no_exchange"])
def test_four_chip_exchange_left_out_is_not_correct(fault):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    proc = subprocess.run(
        [sys.executable, __file__, fault], env=env, capture_output=True,
        text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is (fault == "sound"), out["checks"]


if __name__ == "__main__":
    _sharded_main(sys.argv[1])
