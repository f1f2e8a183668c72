"""Workload replay parity: the churn engine's acceptance matrix.

Every scenario class in the registry (uniform, zipf, phased_drain,
mixed_churn) replays through the `Table` facade with the elastic
`ResizePolicy` active and is differentially checked op-by-op against the
paper-literal sequential oracle — per-lane statuses, every read, and a
final full-content sweep. The churn scenarios must additionally *prove*
elasticity: observed directory-depth increases AND decreases, plus nonzero
policy split/merge counters (auto-merge is the first runtime exercise of
the paper's §4.5 shrink path).

Local placement runs in-process; the sharded placement sweep runs in a
subprocess with 8 forced host devices (device count is process-global),
at reduced scale — same checks, (data=4, model=2) mesh, 2 table shards.

Both sweeps run with ``oracle="both"``: every op is checked against the
materializing `SeqExtHash` AND the streaming `StreamingOracle` in
lock-step, so each scenario replay is simultaneously parity evidence for
the table and an oracle-vs-oracle cross-check (any divergence between
the oracles raises immediately rather than being booked as a table
mismatch). The chaos_* scenarios replay here plain — the event-injecting
runs live in tests/test_chaos.py.
"""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.abspath(__file__)

# the scenario classes whose replay must show BOTH elastic directions
CHURNY = ("phased_drain", "mixed_churn", "snapshot_restore",
          "chaos_churn", "chaos_reshard")

# the 5 base scenario classes: the sharded subprocess sweep is pinned to
# these to bound its runtime (chaos_* get dedicated sharded coverage in
# tests/test_chaos.py, including the event-injecting runs)
BASE_SCENARIOS = ("uniform", "zipf", "phased_drain", "mixed_churn",
                  "snapshot_restore")

# the full registry, swept locally (chaos_* replay plain here: without an
# event schedule they are ordinary churny parity scenarios)
ALL_SCENARIOS = BASE_SCENARIOS + ("chaos_churn", "chaos_reshard")


def _assert_scenario_report(name: str, rep: dict) -> None:
    assert rep["ok"], (name, rep["status_mismatches"],
                       rep["content_mismatches"], rep["mismatch_examples"],
                       rep["error_flag"])
    assert rep["checked"] and rep["mutations"] > 0 and rep["reads"] > 0
    d = rep["depth"]
    # every scenario grows from the empty table: splits must be observable
    # as directory-depth increases, and the policy must have fired
    assert d["max"] > d["start"] and d["increases"] > 0, d
    assert rep["policy"]["splits"] > 0, rep["policy"]
    # snapshot_restore kills/revives the table twice through a durable
    # image; everything after a revive is snapshot-parity evidence
    want_revives = 2 if name == "snapshot_restore" else 0
    assert rep["snapshot_restores"] == want_revives, rep["snapshot_restores"]
    if name in CHURNY:
        # the elastic round trip: depth provably came back DOWN mid-trace
        # (only the §4.5 merge path can shrink the directory) and the
        # policy's merge counter confirms the auto-merge driver did it.
        # NOTE deliberately no `final < max` claim — churn traces may end
        # in a growth phase, parking the final depth back at the peak.
        assert d["decreases"] > 0, d
        assert rep["policy"]["merges"] > 0, rep["policy"]


@pytest.mark.parametrize("name", list(ALL_SCENARIOS))
def test_scenario_replay_parity_local(name):
    import jax
    jax.config.update("jax_platform_name", "cpu")
    from repro.workloads import get_scenario, replay

    spec, trace = get_scenario(name)
    rep = replay(spec, trace, oracle="both", raise_on_mismatch=False)
    assert rep["oracle"] == "both"
    _assert_scenario_report(name, rep)


def test_scenario_registry_covers_matrix():
    from repro.workloads import SCENARIOS
    from repro.workloads.scenarios import scenario_matrix

    assert set(SCENARIOS) == set(ALL_SCENARIOS)
    assert all(v == ("local", "sharded")
               for v in scenario_matrix().values())


def test_generator_determinism():
    """Same (scenario, seed) → bit-identical op stream; different seed →
    a different stream (the generators are the differential harness's
    ground truth, so this is load-bearing)."""
    import numpy as np
    from repro.workloads import get_scenario
    from repro.workloads.trace import gen_steps

    def stream(seed):
        _, trace = get_scenario("mixed_churn", seed=seed)
        out = []
        for step in gen_steps(trace):
            out.append((step.phase, step.kinds.tolist(), step.keys.tolist(),
                        step.vals.tolist(), step.reads.tolist()))
        return out

    a, b = stream(0), stream(0)
    assert a == b
    c = stream(1)
    assert a != c
    # mixes route ops to the right channels: fill is pure inserts
    _, trace = get_scenario("phased_drain")
    first = next(iter(gen_steps(trace)))
    assert first.phase == "fill"
    assert (first.kinds == 1).all() and first.reads.size == 0
    assert np.unique(first.keys).size == first.keys.size


# --- sharded sweep: subprocess with 8 host devices -------------------------


@pytest.mark.subprocess
def test_scenario_replay_parity_sharded():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(HERE), "..", "src"))
    proc = subprocess.run(
        [sys.executable, HERE, "--run-sharded"],
        env=env, capture_output=True, text=True, timeout=2400)
    assert proc.returncode == 0, (proc.stdout[-3000:], proc.stderr[-3000:])
    reports = json.loads(proc.stdout.splitlines()[-1])
    assert set(reports) == set(BASE_SCENARIOS)
    for name, rep in reports.items():
        assert rep["placement"] == "sharded"
        _assert_scenario_report(name, rep)


def _sharded_main() -> int:
    from repro import compat
    from repro.workloads import get_scenario, replay

    mesh = compat.make_mesh((4, 2), ("data", "model"))
    reports = {}
    for name in BASE_SCENARIOS:
        # reduced scale: shard_map on a forced-8-device CPU host is slow,
        # and parity per op is checked regardless of trace length
        spec, trace = get_scenario(name, placement="sharded", scale=0.5)
        reports[name] = replay(spec, trace, mesh=mesh, oracle="both",
                               raise_on_mismatch=False)
    print(json.dumps(reports))
    return 0


if __name__ == "__main__":
    assert sys.argv[1:] == ["--run-sharded"], sys.argv
    sys.exit(_sharded_main())
