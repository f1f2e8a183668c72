"""The trace reduction on a small recorded trace: 40 ms of the device
events (operations and modules) of a profile of the ``ycsb_b.index_l``
cell on one TPU v5e, as ``jax.profiler`` wrote them."""
import json
from pathlib import Path

import pytest

import _tiny  # noqa: F401  (puts bench/ and src/ on the path)
from harness import trace

FIXTURE = Path(__file__).parent / "fixtures" / "trace_excerpt.json"


@pytest.fixture(scope="module")
def events():
    return [trace.Event(*e) for e in json.loads(FIXTURE.read_text())]


def _busy_by_counting(ops, w0, w1):
    """Busy time by a sweep over start and end points, counting how many
    operations are running: an independent way to the union's length."""
    points = sorted([(max(e.start_ns, w0), 1) for e in ops]
                    + [(min(e.end_ns, w1), -1) for e in ops])
    busy, active, last = 0.0, 0, None
    for t, step in points:
        if active > 0:
            busy += t - last
        active += step
        last = t
    return busy * 1e-9


def test_busy_time_is_the_union_of_operations(events):
    red = trace.reduce_events(events)
    ops = [e for e in events if e.line == trace.OPS_LINE]
    w0, w1 = min(e.start_ns for e in ops), max(e.end_ns for e in ops)
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx((w1 - w0) * 1e-9)
    assert red["busy_s"] == pytest.approx(_busy_by_counting(ops, w0, w1))
    # operations overlap (a while spans its body): the sum overcounts
    assert sum(e.dur_ns for e in ops) * 1e-9 > red["busy_s"]
    idle = 1 - red["busy_s"] / red["window_s"]
    assert 0.0 < idle < 1.0


def test_kernels_are_matched_by_instruction_name(events):
    red = trace.reduce_events(events)
    probes = [e for e in events if e.line == trace.OPS_LINE
              and e.name.startswith("%probe.")]
    assert probes, "the excerpt holds probe kernels"
    assert red["category_s"]["lookup"] == pytest.approx(
        sum(e.dur_ns for e in probes) * 1e-9)
    # an operation whose text merely mentions "apply" is not the kernel
    assert any("to_apply" in e.name for e in events)
    applies = [e for e in events if e.line == trace.OPS_LINE
               and e.name.startswith(("%grouped_apply.", "%fused_apply."))]
    assert red["category_s"]["apply"] == pytest.approx(
        sum(e.dur_ns for e in applies) * 1e-9)


def test_breakdown_names_ops_and_idle_gaps(events):
    red = trace.reduce_events(events)
    assert red["device_ops"] and len(red["device_ops"]) <= 10
    for name, seconds in red["device_ops"]:
        assert not name.split("/")[-1].startswith(("while", "cond"))
        assert seconds > 0
    gaps = dict(red["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(
        red["window_s"] - red["busy_s"])
    assert set(gaps) <= {"unannotated"} | {
        e.name for e in events if e.plane.startswith("/host:")}
