"""Serving-tier router acceptance: batching, admission, SLOs, upgrades.

Unit-level: cost-model staircase math, latency histogram percentiles,
bounded shard queues. Integration: the closed-loop multi-client driver
with full differential parity against the sequential oracle — local
in-process, sharded in a subprocess with 8 forced host devices — plus the
rolling-upgrade scenario (mid-trace handover, zero dropped requests) and
the two admission-control behaviors (queue-full shedding, resize-pressure
write deferral/shedding).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.abspath(__file__)


# --- cost model -------------------------------------------------------------

def test_cost_model_staircase():
    from repro.serving.router import CostModel

    m = CostModel(base_s=1e-3, chunk_s=1e-4, n_lanes=16)
    assert m.dispatch_cost(0) == 0.0
    assert m.dispatch_cost(1) == pytest.approx(1e-3 + 1e-4)
    assert m.dispatch_cost(16) == pytest.approx(1e-3 + 1e-4)
    assert m.dispatch_cost(17) == pytest.approx(1e-3 + 2e-4)
    assert m.throughput_ops_s(16) == pytest.approx(16 / (1e-3 + 1e-4))
    # batch_floor: whole chunks, grows with fixed overhead, >= one chunk
    assert m.batch_floor() % 16 == 0
    heavy = CostModel(base_s=1e-2, chunk_s=1e-4, n_lanes=16)
    assert heavy.batch_floor() > m.batch_floor()
    free = CostModel(base_s=0.0, chunk_s=1e-4, n_lanes=16)
    assert free.batch_floor() == 16


def test_cost_model_measured_on_live_table():
    import jax
    jax.config.update("jax_platform_name", "cpu")
    from repro.serving.router import measure_cost_model
    from repro.table_api import Table, TableSpec

    spec = TableSpec(dmax=6, bucket_size=4, pool_size=64, n_lanes=8)
    t = Table.create(spec)
    m = measure_cost_model(t, max_chunks=4, repeats=2)
    assert m.source == "measured"
    assert m.n_lanes == 8 and m.chunk_s > 0 and m.base_s >= 0
    # measuring must not touch the live table
    assert int(t.size()) == 0


# --- latency histogram ------------------------------------------------------

def test_latency_histogram_percentiles():
    from repro.serving.router import LatencyHistogram

    h = LatencyHistogram()
    assert h.percentile(50) == 0.0 and h.summary() == {"count": 0}
    samples = np.linspace(1e-3, 10e-3, 1000)
    h.add_many(samples)
    s = h.summary()
    assert s["count"] == 1000
    # geometric buckets: ~12% relative error bound at 20/decade
    assert s["p50_ms"] == pytest.approx(5.5, rel=0.15)
    assert s["p99_ms"] == pytest.approx(9.9, rel=0.15)
    # estimates are clamped to the observed range
    assert s["min_ms"] <= s["p50_ms"] <= s["p99_ms"] <= s["max_ms"]
    assert s["max_ms"] == pytest.approx(10.0, rel=1e-6)


# --- shard queues -----------------------------------------------------------

def test_shard_queues_bound_and_fifo():
    from repro.serving.router import READ, INS, Request, ShardQueues

    q = ShardQueues(n_shards=2, max_depth_per_shard=3)
    reqs = [Request(rid=i, kind=INS if i % 2 else READ, key=i,
                    shard=i % 2, t_submit=float(i)) for i in range(8)]
    admitted = [q.admit(r) for r in reqs]
    # 3 per shard: rids 0..5 admitted, 6 (shard 0) and 7 (shard 1) shed
    assert admitted == [True] * 6 + [False, False]
    assert q.depth(0) == 3 and q.depth(1) == 3 and len(q) == 6
    assert q.oldest_wait(10.0) == pytest.approx(10.0)
    # FIFO within each channel, depth released on take
    reads = q.take_reads(10)
    assert [r.rid for r in reads] == [0, 2, 4]
    writes = q.take_writes(2)
    assert [r.rid for r in writes] == [1, 3]
    assert q.depth(1) == 1 and len(q) == 1


def test_shard_of_routes_like_the_placement():
    from repro.serving.router import shard_of
    from repro.table_api import TableSpec

    local = TableSpec(dmax=6, bucket_size=4, pool_size=64, n_lanes=8)
    assert shard_of(12345, local) == 0
    sharded = TableSpec(dmax=6, bucket_size=4, pool_size=64, n_lanes=8,
                        placement="sharded", shard_bits=1)
    shards = {shard_of(k, sharded) for k in range(1, 200)}
    assert shards == {0, 1}


# --- admission control ------------------------------------------------------

def _mini_router(max_queue=4, **cfg_kw):
    from repro.serving.router import Router, RouterConfig, default_cost_model
    from repro.table_api import Table, TableSpec

    spec = TableSpec(dmax=6, bucket_size=4, pool_size=64, n_lanes=8)
    cfg = RouterConfig(max_batch=8, max_queue_per_shard=max_queue,
                       max_delay_s=1e-3, **cfg_kw)
    clock = [0.0]
    r = Router(Table.create(spec), cfg,
               cost_model=default_cost_model(8), clock=lambda: clock[0])
    return r, clock


def test_queue_full_shedding():
    from repro.serving.router import INS, SHED_QUEUE_FULL

    r, clock = _mini_router(max_queue=4)
    decisions = [r.submit(INS, k, k, now=0.0)[1] for k in range(1, 7)]
    assert decisions == ["admitted"] * 4 + [SHED_QUEUE_FULL] * 2
    assert r.metrics.shed_queue_full == 2
    done = r.flush(now=0.0)
    assert len(done) == 4 and all(d.status == 1 for d in done)


def test_pressure_sheds_writes_not_reads():
    from repro.serving.router import INS, READ, SHED_PRESSURE

    r, clock = _mini_router()
    r.pressure = 0.9                       # above pressure_shed
    _, dec_w = r.submit(INS, 1, 1, now=0.0)
    _, dec_r = r.submit(READ, 1, now=0.0)
    assert dec_w == SHED_PRESSURE and dec_r == "admitted"
    assert r.metrics.shed_pressure == 1


def test_pressure_defers_writes_behind_reads():
    from repro.serving.router import INS, READ

    r, clock = _mini_router()
    r.submit(INS, 5, 50, now=0.0)
    r.submit(READ, 5, now=0.0)
    r.pressure = 0.5                       # defer < 0.5 < shed
    done = r.pump(now=0.0, force=True)
    # the read dispatched alone; the write is still queued
    assert [d.kind for d in done] == [READ]
    assert r.metrics.deferred_rounds == 1
    assert r.queues.n_writes == 1
    # deferral is bounded: once the write ages past max_delay it goes
    done = r.pump(now=1.0, force=True)
    assert [d.kind for d in done] == [INS] and done[0].status == 1


def test_adaptive_batching_dispatch_points():
    from repro.serving.router import INS, default_cost_model

    r, clock = _mini_router(max_queue=64)
    # high fixed overhead => batch_floor caps at max_batch
    r.cost_model = default_cost_model(8, base_s=1e-2, chunk_s=1e-4)
    assert r.batch_floor == 8              # capped by max_batch
    r.submit(INS, 1, 1, now=0.0)
    assert r.pump(now=0.0) == []           # 1 < floor: hold
    assert len(r.pump(now=0.002)) == 1     # oldest aged past max_delay
    # a full floor's worth dispatches immediately
    for k in range(2, 10):
        r.submit(INS, k, k, now=0.01)
    assert len(r.pump(now=0.01)) == 8


# --- closed loop + parity ---------------------------------------------------

def test_closed_loop_parity_local():
    import jax
    jax.config.update("jax_platform_name", "cpu")
    from repro.core.policy import ResizePolicy
    from repro.serving.router import RouterConfig, default_cost_model
    from repro.table_api import TableSpec
    from repro.workloads import serve_closed_loop

    spec = TableSpec(dmax=8, bucket_size=8, pool_size=512, n_lanes=8,
                     resize_policy=ResizePolicy())
    rep = serve_closed_loop(
        spec, n_clients=6, ops_per_client=50, mix="churn", seed=7,
        cost_model=default_cost_model(spec.n_lanes),
        router_config=RouterConfig(max_batch=16, max_delay_s=1e-3))
    assert rep["ok"], rep["mismatch_examples"]
    assert rep["completed"] == rep["admitted"] == 300
    assert rep["status_mismatches"] == 0
    assert rep["content_mismatches"] == 0
    assert rep["total"]["count"] == 300
    assert rep["mean_batch"] > 1.0         # it actually batched


def test_rolling_upgrade_zero_dropped():
    import jax
    jax.config.update("jax_platform_name", "cpu")
    from repro.core.policy import ResizePolicy
    from repro.serving.router import RouterConfig, default_cost_model
    from repro.table_api import TableSpec
    from repro.workloads import serve_closed_loop

    spec = TableSpec(dmax=8, bucket_size=8, pool_size=512, n_lanes=8,
                     resize_policy=ResizePolicy())
    bigger = TableSpec(dmax=9, bucket_size=8, pool_size=1024, n_lanes=8,
                       resize_policy=ResizePolicy())
    rep = serve_closed_loop(
        spec, n_clients=6, ops_per_client=50, mix="churn", seed=8,
        cost_model=default_cost_model(spec.n_lanes),
        router_config=RouterConfig(max_batch=16, max_delay_s=1e-3),
        handover_at=0.5, handover_spec=bigger)
    assert rep["ok"], rep["mismatch_examples"]
    assert rep["handover_done"] and rep["handovers"] == 1
    assert rep["dropped"] == 0
    assert rep["completed"] == rep["admitted"] == 300


# --- sharded: subprocess with 8 forced host devices -------------------------

@pytest.mark.subprocess
def test_closed_loop_sharded():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(HERE), "..", "src"))
    proc = subprocess.run(
        [sys.executable, HERE, "--run-sharded"],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, (proc.stdout[-3000:], proc.stderr[-3000:])
    assert "sharded serving OK" in proc.stdout


def _sharded_main():
    from repro import compat
    from repro.core.policy import ResizePolicy
    from repro.serving.router import RouterConfig, default_cost_model
    from repro.table_api import TableSpec
    from repro.workloads import serve_closed_loop

    mesh = compat.make_mesh((4, 2), ("data", "model"))
    spec = TableSpec(dmax=8, bucket_size=8, pool_size=256, n_lanes=8,
                     placement="sharded", shard_bits=1,
                     resize_policy=ResizePolicy())
    rep = serve_closed_loop(
        spec, n_clients=4, ops_per_client=30, mix="churn", seed=9, mesh=mesh,
        cost_model=default_cost_model(spec.n_lanes),
        router_config=RouterConfig(max_batch=16, max_delay_s=1e-3))
    assert rep["ok"], rep["mismatch_examples"]

    # mid-trace re-shard: 2-shard table hands over to a local successor
    local = TableSpec(dmax=9, bucket_size=8, pool_size=512, n_lanes=8,
                      resize_policy=ResizePolicy())
    rep2 = serve_closed_loop(
        spec, n_clients=4, ops_per_client=30, mix="churn", seed=10, mesh=mesh,
        cost_model=default_cost_model(spec.n_lanes),
        router_config=RouterConfig(max_batch=16, max_delay_s=1e-3),
        handover_at=0.5, handover_spec=local)
    assert rep2["ok"], rep2["mismatch_examples"]
    assert rep2["handover_done"] and rep2["dropped"] == 0
    print("sharded serving OK")
    return 0


if __name__ == "__main__":
    if "--run-sharded" in sys.argv:
        sys.exit(_sharded_main())
