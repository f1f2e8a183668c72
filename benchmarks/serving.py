"""Offered-load sweep for the serving router: latency SLO trajectory.

Open-loop load generator: requests arrive on a virtual clock at a fixed
offered rate (arrivals do NOT wait for completions — the honest way to
measure queueing latency), the :class:`repro.serving.router.Router`
batches them adaptively against its measured cost model, and every
completed request contributes to the p50/p99/p999 latency histograms.
Each placement sweeps at least three offered-load points, expressed as
fractions of the cost model's predicted full-batch capacity, so the sweep
lands on the interesting part of the latency curve regardless of the
host's absolute speed: below ~0.5x the router dispatches early and
latency hugs the service floor; near 1x batches fill and queue wait
climbs; above 1x admission control sheds instead of queueing without
bound.

Output is ``BENCH_serving.json``::

    {"rows": {"local/load0.50": {"offered_ops_s": ..., "achieved_ops_s":
              ..., "p50_ms": ..., "p99_ms": ..., "shed": ..., ...}, ...},
     "cost_models": {"local": {...}, "sharded": {...}}}

CI runs ``--fast`` and uploads the JSON as an artifact, so every merge
leaves an SLO trajectory behind for both placements.

Usage:
  python -m benchmarks.serving                  # full sweep, both placements
  python -m benchmarks.serving --fast           # CI mode (small op counts)
  python -m benchmarks.serving --placements local --loads 0.25,0.5,1,2
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# the sharded placement shards over a 4x2 mesh of (fake) host devices;
# the flag must land before anything imports jax (repro imports are lazy)
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402


def _specs():
    from repro.core.policy import ResizePolicy
    from repro.table_api import TableSpec

    return {
        "local": TableSpec(
            dmax=12,
            bucket_size=8,
            pool_size=4096,
            n_lanes=16,
            resize_policy=ResizePolicy(),
        ),
        "sharded": TableSpec(
            dmax=10,
            bucket_size=8,
            pool_size=2048,
            n_lanes=16,
            placement="sharded",
            shard_bits=1,
            resize_policy=ResizePolicy(),
        ),
    }


def run_load_point(
    spec,
    mesh,
    cost_model,
    rate_ops_s: float,
    n_ops: int,
    seed: int,
    router_config,
) -> dict:
    """One open-loop point: ``n_ops`` arrivals at ``rate_ops_s`` on the
    virtual clock; returns the latency/throughput summary."""
    from repro.serving.router import INS, READ, Router
    from repro.table_api import Table

    table = Table.create(spec, mesh)
    router = Router(table, router_config, cost_model=cost_model, clock=lambda: 0.0)
    router.warmup()  # compiles are amortized startup, not latency tail
    rng = np.random.default_rng(seed)
    max_delay = router_config.max_delay_s

    inserted = 0
    now = 0.0
    for i in range(n_ops):
        now = max(now, i / rate_ops_s)
        # 60/40 read/upsert against a growing keyspace
        if inserted and rng.random() < 0.6:
            kind, key, val = READ, int(rng.integers(1, inserted + 1)), 0
        else:
            inserted += 1
            kind, key, val = INS, inserted, inserted * 7
        router.submit(kind, key, val, now=now)
        router.pump(now=now)
        # honor max_delay between sparse arrivals: if the next arrival is
        # beyond the oldest request's deadline, dispatch at the deadline
        if len(router.queues):
            deadline = now + max_delay
            if (i + 1) / rate_ops_s > deadline:
                now = deadline
                router.pump(now=now)
    router.flush(now=now)

    rep = router.report()
    tot = rep["total"]
    span = max(now, 1e-9)
    return {
        "offered_ops_s": round(rate_ops_s, 1),
        "achieved_ops_s": round(rep["completed"] / span, 1),
        "completed": rep["completed"],
        "shed": rep["shed_queue_full"] + rep["shed_pressure"],
        "mean_batch": rep["mean_batch"],
        "dispatches": rep["dispatches"],
        "batch_floor": rep["cost_model"]["batch_floor"],
        "p50_ms": tot.get("p50_ms", 0.0),
        "p99_ms": tot.get("p99_ms", 0.0),
        "p999_ms": tot.get("p999_ms", 0.0),
        "queue_wait_p50_ms": rep["queue_wait"].get("p50_ms", 0.0),
        "queue_wait_p99_ms": rep["queue_wait"].get("p99_ms", 0.0),
        "service_p50_ms": rep["service"].get("p50_ms", 0.0),
        "slo": rep.get("slo", {}),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--placements", default="local,sharded")
    ap.add_argument(
        "--loads",
        default="0.25,0.5,1.0",
        help="offered load as fractions of predicted full-batch capacity",
    )
    ap.add_argument("--ops", type=int, default=4000, help="arrivals per point")
    ap.add_argument("--fast", action="store_true", help="CI mode: tiny sweep")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--max-delay-ms", type=float, default=2.0)
    ap.add_argument("--slo-p50-ms", type=float, default=None)
    ap.add_argument("--slo-p99-ms", type=float, default=None)
    ap.add_argument("--out", default="BENCH_serving.json")
    args = ap.parse_args()
    if args.fast:
        args.ops = min(args.ops, 600)

    from repro import compat
    from repro.serving.router import RouterConfig, cost_model_for
    from repro.table_api import Table

    loads = [float(s) for s in args.loads.split(",") if s.strip()]
    assert len(loads) >= 3, "the SLO trajectory needs >=3 load points"
    placements = [p.strip() for p in args.placements.split(",") if p.strip()]
    cfg = RouterConfig(
        max_batch=args.max_batch,
        max_delay_s=args.max_delay_ms / 1e3,
        slo_p50_ms=args.slo_p50_ms,
        slo_p99_ms=args.slo_p99_ms,
    )

    specs = _specs()
    mesh = None
    if "sharded" in placements:
        mesh = compat.make_mesh((4, 2), ("data", "model"))

    rows: dict = {}
    cost_models: dict = {}
    for placement in placements:
        spec = specs[placement]
        pmesh = mesh if placement == "sharded" else None
        # measuring the model also warms the jit cache for this spec, so
        # the sweep's first dispatch is not a compile
        model = cost_model_for(Table.create(spec, pmesh))
        cost_models[placement] = {
            "base_s": model.base_s,
            "chunk_s": model.chunk_s,
            "n_lanes": model.n_lanes,
            "capacity_ops_s": round(model.throughput_ops_s(args.max_batch), 1),
        }
        capacity = model.throughput_ops_s(args.max_batch)
        for frac in loads:
            row = run_load_point(
                spec,
                pmesh,
                model,
                rate_ops_s=max(frac * capacity, 1.0),
                n_ops=args.ops,
                seed=args.seed,
                router_config=cfg,
            )
            row["load_fraction"] = frac
            name = f"{placement}/load{frac:.2f}"
            rows[name] = row
            print(
                f"{name},offered={row['offered_ops_s']:.0f}ops/s,"
                f"p50={row['p50_ms']:.3f}ms,p99={row['p99_ms']:.3f}ms,"
                f"batch={row['mean_batch']},shed={row['shed']}",
                flush=True,
            )

    out = {
        "fast": bool(args.fast),
        "ops_per_point": args.ops,
        "rows": rows,
        "cost_models": cost_models,
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"[serving] wrote {len(rows)} rows to {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
