#!/usr/bin/env python3
"""End-to-end smoke run of the table's served path on a TPU.

    python chip_smoke.py              # phases 1-4 on one chip
    python chip_smoke.py --chips 4    # phase 5 alone: the sharded table

One process owns the chip and starts no other. Every phase prints one JSON
line; the last line is ``{"ok": true, "device": {...}}`` and appears only
when every phase passed. A failed check raises, so the exit code is
non-zero and no ``ok`` line is printed.

1. device  — platform, kind, count, memory stats; no TPU, no run.
2. fused   — the TPU default plan (fused Pallas apply + fused probe) at the
             largest geometry it takes (dmax 17, P 131071): 100,000 seeded
             keys inserted from depth 8, so the directory grows by splits;
             every key plus 20,000 absent keys looked up; a quarter deleted
             and all looked up again. Statuses, found flags and values must
             equal the sequential StreamingOracle exactly; the final state
             must pass the structural invariants and hold the oracle's
             content.
3. large   — the largest geometry TableConfig allows (dmax 20, P 2**20;
             unfused probe + grouped apply), 600,000 keys, same checks.
             Transactions are 512 lanes wide: each one pays the slow
             path's O(P) split pass, so wide batches keep that bounded.
4. served  — the serving Router in closed loop (serve_closed_loop) on the
             phase-2 geometry, YCSB-B, 3,200 ops: ok, no drops, no
             mismatches against the oracle.
5. sharded — (``--chips 4`` only) a (1, 4) (data, model) mesh holding a
             2-shard-bit sharded table; phase 2's operations scaled x4
             against a local table and the oracle; the state must live on
             four devices.

The persistent compile cache is on: ``JAX_COMPILATION_CACHE_DIR`` where
set, else ``<checkout>/.jax_cache`` (``repro/caches.py``). Each phase
reports the backend compile seconds it spent (cache reads included) and
its cache hits. Times are host wall-clock seconds including compiles.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def expect(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)


class CompileMeter:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (the compile event spans cache reads too)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def take(self) -> dict:
        out = {"compile_s": round(self.seconds, 3), "cache_hits": self.hits,
               "cache_misses": self.misses}
        self.seconds, self.hits, self.misses = 0.0, 0, 0
        return out


# ---------------------------------------------------------------------------
# phase 1


def device_phase(want_chips: int) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}
    if d.platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {info}; this smoke run needs "
                         "the chip and never falls back to the CPU")
    expect(len(devs) >= want_chips,
           f"--chips {want_chips} needs {want_chips} devices, found {info}")
    stats = d.memory_stats() or {}
    emit("device", **info,
         memory={k: stats[k] for k in ("bytes_limit", "bytes_in_use")
                 if k in stats})
    return info


# ---------------------------------------------------------------------------
# shared checks


def plan_fields(plan) -> dict:
    return {"backend": plan.backend, "interpret": plan.interpret,
            "fused_lookup": plan.fused_lookup,
            "fused_apply": plan.fused_apply,
            "lookup_tiles": [plan.lookup_tiles.tq, plan.lookup_tiles.pc,
                             plan.lookup_tiles.dc],
            "apply_pc": plan.apply_tiles.pc}


def seeded_keys(seed: int, n: int):
    """``n`` distinct int32 keys (never EMPTY_KEY) and ``n`` values."""
    import numpy as np

    rng = np.random.default_rng(seed)
    keys = np.unique(rng.integers(1, 2**31 - 1, size=n + n // 4 + 64))
    keys = rng.permutation(keys)[:n].astype(np.int32)
    expect(keys.size == n, "not enough distinct keys drawn")
    vals = rng.integers(0, 2**31 - 1, size=n).astype(np.int32)
    return keys, vals


def oracle_for(spec):
    from repro.core.reference import StreamingOracle

    bits = spec.dmax + (spec.shard_bits if spec.placement == "sharded" else 0)
    return StreamingOracle(bits, spec.bucket_size, spec.hash_name)


def lowered_has_kernel(fn, *args) -> bool:
    import jax

    return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()


def timed(fn, *args):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, round(time.perf_counter() - t0, 3)


def check_statuses(got, want, what: str) -> None:
    import numpy as np

    got = np.asarray(got).astype(np.int64)
    bad = np.nonzero(got != want)[0]
    expect(bad.size == 0, f"{what}: {bad.size} status mismatches, first at "
           f"{bad[:5].tolist()}: got {got[bad[:5]].tolist()} want "
           f"{np.asarray(want)[bad[:5]].tolist()}")


def check_lookup(table, oracle, queries, what: str) -> float:
    import numpy as np

    (found, vals), secs = timed(table.lookup, queries)
    w_found, w_vals = oracle.lookup_batch(queries)
    found = np.asarray(found)
    bad = np.nonzero(found != w_found)[0]
    expect(bad.size == 0, f"{what}: {bad.size} found-flag mismatches")
    bad = np.nonzero(np.asarray(vals).astype(np.int64) != w_vals)[0]
    expect(bad.size == 0, f"{what}: {bad.size} value mismatches")
    return secs


def local_content(cfg, state):
    """(keys, values) of every live item, as sorted int64 arrays."""
    import numpy as np

    from repro.core.hashing import EMPTY_KEY

    keys = np.asarray(state.keys)
    vals = np.asarray(state.vals)
    live = np.asarray(state.live)
    if keys.ndim == 3:   # stacked per-shard states
        live = live[:, :cfg.pool_size]
        keys, vals = keys[:, :cfg.pool_size], vals[:, :cfg.pool_size]
    else:
        live = live[:cfg.pool_size]
        keys, vals = keys[:cfg.pool_size], vals[:cfg.pool_size]
    occ = (keys != int(EMPTY_KEY)) & live[..., None]
    k, v = keys[occ].astype(np.int64), vals[occ].astype(np.int64)
    order = np.argsort(k)
    return k[order], v[order]


def check_content(table, oracle, what: str, overflowed: bool) -> None:
    """Structural invariants (per shard) and item-for-item content parity.
    ``overflowed``: the oracle has answered OVERFLOW, which legitimately
    sets the table's error flag."""
    import jax
    import numpy as np

    from repro.core.invariants import check_invariants
    from repro.core.reference import content_digest

    cfg = table.config
    state = table.state
    shards = ([jax.tree.map(lambda a, s=s: a[s], state)
               for s in range(state.keys.shape[0])]
              if state.keys.ndim == 3 else [state])
    for st in shards:
        check_invariants(cfg, st, allow_error=overflowed)
    k, v = local_content(cfg, state)
    want = oracle.as_dict()
    expect(k.size == len(want) == int(table.size()),
           f"{what}: {k.size} items held, oracle {len(want)}, "
           f"size() {int(table.size())}")
    wk = np.fromiter(want.keys(), np.int64, len(want))
    wv = np.fromiter(want.values(), np.int64, len(want))
    order = np.argsort(wk)
    expect((k == wk[order]).all() and (v == wv[order]).all(),
           f"{what}: content differs from the oracle")
    expect(content_digest(k, v) == oracle.digest, f"{what}: digest differs")


# ---------------------------------------------------------------------------
# phases 2, 3, 5: one table workload


def table_workload(name: str, spec, n_keys: int, seed: int, meter,
                   expect_plan: dict, mesh=None, twin_spec=None) -> dict:
    """Insert ``n_keys`` seeded keys, look up all plus 20% absent keys,
    delete a quarter, look up again; oracle parity at every step. With
    ``twin_spec`` a local twin table runs the same operations and must
    agree status for status."""
    import numpy as np

    from repro import compat
    from repro.core.table import DEL, INS, OVERFLOW
    from repro.table_api import Table

    plan = spec.plan()
    for field, want in expect_plan.items():
        expect(getattr(plan, field) == want,
               f"{name}: plan.{field} = {getattr(plan, field)}, want {want}")
    keys, vals = seeded_keys(seed, n_keys + n_keys // 5)
    keys, absent, vals = keys[:n_keys], keys[n_keys:], vals[:n_keys]
    queries = np.concatenate([keys, absent])
    dead = keys[:n_keys // 4]
    oracle = oracle_for(spec)
    out = {"plan": plan_fields(plan), "keys": n_keys, "absent": absent.size,
           "deleted": dead.size}

    tables = {"table": Table.create(spec, mesh)}
    if twin_spec is not None:
        tables["twin"] = Table.create(twin_spec)
    t = tables["table"]
    out["kernels_in_hlo"] = {
        "apply": lowered_has_kernel(lambda t, k, v: t.insert(k, v), t,
                                    keys[:spec.n_lanes], vals[:spec.n_lanes]),
        "lookup": lowered_has_kernel(lambda t, q: t.lookup(q), t,
                                     keys[:spec.n_lanes]),
    }
    if plan.backend == "pallas" and not plan.interpret:
        expect(all(out["kernels_in_hlo"].values()),
               f"{name}: no tpu_custom_call in {out['kernels_in_hlo']}")
    meter.take()

    overflow = 0

    def placed(tab):
        # the sharded table runs under its mesh; the local twin outside it:
        # under an ambient multi-device mesh JAX would partition the twin's
        # Mosaic kernels, which it cannot do
        if tab.mesh is None:
            return contextlib.nullcontext()
        return compat.set_mesh(tab.mesh)

    def mutate(method, kind, *args):
        nonlocal overflow
        want = oracle.run_ops(np.full(args[0].size, kind, np.int32), *args)
        overflow += int((want == OVERFLOW).sum())
        for tag, tab in tables.items():
            with placed(tab):
                (tab, res), secs = timed(getattr(tab, method), *args)
            check_statuses(res.status, want, f"{name}/{tag} {method}")
            expect(bool(res.error) == (overflow > 0),
                   f"{name}/{tag}: error flag {bool(res.error)} after "
                   f"{overflow} OVERFLOW statuses")
            tables[tag] = tab
            out[f"{tag}_{method}_s"] = secs

    def lookup(step):
        for tag, tab in tables.items():
            with placed(tab):
                out[f"{tag}_{step}_s"] = check_lookup(
                    tab, oracle, queries, f"{name}/{tag} {step}")

    mutate("insert", INS, keys, vals)
    with placed(tables["table"]):
        out["depth"] = int(tables["table"].depth())
    lookup("lookup")
    mutate("delete", DEL, dead)
    lookup("relookup")
    for tag, tab in tables.items():
        check_content(tab, oracle, f"{name}/{tag}", overflow > 0)
    out["overflow_statuses"] = overflow
    out["items"] = oracle.size
    out["mismatches"] = 0
    out.update(meter.take())
    out["tables"] = tables
    return out


def served_workload(spec, n_clients: int, ops_per_client: int, seed: int,
                    meter) -> dict:
    from repro.serving.router import RouterConfig
    from repro.serving.router.costmodel import measure_cost_model
    from repro.table_api import Table
    from repro.workloads import serve_closed_loop

    config = RouterConfig(max_batch=2 * spec.n_lanes)
    cost = measure_cost_model(Table.create(spec), max_chunks=2)
    t0 = time.perf_counter()
    rep = serve_closed_loop(spec, n_clients=n_clients,
                            ops_per_client=ops_per_client, mix="B",
                            seed=seed, router_config=config, cost_model=cost)
    secs = round(time.perf_counter() - t0, 3)
    out = {k: rep[k] for k in ("ok", "admitted", "completed", "dropped",
                               "status_mismatches", "content_mismatches")}
    expect(rep["ok"] and rep["dropped"] == 0
           and rep["status_mismatches"] == 0
           and rep["content_mismatches"] == 0,
           f"served: {out} {rep['mismatch_examples']}")
    expect(rep["completed"] == n_clients * ops_per_client,
           f"served: {rep['completed']} of {n_clients * ops_per_client} done")
    out["wall_s"] = secs
    out.update(meter.take())
    return out


# ---------------------------------------------------------------------------


def fused_spec():
    from repro.core.spec import TableSpec

    return TableSpec(dmax=17, bucket_size=8, pool_size=131071, n_lanes=16,
                     initial_depth=8, backend="auto")


TPU_PLAN = {"backend": "pallas", "interpret": False}


def one_chip(meter, seed: int) -> None:
    from repro.core.spec import TableSpec

    out = table_workload(
        "fused", fused_spec(), 100_000, seed, meter,
        {**TPU_PLAN, "fused_lookup": True, "fused_apply": True})
    out.pop("tables")
    emit("fused", **out)

    large = TableSpec(dmax=20, bucket_size=8, pool_size=1 << 20,
                      n_lanes=512, initial_depth=12, backend="auto")
    out = table_workload(
        "large", large, 600_000, seed + 1, meter,
        {**TPU_PLAN, "fused_lookup": False, "fused_apply": False})
    out.pop("tables")
    emit("large", **out)

    emit("served", **served_workload(fused_spec(), 8, 400, seed + 2, meter))


def four_chips(meter, seed: int) -> None:
    import jax

    from repro import compat
    from repro.core.spec import TableSpec

    mesh = compat.make_mesh((1, 4), ("data", "model"),
                            devices=jax.devices()[:4])
    # per shard: phase 2's geometry; 128 lanes per transaction (every shard
    # applies the whole all-gathered batch, so wider batches amortize the
    # collectives). The local twin holds all four shards' keys: two more
    # directory bits, beyond the fused bounds (grouped apply, 512 lanes).
    sharded = TableSpec(dmax=17, bucket_size=8, pool_size=131071, n_lanes=128,
                        initial_depth=8, backend="auto", placement="sharded",
                        shard_bits=2)
    twin = TableSpec(dmax=19, bucket_size=8, pool_size=1 << 19, n_lanes=512,
                     initial_depth=10, backend="auto")
    out = table_workload(
        "sharded", sharded, 400_000, seed + 3, meter,
        {**TPU_PLAN, "fused_lookup": True, "fused_apply": True},
        mesh=mesh, twin_spec=twin)
    t = out.pop("tables")["table"]
    devices = {s.device.id for s in t.state.keys.addressable_shards}
    rows = [s.data.shape for s in t.state.keys.addressable_shards]
    expect(len(devices) == 4, f"sharded state on devices {devices}")
    expect(all(r[0] == 1 for r in rows), f"shard shapes {rows}")
    emit("sharded", **out, state_devices=sorted(devices),
         twin_plan=plan_fields(twin.plan()))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    info = device_phase(args.chips)
    from repro.caches import enable_compile_cache

    cache_dir = enable_compile_cache()
    meter = CompileMeter()
    emit("cache", dir=cache_dir)
    if args.chips == 4:
        four_chips(meter, args.seed)
    else:
        one_chip(meter, args.seed)
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    emit("memory", peak_bytes_in_use=stats.get("peak_bytes_in_use"))
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
