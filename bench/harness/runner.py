"""One run of one cell: set-up, the measured window, the check against
the plain reference, and the result line.

Set-up (counted in ``setup_s``, from process start to the window's
opening): warm up the shapes this cell's traffic uses on scratch tables
and free them, build the cell's table from the configuration, pre-fill
the records through ``Table.insert`` in one call where the traffic
reads, and draw the whole operation stream from the seed. The window
then runs the cell's loop for ``seconds``. After it, the queue is
drained, the device memory read, the final content copied to the host
and the device state freed; only then does the plain reference replay
every operation and compare.
"""
from __future__ import annotations

import contextlib
import gc
import tempfile
import time

import numpy as np

from harness import device as dev
from harness import loops, metrics, trace, traffic
from harness.peaks import peaks_for
from harness.reference import INS, PlainIndex, content_mismatches

EMPTY_KEY = -(2**31)   # the table's free-slot sentinel
MIB = 1 << 20
TRACE_SECONDS = 3.0


# ---------------------------------------------------------------------------
# the system under test, as the configuration states it


def build_spec(config: dict, devices):
    from repro import compat
    from repro.core.policy import ResizePolicy
    from repro.core.spec import TableSpec

    pol = config.get("resize_policy")
    spec = TableSpec(**config["table"],
                     resize_policy=None if pol is None else ResizePolicy(**pol))
    mesh = None
    if config.get("mesh"):
        m = config["mesh"]
        mesh = compat.make_mesh(tuple(m["shape"]), tuple(m["axes"]),
                                devices=devices)
    return spec, mesh


def check_plan(spec, config: dict, platform: str) -> None:
    """On the chip the table must run the kernels the configuration
    names: a run on another plan measures another system."""
    if platform != "tpu":
        return
    plan = spec.plan()
    want = {"backend": "pallas", "interpret": False, **config["plan"]}
    got = {k: getattr(plan, k) for k in want}
    if got != want:
        raise RuntimeError(f"plan {got} differs from the configuration's "
                           f"{want}")


def index_bits(spec) -> int:
    return spec.dmax + (spec.shard_bits if spec.placement == "sharded" else 0)


def table_content(table):
    """(keys, values) of every live item, as int64 host arrays."""
    if hasattr(table, "content"):        # the control
        return table.content()
    cfg = table.config
    P = cfg.pool_size
    keys = np.asarray(table.state.keys)[..., :P, :]
    vals = np.asarray(table.state.vals)[..., :P, :]
    live = np.asarray(table.state.live)[..., :P]
    occ = (keys != EMPTY_KEY) & live[..., None]
    return keys[occ].astype(np.int64), vals[occ].astype(np.int64)


@contextlib.contextmanager
def facade_spans(enabled: bool):
    """Host spans around the facade's calls, for the traced run only."""
    if not enabled:
        yield
        return
    import jax

    from repro.table_api import Table

    saved = {m: getattr(Table, m) for m in ("apply", "lookup", "insert",
                                            "delete")}

    def wrap(name, fn):
        def spanned(self, *a, **k):
            with jax.profiler.TraceAnnotation(f"facade.{name}"):
                return fn(self, *a, **k)
        return spanned

    for m, fn in saved.items():
        setattr(Table, m, wrap(m, fn))
    try:
        yield
    finally:
        for m, fn in saved.items():
            setattr(Table, m, fn)


# ---------------------------------------------------------------------------


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation."""
    return float(np.percentile(np.asarray(values, np.float64), q))


class Run:
    """What a run leaves for the per-layer metric readers."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def run_cell(cell, seed: int, seconds: float, trace_on: bool,
             t_process: float, devices, log=print, make_table=None) -> dict:
    """One run of ``cell``; returns the result line as a dict.
    ``make_table(spec, mesh)`` builds the system under test (the facade's
    ``Table.create``; the control puts the reference there)."""
    from repro import compat
    from repro.table_api import Table

    make_table = make_table or Table.create
    meter = dev.CompileMeter()
    info = dev.describe(devices)
    spec, mesh = build_spec(cell.config, devices)
    check_plan(spec, cell.config, info["platform"])
    rng = np.random.default_rng(seed)
    kind = cell.traffic["loop"]
    placed = (contextlib.nullcontext() if mesh is None
              else compat.set_mesh(mesh))
    tmp = tempfile.TemporaryDirectory(prefix="bench_trace_")
    t_len = min(TRACE_SECONDS, seconds / 3)

    def make_tracer(t0):
        """The profiler covers the window's last ``t_len`` seconds."""
        if not trace_on:
            return loops.NoTracer()
        return trace.Tracer(tmp.name, t0 + seconds - t_len)

    with placed, facade_spans(trace_on), tmp:
        if kind == "open":
            out = _open_cell(cell, spec, mesh, make_table, rng, seconds,
                             meter, make_tracer, t_process, devices, log)
        elif kind == "bulk":
            out = _bulk_cell(cell, spec, mesh, make_table, rng, seconds,
                             meter, make_tracer, t_process, devices, log)
        else:
            raise ValueError(f"unknown loop kind {kind!r}")
        reduced = None
        if trace_on:
            reduced = trace.reduce_events(trace.load_events(tmp.name))
    gc.unfreeze()
    run = out["run"]
    run.trace = reduced
    run.peaks = (peaks_for(info["kind"]) if info["platform"] == "tpu"
                 else None)
    checks = out["checks"]
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"]}
    device = {**info, "memory_peak_bytes": out["memory_peak_bytes"]}
    if trace_on:
        result["metrics"] = metrics.read_per_layer(cell, run)
        device["busy_s"] = (reduced or {}).get("busy_s", 0.0)
        device["window_s"] = (reduced or {}).get("window_s", 0.0)
        if reduced and reduced.get("devices"):
            result["breakdown"] = {
                "device_ops": [list(x) for x in reduced["device_ops"]],
                "idle_gaps": [list(x) for x in reduced["idle_gaps"]]}
    else:
        result["metrics"] = {m["name"]: {"value": out["e2e"][m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    result["device"] = device
    result["checks"] = checks
    for k, v in out["notes"].items():
        log(f"note {k}: {v}")
    for k, c in checks.items():
        log(f"check {k}: {c['value']} (limit {c['limit']})")
    return result


def setup_done(meter, t_process: float, log) -> dict:
    """The end of set-up. What set-up made (modules, compiled programs,
    the pre-generated stream) is moved out of the collector's reach, so
    that a collection in the window walks only what the window made."""
    gc.collect()
    gc.freeze()
    snap = meter.snapshot()
    setup_s = time.perf_counter() - t_process
    log(f"note setup_s: {setup_s} compiles {snap['compiles']} compile_s "
        f"{snap['compile_s']} cache_hits {snap['cache_hits']}")
    return {"setup_s": setup_s, **snap}


class GcPauses:
    """Python's garbage collections in the window and their pauses."""

    def __init__(self):
        self.count = 0
        self.total_s = self.max_s = 0.0
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
            return
        dt = time.perf_counter() - self._t
        self.count += 1
        self.total_s += dt
        self.max_s = max(self.max_s, dt)

    def close(self) -> dict:
        gc.callbacks.remove(self._on)
        return {"gc_collections": self.count,
                "gc_pause_total_ms": self.total_s * 1e3,
                "gc_pause_max_ms": self.max_s * 1e3}


def latency_numbers(done_ops: int, seconds: float, lat_s) -> dict:
    """``ops_per_s`` and ``p50_ms`` of a window: operations completed over
    the time they took, and the median latency of every operation due in
    it; its tail, ``p95_ms`` and ``p99_ms``, is a note."""
    return {"ops_per_s": done_ops / seconds,
            "p50_ms": percentile(lat_s, 50) * 1e3,
            "p95_ms": percentile(lat_s, 95) * 1e3,
            "p99_ms": percentile(lat_s, 99) * 1e3}


def open_numbers(rec, seconds: float) -> dict:
    """The open loop's latency numbers: an operation is timed from its due
    time; one never answered counts with the time to the drain's end."""
    answered = ~np.isnan(rec.t_done)
    lat = np.where(answered, rec.t_done, rec.t_drained) - rec.due
    done = int((answered & (rec.t_done <= rec.t_close)).sum())
    return latency_numbers(done, seconds, lat)


def _open_cell(cell, spec, mesh, make_table, rng, seconds, meter,
               make_tracer, t_process, devices, log) -> dict:
    router, rec_keys, rec_vals, prefill_status = serving_setup(
        cell, spec, mesh, make_table, rng, log)
    stream = traffic.open_stream(cell.traffic, rec_keys,
                                 cell.settings["rate_ops_s"], seconds, rng)
    setup = setup_done(meter, t_process, log)

    tracer = make_tracer(time.perf_counter())
    pauses = GcPauses()
    rec = loops.open_loop(router, stream, seconds, tracer)
    gc_notes = pauses.close()
    in_window = meter.snapshot()["compiles"] - setup["compiles"]
    mem = dev.memory_peak_bytes(devices)
    in_use = dev.memory_in_use_bytes(devices)
    got_k, got_v = table_content(router.table)
    router.table = None
    del router

    checks, unanswered, overflow = check_open(
        spec, rec_keys, rec_vals, prefill_status, stream, rec, got_k, got_v)
    answered = ~np.isnan(rec.t_done)
    # queue waits of the operations due before the profiler started: its
    # start and stop stall the host
    calm = answered & (rec.due < tracer.start_at)
    waits = rec.t_dispatch[calm] - rec.due[calm]
    e2e = {**open_numbers(rec, seconds), "hbm_peak_mib": mem / MIB,
           "setup_s": setup["setup_s"]}
    c = rec.counters
    run = Run(kind="open", spec=spec, seconds=seconds, ops=len(stream),
              router=c, queue_wait_s=waits, hbm_in_use_bytes=in_use,
              trace_counters=getattr(tracer, "counters", None), policy=None)
    notes = {"offered_ops_s": len(stream) / seconds,
             "backlog_at_close": rec.backlog_at_close,
             "drain_s": rec.t_drained - rec.t_close,
             "compiles_in_window": in_window,
             "shed": int(rec.shed.sum()), "overflow_statuses": overflow,
             "dispatches": c["dispatches"],
             "reads": c["read_ops"], "writes": c["write_ops"],
             "maintenance_rounds": c["maintenance_rounds"],
             "max_dispatch_gap_ms": rec.max_dispatch_gap_s * 1e3,
             "p95_ms": e2e["p95_ms"], "p99_ms": e2e["p99_ms"], **gc_notes,
             "hbm_in_use_mib": in_use / MIB}
    return {"run": run, "e2e": e2e, "checks": checks, "notes": notes,
            "attempted": len(stream),
            "failed": int(rec.shed.sum()) + unanswered + overflow,
            "memory_peak_bytes": mem}


def serving_setup(cell, spec, mesh, make_table, rng, log):
    """Warm the serving path on scratch tables, then build the cell's
    table behind a router and pre-fill the records through
    ``Table.insert`` in one call."""
    import jax

    from repro.serving.router import Router, RouterConfig

    n_rec = cell.config["records"]
    config = RouterConfig(**cell.config["router"])
    cost_model = _warm_router_path(spec, mesh, config, log)
    router = Router(make_table(spec, mesh), config, cost_model=cost_model)
    rec_keys = traffic.record_keys(rng, n_rec)
    rec_vals = np.arange(n_rec, dtype=np.int32)
    t = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.prefill"):
        router.table, res = router.table.insert(rec_keys, rec_vals)
        prefill_status = np.asarray(jax.block_until_ready(res.status))
    log(f"note prefill_s: {time.perf_counter() - t}")
    return router, rec_keys, rec_vals, prefill_status


def _warm_router_path(spec, mesh, config, log):
    """Compile every program of the serving path (each dispatch shape,
    the policy's pressure read) through a scratch router on a scratch
    table, which is freed before the cell's table exists. Returns the
    router's measured cost model, for the cell's router to share."""
    from repro.serving.router import INS, READ, Router
    from repro.table_api import Table

    scratch = Router(Table.create(spec, mesh), config)
    log(f"note batch_floor: {scratch.batch_floor} (cost model base_s "
        f"{scratch.cost_model.base_s} chunk_s {scratch.cost_model.chunk_s})")
    scratch.warmup()
    keys = np.arange(1, 2 * config.max_batch + 1)
    for k in keys:
        scratch.submit(INS, int(k), int(k))
        scratch.submit(READ, int(k))
    scratch.flush()
    scratch.table.policy_stats()
    return scratch.cost_model


def _warm_bulk(spec, mesh, call: int, cycle: int) -> None:
    """Compile the bulk loop's call shapes (a whole call, and the last
    call of a set) on a scratch table, freed before the cell's table
    exists."""
    import jax

    from repro.table_api import Table

    scratch = Table.create(spec, mesh)
    for m in sorted({call, cycle % call} - {0}):
        keys = np.arange(1, m + 1, dtype=np.int32)
        scratch, r = scratch.insert(keys, keys)
        scratch, r = scratch.delete(keys)
        jax.block_until_ready(r.status)
    jax.block_until_ready(scratch.policy_stats()["splits"])


def bulk_numbers(rec, seconds: float) -> dict:
    """The bulk loop's latency numbers: an operation is timed from its
    call's start to the call's results on the host. The rate is every
    call begun in the window over the time from its opening to the last
    call's end, so that it does not move by whole calls."""
    calls = rec.calls
    if not calls:
        return latency_numbers(0, seconds, np.zeros(1))
    lat = np.concatenate([np.full(c.status.size, c.t_done - c.t_start)
                          for c in calls])
    done = sum(c.status.size for c in calls)
    return latency_numbers(done, calls[-1].t_done - rec.t_open, lat)


def _bulk_cell(cell, spec, mesh, make_table, rng, seconds, meter,
               make_tracer, t_process, devices, log) -> dict:
    tr = cell.traffic
    call = tr["call_keys"]
    n = tr["cycle_keys"]
    _warm_bulk(spec, mesh, call, n)
    table = make_table(spec, mesh)
    sets = traffic.bulk_key_sets(tr, rng)
    vals = [np.arange(i * n, (i + 1) * n, dtype=np.int32)
            for i in range(len(sets))]
    setup = setup_done(meter, t_process, log)

    p0 = {k: int(v) for k, v in table.policy_stats().items()
          if k != "pressure"}
    tracer = make_tracer(time.perf_counter())
    pauses = GcPauses()
    table, rec = loops.bulk_loop(table, sets, vals, call, seconds, tracer)
    gc_notes = pauses.close()
    ops_total = sum(c.status.size for c in rec.calls)
    in_window = meter.snapshot()["compiles"] - setup["compiles"]
    p1 = {k: int(v) for k, v in table.policy_stats().items()
          if k != "pressure"}
    mem = dev.memory_peak_bytes(devices)
    in_use = dev.memory_in_use_bytes(devices)
    got_k, got_v = table_content(table)
    del table

    checks, overflow = check_bulk(spec, sets, vals, rec, got_k, got_v)
    e2e = {**bulk_numbers(rec, seconds), "hbm_peak_mib": mem / MIB,
           "setup_s": setup["setup_s"]}
    run = Run(kind="bulk", spec=spec, seconds=seconds, ops=ops_total,
              router=None, queue_wait_s=None, hbm_in_use_bytes=in_use,
              trace_counters=getattr(tracer, "counters", None),
              policy={k: p1[k] - p0[k] for k in p1})
    t_last = rec.calls[-1].t_done if rec.calls else rec.t_close
    notes = {"calls": len(rec.calls), "cycles_done": rec.cycles_done,
             "last_call_end_after_close_s": t_last - rec.t_close,
             "compiles_in_window": in_window, "overflow_statuses": overflow,
             "policy_splits": run.policy["splits"],
             "policy_merges": run.policy["merges"],
             "p95_ms": e2e["p95_ms"], "p99_ms": e2e["p99_ms"], **gc_notes,
             "hbm_in_use_mib": in_use / MIB}
    return {"run": run, "e2e": e2e, "checks": checks, "notes": notes,
            "attempted": ops_total, "failed": overflow,
            "memory_peak_bytes": mem}


# ---------------------------------------------------------------------------
# the comparison with the plain reference (every number's limit is 0: the
# comparison is exact)


def _checks(**values) -> dict:
    return {k: {"value": int(v), "limit": 0} for k, v in values.items()}


def check_open(spec, rec_keys, rec_vals, prefill_status, stream, rec,
               got_k, got_v):
    """Replay the pre-fill, then every dispatch in the router's
    linearisation order (writes in lane order, then reads), and compare
    each status and each read; then the final content. Returns the
    checks, the unanswered count and the OVERFLOW count."""
    ref = PlainIndex(index_bits(spec), spec.bucket_size)
    want = ref.write(np.full(rec_keys.size, INS), rec_keys, rec_vals)
    prefill_mm = int((want != prefill_status).sum())
    status_mm = read_mm = 0
    for w_ids, r_ids in rec.batches:
        if w_ids:
            st = ref.write(np.full(len(w_ids), INS), stream.key[w_ids],
                           stream.value[w_ids])
            status_mm += int((st != rec.status[w_ids]).sum())
        if r_ids:
            f, v = ref.read(stream.key[r_ids])
            read_mm += int(((f != rec.found[r_ids])
                            | (v != rec.value[r_ids])).sum())
    want_k, want_v = ref.content()
    answered = ~np.isnan(rec.t_done)
    unanswered = int((~answered & ~rec.shed).sum())
    checks = _checks(prefill_status_mismatches=prefill_mm,
                     status_mismatches=status_mm, read_mismatches=read_mm,
                     content_mismatches=content_mismatches(
                         got_k, got_v, want_k, want_v),
                     unanswered=unanswered)
    return checks, unanswered, int((rec.status == -3).sum())


def check_bulk(spec, sets, vals, rec, got_k, got_v):
    """Replay every call in order and compare each status; then the final
    content. Returns the checks and the OVERFLOW count."""
    ref = PlainIndex(index_bits(spec), spec.bucket_size)
    status_mm = 0
    for c in rec.calls:
        m = c.status.size
        want = ref.write(np.full(m, c.kind), sets[c.key_set][c.pos:c.pos + m],
                         vals[c.key_set][c.pos:c.pos + m])
        status_mm += int((want != c.status).sum())
    want_k, want_v = ref.content()
    checks = _checks(status_mismatches=status_mm,
                     content_mismatches=content_mismatches(
                         got_k, got_v, want_k, want_v))
    return checks, sum(int((c.status == -3).sum()) for c in rec.calls)
