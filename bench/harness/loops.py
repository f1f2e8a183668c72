"""The two loops that run the measured window.

* :func:`open_loop` — independent clients at a fixed offered rate: every
  operation has a due time drawn in set-up, is submitted to the serving
  ``Router`` once it falls due, and is timed from that due time (not from
  the submit call) to its result on the host. Nothing is checked inside
  the window; results are recorded and checked after it.
* :func:`bulk_loop` — one loader in a closed loop over the ``Table``
  facade: load a key set in calls of ``call_keys``, drain it in calls of
  the same size, and repeat with the next set.

Both take a ``tracer`` whose ``poll`` starts the profiler at its set
time, between dispatches, and which they stop once the window has
closed (stopping writes the trace, which takes seconds); host spans (``bench.*``) mark what
the host is doing, so that an idle gap on the device can be named.
"""
from __future__ import annotations

import dataclasses
import time
import typing

import numpy as np

from harness.traffic import KINDS, UPDATE


def _span(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


def router_counters(router) -> dict:
    m = router.metrics
    return {"dispatches": m.dispatches, "write_ops": m.dispatched_ops,
            "read_ops": m.lookup_ops, "shed_queue_full": m.shed_queue_full,
            "shed_pressure": m.shed_pressure,
            "maintenance_rounds": m.maintenance_rounds}


def delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


class NoTracer:
    start_at = float("inf")

    def poll(self, now: float, counters) -> None:
        pass

    def stop(self, counters) -> None:
        pass


@dataclasses.dataclass
class OpenRecords:
    t_open: float
    t_close: float
    t_drained: float
    due: np.ndarray          # absolute due times (host clock)
    t_done: np.ndarray       # nan where no result came
    t_dispatch: np.ndarray
    shed: np.ndarray         # bool
    batches: list            # per dispatch: (write op ids, read op ids)
    status: np.ndarray       # write statuses
    found: np.ndarray
    value: np.ndarray
    counters: dict           # router counters over the window
    backlog_at_close: int
    max_dispatch_gap_s: float  # longest time between two results


def open_loop(router, stream, seconds: float, tracer=NoTracer(),
              clock=time.perf_counter, drain_s: float = 60.0) -> OpenRecords:
    from repro.serving.router import INS, READ

    n = len(stream)
    kinds = np.where(stream.kind == KINDS.index(UPDATE), INS, READ).tolist()
    keys = stream.key.tolist()
    vals = stream.value.tolist()
    shed = np.zeros(n, bool)
    rid_op = {}
    t_done = np.full(n, np.nan)
    t_disp = np.full(n, np.nan)
    status = np.zeros(n, np.int64)
    found = np.zeros(n, bool)
    value = np.full(n, -1, np.int64)
    batches = []
    gaps = []
    submit, pump, queues = router.submit, router.pump, router.queues
    max_delay = router.config.max_delay_s
    c0 = router_counters(router)

    def record(t, reqs):
        """Results go to arrays as they come, so that no request object
        outlives its dispatch."""
        w_ids, r_ids = [], []
        for r in reqs:
            op = rid_op.pop(r.rid)
            t_done[op] = t
            t_disp[op] = r.t_dispatch
            if r.kind == READ:
                found[op] = r.found
                value[op] = r.result if r.found else -1
                r_ids.append(op)
            else:
                status[op] = r.status
                w_ids.append(op)
        batches.append((w_ids, r_ids))

    t0 = clock()
    t_end = t0 + seconds
    due = (stream.due_s + t0).tolist()
    i = 0
    t_last = t0
    while True:
        now = clock()
        if now >= t_end:
            break
        tracer.poll(now, lambda: router_counters(router))
        if i < n and due[i] <= now:
            with _span("bench.submit"):
                while i < n and due[i] <= now:
                    req, _ = submit(kinds[i], keys[i], vals[i], now)
                    if req is None:
                        shed[i] = True
                    else:
                        rid_op[req.rid] = i
                    i += 1
        if router.should_dispatch(now):
            with _span("bench.pump"):
                out = pump(now)
            if out:
                t = clock()
                gaps.append(t - t_last)
                t_last = t
                record(t, out)
            continue
        pump(now)     # no dispatch due: maintenance under resize pressure
        wake = due[i] if i < n else t_end
        if len(queues):
            wake = min(wake, now + max_delay - queues.oldest_wait(now))
        wait = min(wake, t_end) - clock()
        if wait > 0:
            with _span("bench.wait"):
                time.sleep(wait)
    tracer.stop(lambda: router_counters(router))
    t_close = clock()
    backlog = len(queues) + (n - i)
    c1 = router_counters(router)
    while i < n:          # due before the close, not yet submitted
        req, _ = submit(kinds[i], keys[i], vals[i], t_close)
        if req is None:
            shed[i] = True
        else:
            rid_op[req.rid] = i
        i += 1
    deadline = t_close + drain_s
    while len(queues) and clock() < deadline:
        out = pump(clock(), force=True)
        if out:
            record(clock(), out)
    t_drained = clock()
    return OpenRecords(t_open=t0, t_close=t_end, t_drained=t_drained,
                       due=np.asarray(due), t_done=t_done, t_dispatch=t_disp,
                       shed=shed, batches=batches, status=status, found=found,
                       value=value, counters=delta(c1, c0),
                       backlog_at_close=backlog,
                       max_dispatch_gap_s=max(gaps, default=0.0))


class Call(typing.NamedTuple):
    """One call of the bulk loop: its host times, its kind (INS or DEL),
    the key set and the index of its first key there, and its statuses."""
    t_start: float
    t_done: float
    kind: int
    key_set: int
    pos: int
    status: np.ndarray


@dataclasses.dataclass
class BulkRecords:
    t_open: float
    t_close: float
    calls: list      # of Call, in order
    cycles_done: int


def bulk_loop(table, key_sets, value_sets, call_keys: int, seconds: float,
              tracer=NoTracer(), clock=time.perf_counter):
    """Load and drain key sets until the window closes. Returns the final
    table handle and the records; the call that is running when the
    window closes completes after it."""
    import jax

    from repro.core.table import DEL, INS

    calls = []
    n = len(key_sets[0])
    c = pos = cycles = 0
    phase = INS
    t0 = clock()
    t_end = t0 + seconds
    ops = [0]
    while True:
        tracer.poll(clock(), lambda: {"write_ops": ops[0]})
        if clock() >= t_end:
            break
        s = c % len(key_sets)
        keys = key_sets[s][pos:pos + call_keys]
        t_start = clock()
        with _span("bench.call"):
            if phase == INS:
                table, res = table.insert(keys,
                                          value_sets[s][pos:pos + call_keys])
            else:
                table, res = table.delete(keys)
            status = np.asarray(jax.block_until_ready(res.status))
        calls.append(Call(t_start, clock(), phase, s, pos, status))
        ops[0] += status.size
        pos += call_keys
        if pos >= n:
            pos = 0
            if phase == INS:
                phase = DEL
            else:
                phase, c, cycles = INS, c + 1, cycles + 1
    tracer.stop(lambda: {"write_ops": ops[0]})
    return table, BulkRecords(t_open=t0, t_close=t_end, calls=calls,
                              cycles_done=cycles)
