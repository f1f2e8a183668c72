"""Capture of a profiler trace in the middle of the window, and its
reduction to busy time, idle gaps, kernel time and collective time.

The reduction reads the ``.xplane.pb`` that ``jax.profiler`` writes, as a
flat list of :class:`Event` (plane, line, name, start, duration), so that
a small recorded excerpt can be kept as a test fixture:

* the traced window is the host span ``bench.traced``, opened right after
  the profiler starts and closed right before it stops;
* a device is a plane named ``/device:TPU:<n>``; its operations are the
  events of its ``XLA Ops`` line, named by their HLO instruction text.
  Busy time is the length of the union of their intervals inside the
  window; idle is the rest;
* a ``while`` or ``cond`` spans the operations of its body; per-operation
  and per-category times count only operations that hold no other one;
* kernel and collective time is the summed duration of the operations
  whose instruction name matches a category (:data:`CATEGORIES`);
  collectives are also counted where the ``Async XLA Ops`` line spans
  them;
* each idle gap of the first device is named by the host span
  (``bench.*`` and ``facade.*``) that overlaps it most, ``unannotated``
  where none does.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.traced"
HOST_SPAN = re.compile(r"^(bench|facade)\.")
INSTRUCTION = re.compile(r"^%?([\w.-]+?)(\.\d+)? = ")

# HLO instruction names as the TPU trace shows them (the Pallas kernels
# take their names from the kernel wrappers), by what they do
CATEGORIES = {
    "lookup": re.compile(r"^(fused_probe|probe)$"),
    "apply": re.compile(r"^(fused_apply|grouped_apply)$"),
    "collective": re.compile(r"^(all-reduce|all-gather|reduce-scatter|"
                             r"collective-permute|all-to-all)(-start)?$"),
}


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def op(self) -> str:
        """The HLO instruction's name without its number (``probe.1`` ->
        ``probe``), or the whole name where it is no instruction."""
        m = INSTRUCTION.match(self.name)
        return m.group(1) if m else self.name

    @property
    def instruction(self) -> str:
        m = INSTRUCTION.match(self.name)
        return m.group(1) + (m.group(2) or "") if m else self.name


def load_events(trace_dir: str) -> list:
    """Every device event and host span of the newest ``.xplane.pb`` under
    ``trace_dir``."""
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return []
    data = jax.profiler.ProfileData.from_file(paths[-1])
    out = []
    for plane in data.planes:
        device = DEVICE_PLANE.match(plane.name)
        if not (device or plane.name.startswith("/host:")):
            continue
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, ASYNC_LINE,
                                             MODULES_LINE):
                continue
            for ev in line.events:
                if device or HOST_SPAN.match(ev.name):
                    out.append(Event(plane.name, line.name, ev.name,
                                     float(ev.start_ns),
                                     float(ev.duration_ns)))
    return out


def _union(intervals) -> list:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _leaves(ops) -> list:
    """Operations that hold no other operation: a ``while`` or ``cond``
    spans the operations of its body, which are counted instead."""
    ops = sorted(ops, key=lambda e: (e.start_ns, -e.dur_ns))
    parent = set()
    stack = []
    for e in ops:
        while stack and stack[-1].end_ns <= e.start_ns:
            stack.pop()
        if stack and e.end_ns <= stack[-1].end_ns:
            parent.add(id(stack[-1]))
        stack.append(e)
    return [e for e in ops if id(e) not in parent]


def _clip(e, w0, w1) -> float:
    return max(0.0, min(e.end_ns, w1) - max(e.start_ns, w0)) * 1e-9


def reduce_events(events, categories=CATEGORIES, top: int = 10) -> dict:
    """Busy, idle, per-category and per-operation device time of the
    traced window (seconds; per device, and their mean)."""
    spans = [e for e in events if e.plane.startswith("/host:")]
    win = [e for e in spans if e.name == WINDOW_SPAN]
    devices = sorted({e.plane for e in events if DEVICE_PLANE.match(e.plane)},
                     key=lambda p: int(DEVICE_PLANE.match(p).group(1)))
    if not devices:
        return {"devices": 0}
    ops = [e for e in events if e.plane in devices and e.line == OPS_LINE]
    if win:
        w0, w1 = win[0].start_ns, win[0].end_ns
    else:
        w0 = min(e.start_ns for e in ops)
        w1 = max(e.end_ns for e in ops)
    per_dev = []
    op_time: dict = {}
    for plane in devices:
        mine = [e for e in ops if e.plane == plane
                and e.start_ns < w1 and e.end_ns > w0]
        busy = _union((max(e.start_ns, w0), min(e.end_ns, w1))
                      for e in mine)
        modules = sorted((e.start_ns, e.end_ns, e.name.split("(")[0])
                         for e in events if e.plane == plane
                         and e.line == MODULES_LINE)
        cats = {c: 0.0 for c in categories}
        for e in _leaves(mine):
            d = _clip(e, w0, w1)
            mod = next((m for s, t, m in modules
                        if s <= e.start_ns < t), "")
            key = f"{mod}/{e.instruction}" if mod else e.instruction
            op_time[key] = op_time.get(key, 0.0) + d / len(devices)
            for c, pat in categories.items():
                if pat.match(e.op):
                    cats[c] += d
        # collectives run asynchronously too: their async line spans them
        for e in events:
            if (e.plane == plane and e.line == ASYNC_LINE
                    and categories.get("collective")
                    and categories["collective"].match(e.op)):
                cats["collective"] += _clip(e, w0, w1)
        per_dev.append({"busy_s": sum(t - s for s, t in busy) * 1e-9,
                        "categories": cats, "busy": busy})
    gaps = {}
    edges = [w0] + [x for s, t in per_dev[0]["busy"] for x in (s, t)] + [w1]
    named = [e for e in spans if e.name != WINDOW_SPAN]
    for s, t in zip(edges[0::2], edges[1::2]):
        if t <= s:
            continue
        best, name = 0.0, "unannotated"
        for sp in named:
            ov = min(t, sp.end_ns) - max(s, sp.start_ns)
            if ov > best:
                best, name = ov, sp.name
        gaps[name] = gaps.get(name, 0.0) + (t - s) * 1e-9
    n = len(per_dev)
    return {
        "devices": n,
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(d["busy_s"] for d in per_dev) / n,
        "busy_s_per_device": [d["busy_s"] for d in per_dev],
        "category_s": {c: sum(d["categories"][c] for d in per_dev) / n
                       for c in categories},
        "category_s_device0": per_dev[0]["categories"],
        "device_ops": sorted(op_time.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(gaps.items(), key=lambda kv: -kv[1])[:top],
    }


class Tracer:
    """Starts the profiler at the first poll after ``start_at`` (host
    clock); :meth:`stop` ends it. Notes the loop's counters at both
    ends."""

    def __init__(self, trace_dir: str, start_at: float):
        self.dir = trace_dir
        self.start_at = start_at
        self.state = "waiting"
        self.counters = None

    def poll(self, now: float, counters) -> None:
        import jax

        if self.state == "waiting" and now >= self.start_at:
            jax.profiler.start_trace(self.dir)
            self._c0 = counters()
            self._ann = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            self._ann.__enter__()
            self.state = "tracing"

    def stop(self, counters) -> None:
        import jax

        if self.state != "tracing":
            return
        self._ann.__exit__(None, None, None)
        c1 = counters()
        jax.profiler.stop_trace()
        self.counters = {k: c1[k] - self._c0[k] for k in c1}
        self.state = "done"
