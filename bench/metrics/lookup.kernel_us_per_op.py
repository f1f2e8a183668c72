"""Device time of the probe kernels (fused and unfused) in the traced
window, per lookup dispatched there (mean over the cell's chips)."""


def read(run):
    t, c = run.trace, run.trace_counters
    if not t or not t.get("devices") or not c or c.get("read_ops", 0) <= 0:
        return None
    s = t["category_s"]["lookup"]
    return s / c["read_ops"] * 1e6 if s > 0 else None
