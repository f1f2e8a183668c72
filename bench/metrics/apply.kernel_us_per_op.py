"""Device time of the apply kernels (grouped and fused) in the traced
window, per write issued there (mean over the cell's chips)."""


def read(run):
    t, c = run.trace, run.trace_counters
    if not t or not t.get("devices") or not c or c.get("write_ops", 0) <= 0:
        return None
    s = t["category_s"]["apply"]
    return s / c["write_ops"] * 1e6 if s > 0 else None
