"""Share of the HBM roofline the apply kernels reach: the bytes the
writes of the traced window need (harness/work.py), at the peak
bandwidth of the cell's chips, over the apply kernels' device time."""
from harness import work


def read(run):
    t, c = run.trace, run.trace_counters
    if not t or not t.get("devices") or not c or not run.peaks:
        return None
    return work.roofline_pct(
        c.get("write_ops", 0), work.write_bytes(run.spec.bucket_size),
        t["category_s"]["apply"],
        run.peaks["hbm_bytes_per_s"] * t["devices"])
