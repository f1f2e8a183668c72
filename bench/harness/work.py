"""Bytes each operation needs to move, from the table's shapes alone.

The count is what the operation must touch in HBM whatever kernel runs
it, so a roofline share built on it does not move when the
implementation does (an implementation that sweeps the whole pool for
every query tile moves far more, and shows as a low share):

* a lookup reads its query key, one directory word and one bucket row
  (``bucket_size`` keys and as many values), and writes its result
  (a found flag and a value);
* a write (insert, update or delete) reads its op (kind, key, value),
  one directory word and one bucket row, writes that bucket row back,
  and writes its status.

Keys, values, directory words and op fields are 4-byte words; found
flags and statuses are one byte.
"""
from __future__ import annotations

WORD = 4
FLAG = 1


def bucket_row_bytes(bucket_size: int) -> int:
    return 2 * bucket_size * WORD


def lookup_bytes(bucket_size: int) -> int:
    """HBM bytes one lookup needs."""
    return WORD + WORD + bucket_row_bytes(bucket_size) + FLAG + WORD


def write_bytes(bucket_size: int) -> int:
    """HBM bytes one insert, update or delete needs."""
    return 3 * WORD + WORD + 2 * bucket_row_bytes(bucket_size) + FLAG


def roofline_pct(n_ops: int, bytes_per_op: int, kernel_s: float,
                 hbm_bytes_per_s: float):
    """Share (%) of the bandwidth roofline: the least time the needed
    bytes take at peak bandwidth, over the measured kernel time. None
    where there is nothing to divide by."""
    if n_ops <= 0 or kernel_s <= 0:
        return None
    return 100.0 * (n_ops * bytes_per_op / hbm_bytes_per_s) / kernel_s
