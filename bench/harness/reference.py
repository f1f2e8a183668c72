"""The plain reference: a hash index as a Python dict, with the table's
stated capacity rule, written from the semantics and sharing no code
with the program.

Semantics (the paper's sequential table, DESIGN.md §3 of the program):

* an insert is an upsert: TRUE if the key was absent, FALSE if its value
  was replaced; a delete is TRUE if the key was present, else FALSE;
* the index addresses keys by the top ``bits`` bits of their fmix32 hash
  (``dmax``, plus the shard bits when the index is sharded). Keys that
  share all those bits share one bucket of ``bucket_size`` slots, which no
  split can divide. While such a group holds ``bucket_size`` live keys,
  every write to a key of that group (insert, update or delete) returns
  OVERFLOW and changes nothing;
* a read returns ``(True, value)`` for a live key, ``(False, -1)``
  otherwise.

Writes and reads are applied in the order given: the caller replays the
router's linearisation order (per dispatch, writes in lane order, then
reads), or the bulk loop's call order.
"""
from __future__ import annotations

import numpy as np

TRUE, FALSE, OVERFLOW = 1, 0, -3
INS, DEL = 1, 2


def fmix32(keys: np.ndarray) -> np.ndarray:
    """MurmurHash3's 32-bit finalizer over int32 keys, as uint32."""
    h = np.asarray(keys).astype(np.int64).astype(np.uint32)
    with np.errstate(over="ignore"):
        h = h ^ (h >> np.uint32(16))
        h = h * np.uint32(0x85EBCA6B)
        h = h ^ (h >> np.uint32(13))
        h = h * np.uint32(0xC2B2AE35)
        h = h ^ (h >> np.uint32(16))
    return h


class PlainIndex:
    """Sequential key → value index with the bucket-group capacity rule."""

    def __init__(self, bits: int, bucket_size: int):
        self.shift = np.uint32(32 - bits)
        self.bucket_size = bucket_size
        self.items: dict = {}
        self.groups: dict = {}

    def groups_of(self, keys) -> list:
        return (fmix32(keys) >> self.shift).tolist()

    def write(self, kinds, keys, values) -> np.ndarray:
        """Apply writes in order; returns their statuses."""
        items, groups, b = self.items, self.groups, self.bucket_size
        out = []
        for kind, key, value, g in zip(np.asarray(kinds).tolist(),
                                       np.asarray(keys).tolist(),
                                       np.asarray(values).tolist(),
                                       self.groups_of(keys)):
            n = groups.get(g, 0)
            if n >= b:
                out.append(OVERFLOW)
            elif kind == INS:
                if key in items:
                    out.append(FALSE)
                else:
                    groups[g] = n + 1
                    out.append(TRUE)
                items[key] = value
            elif kind == DEL:
                if key in items:
                    del items[key]
                    groups[g] = n - 1
                    out.append(TRUE)
                else:
                    out.append(FALSE)
            else:
                raise ValueError(f"unknown write kind {kind}")
        return np.asarray(out, np.int64)

    def read(self, keys):
        """``(found, values)`` for a batch of keys, -1 where absent."""
        got = [self.items.get(k) for k in np.asarray(keys).tolist()]
        found = np.asarray([v is not None for v in got], bool)
        vals = np.asarray([-1 if v is None else v for v in got], np.int64)
        return found, vals

    def content(self):
        """(keys, values) of every live item, sorted by key, as int64."""
        k = np.fromiter(self.items.keys(), np.int64, len(self.items))
        v = np.fromiter(self.items.values(), np.int64, len(self.items))
        order = np.argsort(k)
        return k[order], v[order]


def content_mismatches(got_keys, got_vals, want_keys, want_vals) -> int:
    """Items present in one content and not the other, or with another
    value: the size of the symmetric difference of the two item sets."""
    got = set(zip(np.asarray(got_keys).tolist(), np.asarray(got_vals).tolist()))
    want = set(zip(np.asarray(want_keys).tolist(),
                   np.asarray(want_vals).tolist()))
    return len(got ^ want) + (len(got_keys) - len(got))
