"""The control: the plain reference put in the table's place, with one
guarantee that the configurations state broken. A comparison that the
control passes cannot tell a sound table from a broken one.

* Served cells (:class:`StaleReads`): each dispatch's reads are answered
  from the index as it was before that dispatch's writes, which breaks
  "results are linearisable in the router's dispatch order" (writes in
  lane order, then reads) and with it "every acknowledged write is read
  back" for reads dispatched with the write.
* The bulk cell (:class:`LostWrite`): the last insert of every call is
  acknowledged (TRUE) but never applied, which breaks "every acknowledged
  write is read back".

Both answer through the surface the router and the bulk loop call
(``apply``, ``lookup``, ``insert``, ``delete``, ``policy_stats``), so the
run around them is the benchmark's own.
"""
from __future__ import annotations

import numpy as np

from harness.reference import DEL, INS, TRUE, PlainIndex


class _Result:
    def __init__(self, status):
        self.status = np.asarray(status, np.int8)
        self.error = False


class _ReferenceTable:
    def __init__(self, spec, mesh=None, bits=None):
        self.spec, self.mesh = spec, mesh
        if bits is None:
            bits = spec.dmax + (spec.shard_bits
                                if spec.placement == "sharded" else 0)
        self.ref = PlainIndex(bits, spec.bucket_size)

    def _write(self, kinds, keys, values):
        kinds, keys = np.asarray(kinds), np.asarray(keys)
        values = np.zeros_like(keys) if values is None else np.asarray(values)
        status = np.zeros(kinds.size, np.int64)
        live = kinds != 0
        if live.any():
            status[live] = self.ref.write(kinds[live], keys[live],
                                          values[live])
        return status

    def policy_stats(self):
        return {"splits": 0, "merges": 0, "pressure": 0.0}

    def content(self):
        return self.ref.content()


class StaleReads(_ReferenceTable):
    """Reads see the index as it was before the same dispatch's writes."""

    def __init__(self, *a, **k):
        super().__init__(*a, **k)
        self._before = {}

    def apply(self, kinds, keys, values=None):
        self._before = {}
        for kind, key in zip(np.asarray(kinds).tolist(),
                             np.asarray(keys).tolist()):
            if kind != 0 and key not in self._before:
                self._before[key] = self.ref.items.get(key)
        return self, _Result(self._write(kinds, keys, values))

    def insert(self, keys, values=None):
        return self.apply(np.full(len(keys), INS), keys, values)

    def lookup(self, keys):
        found, vals = self.ref.read(keys)
        for i, key in enumerate(np.asarray(keys).tolist()):
            if key in self._before:
                old = self._before[key]
                found[i] = old is not None
                vals[i] = -1 if old is None else old
        self._before = {}
        return found, vals


class LostWrite(_ReferenceTable):
    """The last insert of every call is acknowledged and dropped."""

    def insert(self, keys, values=None):
        keys = np.asarray(keys)
        kinds = np.full(keys.size, INS)
        kinds[-1] = 0
        status = self._write(kinds, keys, values)
        status[-1] = TRUE
        return self, _Result(status)

    def delete(self, keys):
        return self, _Result(self._write(np.full(len(keys), DEL), keys, None))
