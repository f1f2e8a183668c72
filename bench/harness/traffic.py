"""The one general traffic generator: every mix is a data file under
``bench/traffic`` that this module reads.

Everything is drawn from the run's seed, in set-up, before the window
opens. Sizes do not depend on the seed: an open-loop stream holds exactly
``round(rate * seconds)`` operations with exactly the mix's share of each
kind, spread over the window by normalised exponential gaps (Poisson-like
arrivals whose count does not vary); only the order, the keys and the
gaps do.

Keys are 31-bit record ids, distinct, never 0 and never the table's
empty-slot sentinel. A record's value is its row id in the store; an
update appends a new version of the record and repoints the index entry
at it, so update values are fresh row ids from ``records`` upward.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from harness.zipf import scrambled_zipf

READ, UPDATE, INSERT, DELETE = "read", "update", "insert", "delete"
KINDS = (READ, UPDATE, INSERT, DELETE)


def record_keys(rng: np.random.Generator, n: int) -> np.ndarray:
    """``n`` distinct 31-bit record ids in random order."""
    keys = np.unique(rng.integers(1, 2**31 - 1, size=n + n // 4 + 64))
    keys = rng.permutation(keys)[:n].astype(np.int32)
    if keys.size != n:
        raise RuntimeError("not enough distinct keys drawn")
    return keys


@dataclasses.dataclass
class OpenStream:
    """Pre-generated open-loop operations, in due order."""

    due_s: np.ndarray      # float64[n], seconds after the window opens
    kind: np.ndarray       # int8[n], index into KINDS
    key: np.ndarray        # int32[n]
    value: np.ndarray      # int32[n] (row id for writes, 0 for reads)

    def __len__(self) -> int:
        return self.due_s.size


def exact_counts(mix: dict, n: int) -> dict:
    """Per-kind op counts summing to ``n`` in the mix's proportions."""
    total = sum(mix.values())
    names = [k for k in KINDS if mix.get(k, 0) > 0]
    counts = {k: int(round(n * mix[k] / total)) for k in names}
    counts[names[0]] += n - sum(counts.values())
    return counts


def open_stream(traffic: dict, record_ids: np.ndarray, rate_ops_s: float,
                seconds: float, rng: np.random.Generator) -> OpenStream:
    """The open-loop stream of one run: reads and updates of existing
    records, keys chosen by the mix's distribution."""
    n = max(1, int(round(rate_ops_s * seconds)))
    counts = exact_counts(traffic["mix"], n)
    unknown = set(counts) - {READ, UPDATE}
    if unknown:
        raise ValueError(f"open loop serves reads and updates, not {unknown}")
    kind = np.concatenate([np.full(c, KINDS.index(k), np.int8)
                           for k, c in counts.items()])
    kind = rng.permutation(kind)
    dist = traffic["keys"]
    if dist["distribution"] != "scrambled_zipfian":
        raise ValueError(f"unknown key distribution {dist['distribution']}")
    if traffic["arrivals"] != "poisson":
        raise ValueError(f"unknown arrival process {traffic['arrivals']}")
    rec = scrambled_zipf(rng, n, record_ids.size, dist["zipfian_constant"])
    gaps = rng.exponential(size=n + 1)
    ends = np.cumsum(gaps)
    due = ends[:n] / ends[n] * seconds
    value = np.zeros(n, np.int64)
    upd = kind == KINDS.index(UPDATE)
    value[upd] = record_ids.size + np.arange(int(upd.sum()))
    return OpenStream(due_s=due, kind=kind, key=record_ids[rec],
                      value=value.astype(np.int32))


# Which keys a bulk set holds decides how often the table splits and
# merges, and so the loop's rate: every seed loads the same keys, each set
# in an order of its own.
BULK_POOL_SEED = 0x5EED


def bulk_key_sets(traffic: dict, rng: np.random.Generator) -> list:
    """Key sets of the bulk loop: ``key_sets`` sets of ``cycle_keys``
    fresh keys, the same for every seed, each in the seed's order; cycle
    ``c`` loads and drains set ``c % key_sets``."""
    n = traffic["cycle_keys"]
    keys = record_keys(np.random.default_rng(BULK_POOL_SEED),
                       n * traffic["key_sets"])
    return [rng.permutation(keys[i * n:(i + 1) * n])
            for i in range(traffic["key_sets"])]
