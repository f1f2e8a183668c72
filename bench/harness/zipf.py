"""YCSB's scrambled Zipfian key chooser, vectorised.

A transcription of ``ZipfianGenerator`` (Gray et al.'s closed form) and
``ScrambledZipfianGenerator`` from YCSB's core package
(github.com/brianfrankcooper/YCSB, ``core/.../generator``): ranks are
drawn from a Zipfian over 10^10 items with constant 0.99 and the
precomputed zeta, then scrambled onto the record ids with
``fnvhash64(rank) % recordcount``. The hot records are therefore spread
over the key space rather than being the first records loaded.
"""
from __future__ import annotations

import numpy as np

ITEM_COUNT = 10_000_000_000
ZIPFIAN_CONSTANT = 0.99
ZETAN = 26.46902820178302     # zeta(ITEM_COUNT, 0.99), as YCSB hard-codes it

FNV_OFFSET_BASIS_64 = np.uint64(0xCBF29CE484222325)
FNV_PRIME_64 = np.uint64(1099511628211)


def zeta(n: int, theta: float) -> float:
    return float(np.sum(np.arange(1, n + 1, dtype=np.float64) ** -theta))


def zipf_ranks(rng: np.random.Generator, size: int,
               items: int = ITEM_COUNT, theta: float = ZIPFIAN_CONSTANT,
               zetan: float | None = None) -> np.ndarray:
    """``size`` Zipfian ranks in ``[0, items)``; rank 0 is the hottest."""
    if zetan is None:
        zetan = ZETAN if (items, theta) == (ITEM_COUNT, ZIPFIAN_CONSTANT) \
            else zeta(items, theta)
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    u = rng.random(size)
    uz = u * zetan
    ranks = (items * np.power(eta * u - eta + 1.0, alpha)).astype(np.int64)
    ranks = np.where(uz < zeta2, 1, ranks)
    ranks = np.where(uz < 1.0, 0, ranks)
    return np.minimum(ranks, items - 1)


def fnvhash64(values: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64``: FNV-1a over the 8 little-endian bytes,
    then ``Math.abs`` of the signed result."""
    v = values.astype(np.int64).view(np.uint64)
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h = (h ^ (v & np.uint64(0xFF))) * FNV_PRIME_64
            v = v >> np.uint64(8)
    return np.abs(h.view(np.int64))


def scrambled_zipf(rng: np.random.Generator, size: int, records: int,
                   theta: float = ZIPFIAN_CONSTANT) -> np.ndarray:
    """``size`` record numbers in ``[0, records)``, scrambled Zipfian."""
    return fnvhash64(zipf_ranks(rng, size, theta=theta)) % records
