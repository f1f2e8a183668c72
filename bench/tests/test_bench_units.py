"""The yardstick's parts on their own: the plain reference against the
program's StreamingOracle, the scrambled Zipfian's rank frequencies, and
the needed-work counts under both kernel plans."""
import numpy as np
import pytest

import _tiny  # noqa: F401  (puts bench/ and src/ on the path)
from harness import traffic, work, zipf
from harness.reference import OVERFLOW, PlainIndex, fmix32


@pytest.mark.parametrize("bits", [6, 9])
def test_plain_reference_matches_streaming_oracle(bits):
    from repro.core.reference import StreamingOracle

    rng = np.random.default_rng(2**31 + 7)
    pool = rng.integers(1, 2**31 - 1, 3000).astype(np.int32)
    ref, oracle = PlainIndex(bits, 8), StreamingOracle(bits, 8)
    n_overflow = 0
    for _ in range(20):
        kinds = rng.choice([1, 1, 1, 2], 400)
        keys = pool[rng.integers(0, pool.size, 400)]
        vals = rng.integers(0, 2**31 - 1, 400)
        got = ref.write(kinds, keys, vals)
        want = oracle.run_ops(kinds, keys, vals)
        np.testing.assert_array_equal(got, want)
        n_overflow += int((got == OVERFLOW).sum())
        q = pool[rng.integers(0, pool.size, 200)]
        f1, v1 = ref.read(q)
        f2, v2 = oracle.lookup_batch(q)
        np.testing.assert_array_equal(f1, f2)
        np.testing.assert_array_equal(v1, v2)
    assert n_overflow > 0 or bits > 6   # the small index does run out
    k, v = ref.content()
    assert dict(zip(k.tolist(), v.tolist())) == oracle.as_dict()


def test_fmix32_matches_the_program():
    from repro.core.hashing import hash_np

    keys = np.random.default_rng(3).integers(-2**31, 2**31 - 1, 1000,
                                             dtype=np.int64).astype(np.int32)
    np.testing.assert_array_equal(fmix32(keys), hash_np("fmix32", keys))


def test_zipf_rank_frequencies_follow_the_law():
    """Ranks 0 and 1 are exact in YCSB's closed form (Gray et al.); above
    them the form inverts an integral of the law, so ranks 2..9 follow
    that inverse exactly and the 1/rank**theta law within a quarter."""
    rng = np.random.default_rng(2**32 + 5)
    n = 2_000_000
    ranks = zipf.zipf_ranks(rng, n)
    theta, items, zetan = zipf.ZIPFIAN_CONSTANT, zipf.ITEM_COUNT, zipf.ZETAN
    eta = (1 - (2 / items) ** (1 - theta)) / (1 - (1 + 0.5**theta) / zetan)

    def below(r):          # P(rank < r) for r >= 2, from the inverse
        return ((r / items) ** (1 - theta) + eta - 1) / eta

    for r in range(10):
        got = float((ranks == r).mean())
        law = (r + 1) ** -theta / zetan
        want = law if r < 2 else below(r + 1) - below(r)
        assert abs(got - want) < 5 * np.sqrt(want / n), (r, got, want)
        assert abs(got - law) < 0.25 * law, (r, got, law)
    assert ranks.min() >= 0 and ranks.max() < items


def test_scrambled_zipf_spreads_the_hot_records():
    rng = np.random.default_rng(11)
    recs = zipf.scrambled_zipf(rng, 500_000, 600_000)
    assert recs.min() >= 0 and recs.max() < 600_000
    counts = np.bincount(recs, minlength=600_000)
    hottest = int(np.argmax(counts))
    # rank 0 lands on fnvhash64(0) % records, whatever the seed
    assert hottest == int(zipf.fnvhash64(np.array([0]))[0] % 600_000)
    assert counts[hottest] / recs.size == pytest.approx(1 / zipf.ZETAN,
                                                        rel=0.05)


def test_fnvhash64_matches_ycsb():
    # Utils.fnvhash64(0) and (1) in YCSB, computed by hand from FNV-1a
    def fnv(v):
        h = 0xCBF29CE484222325
        for _ in range(8):
            h = ((h ^ (v & 0xFF)) * 1099511628211) & (2**64 - 1)
            v >>= 8
        s = h - 2**64 if h >= 2**63 else h
        return abs(s)
    vals = np.array([0, 1, 12345, 2**40 + 7])
    assert zipf.fnvhash64(vals).tolist() == [fnv(int(v)) for v in vals]


def test_open_stream_sizes_do_not_depend_on_the_seed():
    keys = traffic.record_keys(np.random.default_rng(0), 1000)
    mix = {"mix": {"read": 0.95, "update": 0.05},
           "keys": {"distribution": "scrambled_zipfian",
                    "zipfian_constant": 0.99}, "arrivals": "poisson"}
    a = traffic.open_stream(mix, keys, 1000.0, 3.0, np.random.default_rng(1))
    b = traffic.open_stream(mix, keys, 1000.0, 3.0,
                            np.random.default_rng(2**33))
    assert len(a) == len(b) == 3000
    assert (a.kind == 1).sum() == (b.kind == 1).sum() == 150
    assert a.due_s.max() < 3.0 and np.all(np.diff(a.due_s) >= 0)
    assert not np.array_equal(a.key, b.key)


def test_bulk_sets_hold_the_same_keys_for_every_seed():
    mix = {"cycle_keys": 600, "key_sets": 3}
    a = traffic.bulk_key_sets(mix, np.random.default_rng(1))
    b = traffic.bulk_key_sets(mix, np.random.default_rng(2**33))
    assert len(a) == len(b) == 3
    assert len(np.unique(np.concatenate(a))) == 1800
    for x, y in zip(a, b):
        assert sorted(x.tolist()) == sorted(y.tolist())
        assert not np.array_equal(x, y)


@pytest.mark.parametrize("geo", [dict(dmax=17, pool_size=131071),
                                 dict(dmax=20, pool_size=1 << 20)])
def test_needed_work_is_the_same_under_both_plans(geo, monkeypatch):
    from repro.core.spec import TableSpec
    from repro.kernels.plan import KernelPlan
    from repro.kernels.tuning import TileConfig

    counts = set()
    for fused in (True, False):
        spec = TableSpec(**geo, bucket_size=8, n_lanes=16)
        object.__setattr__(spec, "_plan", KernelPlan(
            backend="pallas", interpret=False, fused_lookup=fused,
            fused_apply=fused, lookup_tiles=TileConfig(),
            apply_tiles=TileConfig()))
        assert spec.plan().fused_apply is fused
        counts.add((work.lookup_bytes(spec.bucket_size),
                    work.write_bytes(spec.bucket_size)))
    assert counts == {(77, 145)}


def test_roofline_share_of_known_numbers():
    # 1e6 lookups of 77 bytes in 1 ms at 819 GB/s: 77e6 / 819e9 s / 1e-3 s
    assert work.roofline_pct(10**6, 77, 1e-3, 819e9) == pytest.approx(
        100 * 77e6 / 819e9 / 1e-3)
    assert work.roofline_pct(0, 77, 1e-3, 819e9) is None


def test_peaks_are_keyed_by_device_kind():
    from harness.peaks import UnknownDevice, peaks_for

    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(UnknownDevice):
        peaks_for("TPU v9 imaginary")


def test_bulk_rate_counts_every_call_over_its_time():
    from harness.loops import BulkRecords, Call
    from harness.runner import bulk_numbers

    status = np.ones(512, np.int32)
    calls = [Call(10.0 + 0.8 * i, 10.8 + 0.8 * i, 1, 0, 512 * i, status)
             for i in range(4)]          # the last call ends after the close
    rec = BulkRecords(t_open=10.0, t_close=13.0, calls=calls, cycles_done=0)
    got = bulk_numbers(rec, 3.0)
    assert got["ops_per_s"] == pytest.approx(4 * 512 / 3.2)
    assert got["p50_ms"] == pytest.approx(800.0)
