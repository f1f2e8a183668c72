"""Pallas kernel validation: shape/dtype sweeps + hypothesis vs ref oracles.

Kernels run in interpret mode on CPU (the kernel body executes in Python),
asserting exact equality with the pure-jnp oracles in kernels/ref.py, and
end-to-end equivalence of the kernel fast path with the reference table
transaction.
"""
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # hypothesis or fallback shim

from repro.core import table as T
from repro.core.invariants import to_dict
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.apply import grouped_apply
from repro.kernels.lookup import probe

jax.config.update("jax_platform_name", "cpu")

EMPTY = np.int32(-2147483648)


def random_pool(rng, P, B, fill=0.5):
    """Random pool with unique keys per row, ~fill occupancy."""
    keys = np.full((P, B), EMPTY, np.int32)
    vals = np.zeros((P, B), np.int32)
    for p in range(P):
        k = rng.choice(np.arange(1, 10_000), size=B, replace=False)
        occ = rng.random(B) < fill
        keys[p, occ] = k[occ]
        vals[p, occ] = rng.integers(0, 1 << 20, size=occ.sum())
    return jnp.asarray(keys), jnp.asarray(vals)


# ---------------------------------------------------------------------------
# probe kernel


@pytest.mark.parametrize("P,B,N", [
    (8, 4, 16),
    (64, 8, 100),      # N not a multiple of 8
    (130, 8, 257),     # P not a multiple of the 128-bucket tile
    (32, 16, 64),
    (512, 8, 512),     # four tiles
])
def test_probe_matches_ref_sweep(P, B, N):
    rng = np.random.default_rng(P * 1000 + N)
    pk, pv = random_pool(rng, P, B)
    bid = jnp.asarray(rng.integers(0, P, size=N), jnp.int32)
    # half the queries are present keys, half are misses
    present = np.asarray(pk)[np.asarray(bid), rng.integers(0, B, size=N)]
    miss = rng.integers(20_000, 30_000, size=N).astype(np.int32)
    take = rng.random(N) < 0.5
    q = jnp.asarray(np.where(take & (present != EMPTY), present, miss))
    f_ref, v_ref = kref.probe_ref(bid, q, pk, pv)
    f_k, v_k = probe(bid, q, pk, pv, interpret=True)
    np.testing.assert_array_equal(np.asarray(f_k), np.asarray(f_ref))
    np.testing.assert_array_equal(np.asarray(v_k), np.asarray(v_ref))


def test_probe_extreme_key_values():
    """int32 extremes must survive the compare and the value path exactly."""
    pk = jnp.asarray([[2147483647, -2147483647, 1, EMPTY]], jnp.int32)
    pv = jnp.asarray([[-2147483648 + 1, 2147483647, -7, 0]], jnp.int32)
    bid = jnp.zeros(4, jnp.int32)
    q = jnp.asarray([2147483647, -2147483647, 1, 12345], jnp.int32)
    f, v = probe(bid, q, pk, pv, interpret=True)
    np.testing.assert_array_equal(np.asarray(f), [True, True, True, False])
    np.testing.assert_array_equal(np.asarray(v)[:3],
                                  [-2147483647, 2147483647, -7])


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_probe_hypothesis(data):
    P = data.draw(st.sampled_from([4, 16, 64, 200]))
    B = data.draw(st.sampled_from([4, 8]))
    N = data.draw(st.integers(1, 80))
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    pk, pv = random_pool(rng, P, B, fill=data.draw(st.floats(0.0, 1.0)))
    bid = jnp.asarray(rng.integers(0, P, size=N), jnp.int32)
    q = jnp.asarray(rng.integers(-(1 << 31) + 1, 1 << 31, size=N), jnp.int32)
    f_ref, v_ref = kref.probe_ref(bid, q, pk, pv)
    f_k, v_k = probe(bid, q, pk, pv, interpret=True)
    np.testing.assert_array_equal(np.asarray(f_k), np.asarray(f_ref))
    np.testing.assert_array_equal(np.asarray(v_k), np.asarray(v_ref))


def _whole_pools(rng, P, B):
    """The table's [P+1, B] pools: P random buckets and the empty trash
    row, which the directory never routes to."""
    pk, pv = random_pool(rng, P, B)
    pk = jnp.concatenate([pk, jnp.full((1, B), EMPTY, jnp.int32)])
    pv = jnp.concatenate([pv, jnp.zeros((1, B), jnp.int32)])
    return pk, pv


def _present(rng, pk, bid):
    """A key stored in each routed bucket (a miss where the slot drawn is
    empty)."""
    B = pk.shape[1]
    k = np.asarray(pk)[bid, rng.integers(0, B, size=bid.size)]
    return np.where(k != EMPTY, k, 20_000 + np.arange(bid.size)).astype(
        np.int32)


@pytest.mark.parametrize("case", ["one_tile", "last_tile", "duplicates",
                                  "empty_key", "padding", "odd_n",
                                  "two_launches"])
def test_probe_whole_pools(case):
    """The routed probe over whole [P+1, B] pools (301 buckets: three tiles
    of 128, the last one partial and holding the trash row)."""
    P, B = 300, 8
    rng = np.random.default_rng(len(case))
    pk, pv = _whole_pools(rng, P, B)
    if case == "one_tile":            # 200 queries on one tile, fetched once
        bid = rng.integers(128, 256, size=200)
    elif case == "last_tile":         # up to the row next to the trash row
        bid = np.concatenate([[P - 1, P - 1, 256], rng.integers(256, P, 61)])
    elif case == "duplicates":        # 16 keys, each asked 8 times
        bid = np.repeat(rng.integers(0, P, size=16), 8)
    elif case == "two_launches":      # longer than one launch's SMEM budget
        bid = rng.integers(0, P, size=8192 + 100)
    else:
        bid = rng.integers(0, P, size=37 if case == "odd_n" else 64)
    q = _present(rng, pk, bid)
    if case == "duplicates":
        perm = rng.permutation(bid.size)
        bid, q = bid[perm], q[perm]
    elif case == "empty_key":         # the empty-slot marker never matches
        q[::2] = EMPTY
    elif case == "padding":           # a batch padded with key-0 lanes
        pad = 512 - bid.size
        b0 = 77
        pk = pk.at[b0, 3].set(0)
        pv = pv.at[b0, 3].set(4242)
        bid = np.concatenate([bid, np.full(pad, b0)])
        q = np.concatenate([q, np.zeros(pad, np.int32)])
    bid = jnp.asarray(bid, jnp.int32)
    q = jnp.asarray(q, jnp.int32)
    f_ref, v_ref = kref.probe_ref(bid, q, pk, pv)
    f_ref = f_ref & (q != EMPTY)
    v_ref = jnp.where(f_ref, v_ref, -1)
    f_k, v_k = probe(bid, q, pk, pv, interpret=True)
    np.testing.assert_array_equal(np.asarray(f_k), np.asarray(f_ref))
    np.testing.assert_array_equal(np.asarray(v_k), np.asarray(v_ref))
    assert np.asarray(f_k).any()
    if case == "empty_key":
        assert not np.asarray(f_k)[::2].any()
    if case == "padding":
        assert np.asarray(f_k)[-1] and np.asarray(v_k)[-1] == 4242


# ---------------------------------------------------------------------------
# fused hash → route → probe kernel


@pytest.mark.parametrize("dmax,P,B,N,hash_name,shift", [
    (4, 16, 4, 33, "fmix32", 0),
    (6, 64, 8, 100, "fmix32", 0),
    (6, 64, 8, 64, "identity", 0),
    (5, 32, 4, 50, "fmix32", 2),     # sharded-table route (hash_shift)
])
def test_fused_probe_matches_unfused_route(dmax, P, B, N, hash_name, shift):
    from repro.core.hashing import HASH_FNS, dir_index
    from repro.kernels.lookup import fused_probe

    rng = np.random.default_rng(dmax * 100 + N)
    pk, pv = random_pool(rng, P, B)
    # random (valid) directory over the pool
    directory = jnp.asarray(rng.integers(0, P, size=1 << dmax), jnp.int32)
    q = jnp.asarray(rng.integers(-(1 << 31) + 1, 1 << 31, size=N), jnp.int32)
    h = HASH_FNS[hash_name](q) << shift if shift else HASH_FNS[hash_name](q)
    bid = directory[dir_index(h, dmax)]
    f_ref, v_ref = kref.probe_ref(bid, q, pk, pv)
    f_k, v_k = fused_probe(directory, q, pk, pv, dmax=dmax,
                           hash_name=hash_name, hash_shift=shift,
                           tq=16, pc=16, dc=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(f_k), np.asarray(f_ref))
    np.testing.assert_array_equal(np.asarray(v_k), np.asarray(v_ref))


def test_tile_tuning_env_and_registry(monkeypatch):
    import pytest

    from repro.kernels import tuning

    t = tuning.pick_tiles(1000, 300, 64)
    assert t.tq <= 256 and t.pc <= 300 and t.dc <= 64
    key = tuning.tile_key("lookup", dmax=6, pool_size=1000, n_lanes=64)
    tuning.register_tiles(key, tuning.TileConfig(tq=32, pc=64, dc=16),
                          override=True)
    assert tuning.pick_tiles(1000, 1000, 0, key=key).tq == 32
    monkeypatch.setenv("REPRO_TILE_TQ", "8")
    assert tuning.pick_tiles(1000, 1000, 0, key=key).tq == 8  # env wins
    monkeypatch.delenv("REPRO_TILE_TQ")
    # keys outside the plan schema are rejected, not silently accepted
    with pytest.raises(ValueError, match="plan schema"):
        tuning.register_tiles("k1", tuning.TileConfig())
    with pytest.raises(ValueError, match="plan schema"):
        tuning.pick_tiles(64, 64, key="free-form")
    # colliding re-registration (different tiles, same key) raises ...
    with pytest.raises(ValueError, match="collision"):
        tuning.register_tiles(key, tuning.TileConfig(tq=8, pc=8, dc=8))
    # ... but idempotent and explicit-override writes are fine
    tuning.register_tiles(key, tuning.TileConfig(tq=32, pc=64, dc=16))
    tuning.register_tiles(key, tuning.TileConfig(tq=8, pc=8, dc=8),
                          override=True)
    assert tuning.pick_tiles(1000, 1000, 0, key=key).tq == 8


# ---------------------------------------------------------------------------
# combining-apply kernel


def sort_ops(kinds, keys, values, bids, P):
    """Pre-sort by (bucket, lane) as the kernel contract requires."""
    order = np.argsort(np.where(kinds != 0, bids, P + 1), kind="stable")
    return (jnp.asarray(kinds[order]), jnp.asarray(keys[order]),
            jnp.asarray(values[order]), jnp.asarray(bids[order]), order)


@pytest.mark.parametrize("P,B,M,pc", [
    (8, 4, 8, 4),
    (64, 8, 32, 16),
    (100, 8, 16, 64),   # non-divisible P
    (32, 16, 48, 32),
])
def test_apply_matches_ref_sweep(P, B, M, pc):
    rng = np.random.default_rng(P * 31 + M)
    pk, pv = random_pool(rng, P, B, fill=0.6)
    kinds = rng.integers(0, 3, size=M).astype(np.int32)
    bids = rng.integers(0, P, size=M).astype(np.int32)
    # mix of existing keys and fresh keys
    ex = np.asarray(pk)[bids, rng.integers(0, B, size=M)]
    fresh = rng.integers(30_000, 40_000, size=M).astype(np.int32)
    keys = np.where((rng.random(M) < 0.5) & (ex != EMPTY), ex, fresh)
    values = rng.integers(0, 1 << 15, size=M).astype(np.int32)

    ks, keq, vs, bs, order = sort_ops(kinds, keys, values, bids, P)
    pk1, pv1, st1 = kref.apply_ref(ks, keq, vs, bs, pk, pv)
    pk2, pv2, st2 = grouped_apply(ks, keq, vs, bs, pk, pv, pc=pc,
                                  interpret=True)
    np.testing.assert_array_equal(np.asarray(pk2), np.asarray(pk1))
    np.testing.assert_array_equal(np.asarray(pv2), np.asarray(pv1))
    np.testing.assert_array_equal(np.asarray(st2), np.asarray(st1))


def test_apply_full_bucket_reports_st_full():
    B = 4
    pk = jnp.asarray([[1, 2, 3, 4]], jnp.int32)     # full bucket
    pv = jnp.zeros((1, B), jnp.int32)
    kinds = jnp.asarray([1, 2], jnp.int32)          # insert 9 / delete 1
    keys = jnp.asarray([9, 1], jnp.int32)
    vals = jnp.asarray([5, 0], jnp.int32)
    bids = jnp.zeros(2, jnp.int32)
    pk2, pv2, status = grouped_apply(kinds, keys, vals, bids, pk, pv, pc=4,
                                     interpret=True)
    # full test comes first: BOTH ops blocked (not even Delete runs)
    np.testing.assert_array_equal(np.asarray(status), [kref.ST_FULL] * 2)
    np.testing.assert_array_equal(np.asarray(pk2), np.asarray(pk))


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_apply_hypothesis(data):
    P = data.draw(st.sampled_from([4, 16, 64]))
    B = data.draw(st.sampled_from([2, 8]))
    M = data.draw(st.integers(1, 40))
    pc = data.draw(st.sampled_from([4, 16]))
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    pk, pv = random_pool(rng, P, B, fill=data.draw(st.floats(0.0, 1.0)))
    kinds = rng.integers(0, 3, size=M).astype(np.int32)
    bids = rng.integers(0, P, size=M).astype(np.int32)
    keys = rng.integers(1, 50, size=M).astype(np.int32)
    values = rng.integers(0, 99, size=M).astype(np.int32)
    ks, keq, vs, bs, _ = sort_ops(kinds, keys, values, bids, P)
    pk1, pv1, st1 = kref.apply_ref(ks, keq, vs, bs, pk, pv)
    pk2, pv2, st2 = grouped_apply(ks, keq, vs, bs, pk, pv, pc=pc,
                                  interpret=True)
    np.testing.assert_array_equal(np.asarray(pk2), np.asarray(pk1))
    np.testing.assert_array_equal(np.asarray(pv2), np.asarray(pv1))
    np.testing.assert_array_equal(np.asarray(st2), np.asarray(st1))


# ---------------------------------------------------------------------------
# fully-fused apply kernel: route + probe + combine + scatter, one launch


def random_fused_case(rng, dmax, P, B, n, fill=0.6, ins_frac=None,
                      frozen_frac=0.25, key_lo=1, key_hi=64):
    """Directory, frozen mask, [P+1, B] pools and one op batch.

    The directory is an arbitrary map entry -> live row (the kernel only
    follows it); keys are drawn from a small range so intra-batch
    duplicates and genuine hits are common; ~frozen_frac of the live rows
    are frozen; fill near 1.0 yields full buckets (ST_FULL coverage)."""
    directory = jnp.asarray(rng.integers(0, P, size=1 << dmax), jnp.int32)
    frozen = np.zeros(P + 1, bool)
    frozen[:P] = rng.random(P) < frozen_frac
    pk, pv = random_pool(rng, P + 1, B, fill)
    if ins_frac is None:
        kinds = rng.integers(0, 3, size=n).astype(np.int32)
    else:
        kinds = np.where(rng.random(n) < ins_frac, 1, 2).astype(np.int32)
    keys = rng.integers(key_lo, key_hi, size=n).astype(np.int32)
    values = rng.integers(0, 1 << 15, size=n).astype(np.int32)
    return (directory, jnp.asarray(frozen), jnp.asarray(kinds),
            jnp.asarray(keys), jnp.asarray(values), pk, pv)


def assert_fused_matches_ref(directory, frozen, kinds, keys, values, pk, pv,
                             *, dmax, rounds=2, rng=None):
    """Run `rounds` sequential batches through kernel and oracle, carrying
    the pools forward, so later rounds hit keys earlier rounds inserted.
    Live rows, status and bucket ids must match exactly; the trash row
    (row P) is unspecified by contract and excluded."""
    from repro.kernels.apply import fused_apply

    P = pk.shape[0] - 1
    pk_k, pv_k = pk, pv
    pk_r, pv_r = pk, pv
    for r in range(rounds):
        if r and rng is not None:   # fresh ops over the same key range
            kinds = jnp.asarray(
                rng.integers(0, 3, size=kinds.shape[0]).astype(np.int32))
        pk_k, pv_k, st_k, bid_k = fused_apply(
            directory, frozen, kinds, keys, values, pk_k, pv_k,
            dmax=dmax, interpret=True)
        pk_r, pv_r, st_r, bid_r = kref.fused_apply_ref(
            directory, frozen, kinds, keys, values, pk_r, pv_r, dmax=dmax)
        np.testing.assert_array_equal(np.asarray(bid_k), np.asarray(bid_r),
                                      err_msg=f"round {r}: bucket ids")
        np.testing.assert_array_equal(np.asarray(st_k), np.asarray(st_r),
                                      err_msg=f"round {r}: status")
        np.testing.assert_array_equal(np.asarray(pk_k)[:P],
                                      np.asarray(pk_r)[:P],
                                      err_msg=f"round {r}: pool keys")
        np.testing.assert_array_equal(np.asarray(pv_k)[:P],
                                      np.asarray(pv_r)[:P],
                                      err_msg=f"round {r}: pool vals")
    return st_r


@pytest.mark.parametrize("dmax,P,B,n,fill", [
    (6, 16, 4, 8, 0.5),
    (6, 64, 8, 32, 0.6),
    (8, 100, 8, 64, 0.5),    # non-power-of-two P
    (6, 32, 16, 16, 0.95),   # near-full pools → ST_FULL coverage
    (4, 8, 4, 8, 1.0),       # everything full
])
def test_fused_apply_matches_ref_sweep(dmax, P, B, n, fill):
    rng = np.random.default_rng(dmax * 1000 + P + n)
    case = random_fused_case(rng, dmax, P, B, n, fill=fill)
    status = assert_fused_matches_ref(*case, dmax=dmax, rounds=3, rng=rng)
    # the sweep must exercise real outcomes, not vacuously pass
    assert np.asarray(status).size == n


@pytest.mark.parametrize("ins_frac", [0.0, 0.5, 1.0])
def test_fused_apply_insert_mixes(ins_frac):
    """0/50/100% insert mixes with heavy intra-batch duplicate keys: the
    kernel's duplicate-bucket linkage must reproduce the oracle's strict
    lane-order linearization (rule B makes that the only order that
    matters)."""
    rng = np.random.default_rng(int(ins_frac * 7) + 11)
    case = random_fused_case(rng, 6, 32, 4, 32, fill=0.5, ins_frac=ins_frac,
                             key_lo=1, key_hi=12)   # ~3 lanes per key
    assert_fused_matches_ref(*case, dmax=6, rounds=2)


def test_fused_apply_status_space_covered():
    """One adversarial geometry must surface every status code — frozen
    hits, full-bucket blocks, hits, misses and idle lanes all in one
    batch (guards the sweep against silently losing coverage)."""
    rng = np.random.default_rng(5)
    counts = {kref.ST_IDLE: 0, kref.ST_FALSE: 0, kref.ST_TRUE: 0,
              kref.ST_FROZEN: 0, kref.ST_FULL: 0}
    for trial in range(6):
        # alternate sparse/packed pools: packed trials produce ST_FULL,
        # sparse ones leave room for TRUE/FALSE insert+delete outcomes
        case = random_fused_case(rng, 5, 16, 4, 64,
                                 fill=0.45 if trial % 2 else 0.95,
                                 frozen_frac=0.4, key_hi=32)
        status = np.asarray(assert_fused_matches_ref(*case, dmax=5,
                                                     rounds=2, rng=rng))
        for code in counts:
            counts[code] += int((status == code).sum())
    missing = [code for code, c in counts.items() if c == 0]
    assert not missing, f"status codes never produced: {missing} ({counts})"


@settings(max_examples=20, deadline=None)
@given(st.data())
def test_fused_apply_hypothesis(data):
    dmax = data.draw(st.sampled_from([4, 6]))
    P = data.draw(st.sampled_from([8, 16, 64]))
    B = data.draw(st.sampled_from([2, 8]))
    n = data.draw(st.sampled_from([8, 24]))
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    case = random_fused_case(rng, dmax, P, B, n,
                             fill=data.draw(st.floats(0.0, 1.0)),
                             frozen_frac=data.draw(st.floats(0.0, 0.5)))
    assert_fused_matches_ref(*case, dmax=dmax, rounds=2, rng=rng)


# ---------------------------------------------------------------------------
# end-to-end: kernel fast path == reference transaction


@lru_cache(maxsize=None)
def table_fns(cfg):
    return {
        "apply_ref": jax.jit(partial(T.apply_batch, cfg)),
        "apply_kernel": partial(kops.apply_batch_kernel, cfg, interpret=True),
        "apply_fused": partial(kops.apply_batch_fused, cfg, interpret=True),
        "lookup_kernel": partial(kops.kernel_lookup, cfg, interpret=True),
    }


@pytest.mark.parametrize("kernel", ["apply_kernel", "apply_fused"])
def test_kernel_fastpath_equals_reference_transaction(kernel):
    cfg = T.TableConfig(dmax=6, bucket_size=4, pool_size=64, n_lanes=8)
    fns = table_fns(cfg)
    rng = np.random.default_rng(7)
    s_ref = T.init_table(cfg)
    s_ker = T.init_table(cfg)
    for step in range(30):
        kinds = rng.integers(0, 3, size=8).astype(np.int32)
        keys = rng.integers(1, 200, size=8).astype(np.int32)
        vals = rng.integers(0, 99, size=8).astype(np.int32)
        ops = T.make_ops(cfg, s_ref, kinds, keys, vals)
        s_ref, r_ref = fns["apply_ref"](s_ref, ops)
        s_ker, r_ker = fns[kernel](s_ker, ops)
        np.testing.assert_array_equal(np.asarray(r_ker.status),
                                      np.asarray(r_ref.status),
                                      err_msg=f"step {step}")
        assert to_dict(cfg, s_ker) == to_dict(cfg, s_ref), f"step {step}"
        # kernel path maintains the incremental occupancy counts exactly
        occ = (np.asarray(s_ker.keys) != EMPTY).sum(-1)
        live = np.asarray(s_ker.live)
        assert (np.asarray(s_ker.counts)[live] == occ[live]).all(), \
            f"step {step}: kernel counts out of sync"
    # kernel lookups agree with reference lookups on the final state
    q = jnp.asarray(rng.integers(1, 200, size=64), jnp.int32)
    f1, v1 = T.lookup(cfg, s_ref, q)
    f2, v2 = fns["lookup_kernel"](s_ker, q)
    np.testing.assert_array_equal(np.asarray(f2), np.asarray(f1))
    np.testing.assert_array_equal(np.asarray(v2), np.asarray(v1))


@pytest.mark.parametrize("kernel", ["apply_kernel", "apply_fused"])
def test_kernel_path_blocks_frozen_buckets(kernel):
    """The grouped kernel combiner is freeze-oblivious (its wrapper masks
    frozen destinations); the fused kernel checks the frozen vector
    in-kernel. Either way frozen-bucket ops must complete with FROZEN and
    leave the bucket untouched (paper §4.5), exactly like the reference
    transaction."""
    cfg = T.TableConfig(hash_name="identity", bucket_size=4, dmax=6,
                        pool_size=64, n_lanes=8)
    fns = table_fns(cfg)
    s = T.init_table(cfg)
    ks = [np.int32(np.uint32(v)) for v in
          (0x01 << 24 | 1, 0x11 << 24, 0x21 << 24, 0x90 << 24, 0xC0 << 24)]
    for kind, k in [(T.INS, k) for k in ks] + \
            [(T.DEL, ks[1]), (T.DEL, ks[2])]:  # shrink so buddies can merge
        kinds = np.zeros(8, np.int32)
        kinds[0] = kind
        keys = np.zeros(8, np.int32)
        keys[0] = k
        ops = T.make_ops(cfg, s, kinds, keys, keys)
        s, _ = fns["apply_ref"](s, ops)
    depth = int(s.depth)
    assert depth >= 1
    s, ok = T.freeze_buddies(cfg, s, 0, depth - 1)
    assert bool(ok)
    # an insert routed into the frozen bucket, via the kernel path
    kinds = np.zeros(8, np.int32)
    kinds[0] = T.INS
    keys = np.zeros(8, np.int32)
    keys[0] = np.int32(np.uint32(0x02 << 24))
    ops = T.make_ops(cfg, s, kinds, keys, keys)
    s_ker, r_ker = fns[kernel](jax.tree.map(jnp.copy, s), ops)
    s_ref, r_ref = fns["apply_ref"](s, ops)
    assert int(r_ker.status[0]) == int(r_ref.status[0]) == T.FROZEN
    assert to_dict(cfg, s_ker) == to_dict(cfg, s_ref)
    np.testing.assert_array_equal(np.asarray(s_ker.applied_seq),
                                  np.asarray(s_ref.applied_seq))


def test_fused_overflow_batch_triggers_split_fallback():
    """Insert batches that overflow tiny buckets: the fused kernel reports
    ST_FULL, the wrapper's slow path splits/doubles, and the retried state
    stays bit-identical with the reference transaction throughout."""
    cfg = T.TableConfig(dmax=6, bucket_size=2, pool_size=32, n_lanes=8)
    fns = table_fns(cfg)
    rng = np.random.default_rng(13)
    s_ref = T.init_table(cfg)
    s_ker = T.init_table(cfg)
    for step in range(6):
        keys = rng.choice(np.arange(1, 500), size=8, replace=False)
        keys = keys.astype(np.int32)
        kinds = np.full(8, T.INS, np.int32)
        ops = T.make_ops(cfg, s_ref, kinds, keys, keys)
        s_ref, r_ref = fns["apply_ref"](s_ref, ops)
        s_ker, r_ker = fns["apply_fused"](s_ker, ops)
        np.testing.assert_array_equal(np.asarray(r_ker.status),
                                      np.asarray(r_ref.status),
                                      err_msg=f"step {step}")
        assert to_dict(cfg, s_ker) == to_dict(cfg, s_ref), f"step {step}"
    # 48 distinct keys into 2-wide buckets: splits definitely happened
    assert int(s_ker.depth) >= 1
    np.testing.assert_array_equal(np.asarray(s_ker.depth),
                                  np.asarray(s_ref.depth))


# ---------------------------------------------------------------------------
# facade: fused/interpret vs XLA single-pass vs wave fallback


@pytest.mark.parametrize("ins_frac", [0.0, 0.5, 1.0])
def test_facade_backend_parity_insert_mixes(ins_frac):
    """The same op stream through three resolved plans — XLA single-pass,
    the fused Pallas kernel (interpret), and the wave-loop fallback
    (use_fast_path=False) — must produce identical statuses and identical
    logical content at every step."""
    from repro.core.spec import TableSpec
    from repro.table_api import Table

    base = dict(dmax=6, bucket_size=4, pool_size=64, n_lanes=8)
    specs = {
        "xla": TableSpec(**base, backend="xla"),
        "fused": TableSpec(**base, backend="interpret"),
        "wave": TableSpec(**base, backend="xla", use_fast_path=False),
    }
    assert specs["fused"].plan().fused_apply   # interpret default = fused
    assert specs["xla"].plan().backend == "xla"
    tables = {k: Table.create(s) for k, s in specs.items()}
    seed_keys = np.arange(1, 20, dtype=np.int32)   # deletes have targets
    for name in tables:
        tables[name], _ = tables[name].insert(seed_keys, seed_keys)
    rng = np.random.default_rng(int(ins_frac * 10) + 3)
    for step in range(5):
        m = 12   # not a lane multiple → exercises the NOP-padding path
        kinds = np.where(rng.random(m) < ins_frac, T.INS, T.DEL)
        kinds = kinds.astype(np.int32)
        keys = rng.integers(1, 40, size=m).astype(np.int32)  # heavy dups
        vals = rng.integers(0, 99, size=m).astype(np.int32)
        res = {}
        for name in tables:
            tables[name], res[name] = tables[name].apply(kinds, keys, vals)
        st_x = np.asarray(res["xla"].status)
        np.testing.assert_array_equal(np.asarray(res["fused"].status), st_x,
                                      err_msg=f"step {step}: fused vs xla")
        np.testing.assert_array_equal(np.asarray(res["wave"].status), st_x,
                                      err_msg=f"step {step}: wave vs xla")
        d_x = to_dict(tables["xla"].config, tables["xla"].state)
        assert to_dict(tables["fused"].config,
                       tables["fused"].state) == d_x, f"step {step}"
        assert to_dict(tables["wave"].config,
                       tables["wave"].state) == d_x, f"step {step}"
    # the mixes must really have exercised the kernel: lookups agree too
    q = np.arange(1, 40, dtype=np.int32)
    f_x, v_x = tables["xla"].lookup(q)
    f_f, v_f = tables["fused"].lookup(q)
    np.testing.assert_array_equal(np.asarray(f_f), np.asarray(f_x))
    np.testing.assert_array_equal(np.asarray(v_f), np.asarray(v_x))
