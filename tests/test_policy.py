"""The elastic ResizePolicy: shrink-path coverage + hysteresis properties.

Layers:
  1. drain → merge: a filled-then-drained table must shrink (policy merge
     counter advances, logical depth decreases) while content stays exactly
     the reference oracle's;
  2. hysteresis at the watermark boundary (identity hash, crafted keys):
     oscillation strictly inside the (lo, hi) band performs ZERO resize
     actions; oscillation touching the split watermark performs exactly ONE
     split and then stays quiet — actions are bounded by the band crossing
     count, never by the number of oscillation rounds;
  3. FROZEN retries during an in-flight merge: ops targeting frozen buddies
     complete with status FROZEN and leave no trace; once the merge
     finishes, the retried batch produces exactly the oracle's statuses and
     content (exact parity through the freeze window);
  4. randomized property (hypothesis or shim): arbitrary op streams through
     a policy-active facade keep every structural invariant and full
     content/status parity with the oracle;
  5. policy observability under sharded placement (subprocess, 8 forced
     host devices): `policy_stats()` sums splits/merges over the stacked
     shard states, `resize_pressure` works elementwise on them, and
     `Table.depth()` reports the max over shards.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # hypothesis or fallback shim

from repro.core import table as T
from repro.core.invariants import check_invariants, to_dict
from repro.core.policy import ResizePolicy
from repro.core.reference import SeqExtHash
from repro.table_api import Table, TableSpec

jax.config.update("jax_platform_name", "cpu")


def _stats(t):
    return tuple(int(v) for v in np.asarray(t.state.policy_counts))


def _nop_round(t, rounds=1):
    """Drive the policy with all-NOP transactions (read-only traffic)."""
    nop = np.zeros(t.spec.n_lanes, np.int32)
    for _ in range(rounds):
        t, _ = t.apply(nop, nop)
    return t


# ---------------------------------------------------------------------------
# 1. the shrink path fires and is content-transparent


def test_drain_triggers_merges_and_depth_shrinks():
    pol = ResizePolicy(split_watermark=0.75, merge_watermark=0.375,
                       max_splits=8, max_merges=4)
    spec = TableSpec(dmax=9, bucket_size=8, pool_size=512, n_lanes=16,
                     backend="xla", resize_policy=pol)
    t = Table.create(spec)
    ref = SeqExtHash(9, 8)
    rng = np.random.default_rng(42)
    keys = rng.choice(np.arange(1, 1 << 20), size=260,
                      replace=False).astype(np.int32)

    t, res = t.insert(keys, keys * 5)
    for k in keys:
        ref.insert(int(k), int(k) * 5)
    assert (np.asarray(res.status) == 1).all()
    splits0, merges0 = _stats(t)
    assert splits0 > 0 and merges0 == 0
    depth_hi = int(t.depth())
    assert depth_hi > 0
    check_invariants(t.config, t.state)

    # drain 95% and let read-only maintenance traffic keep the policy fed
    t, _ = t.delete(keys[:247])
    for k in keys[:247]:
        ref.delete(int(k))
    t = _nop_round(t, rounds=30)

    splits1, merges1 = _stats(t)
    assert merges1 > 0, "drain must drive the §4.5 merge path"
    assert int(t.depth()) < depth_hi, "logical directory depth must shrink"
    assert not bool(t.state.error)
    check_invariants(t.config, t.state)
    assert to_dict(t.config, t.state) == ref.as_dict()


def test_policy_validation():
    with pytest.raises(AssertionError):
        ResizePolicy(split_watermark=0.5, merge_watermark=0.5)
    with pytest.raises(AssertionError):
        ResizePolicy(split_watermark=0.2, merge_watermark=0.6)
    # B-dependent degeneracy is caught at spec construction: a split
    # threshold of ceil(0.4 * 2) = 1 item would split every non-empty bucket
    with pytest.raises(AssertionError):
        TableSpec(bucket_size=2, resize_policy=ResizePolicy(
            split_watermark=0.4, merge_watermark=0.1))


# ---------------------------------------------------------------------------
# 2. hysteresis: crafted identity-hash keys at the watermark boundary


def _key(prefix: int, depth: int, j: int) -> int:
    """An i32 key whose identity-hash top `depth` bits equal `prefix`
    (wrapped to signed — prefixes with the MSB set come out negative)."""
    assert 0 <= prefix < (1 << depth)
    u = ((prefix << (32 - depth)) | (j + 1)) & 0xFFFFFFFF
    k = int(np.int32(np.uint32(u)))
    assert k != -2147483648, "EMPTY_KEY sentinel is not a legal key"
    return k


def test_hysteresis_no_thrash_at_watermark_boundary():
    # B=8 -> split at 6, merge at combined <= 3: band (3, 6)
    pol = ResizePolicy(split_watermark=0.75, merge_watermark=0.375,
                       max_splits=4, max_merges=4, min_depth=2)
    spec = TableSpec(dmax=6, bucket_size=8, pool_size=64, n_lanes=8,
                     hash_name="identity", initial_depth=2, backend="xla",
                     resize_policy=pol)
    t = Table.create(spec)

    # 5 keys in the depth-2 prefix-1 region, mixed on the next hash bit
    # (so an eventual split distributes 3 / 2)
    region = [_key(0b010, 3, j) for j in range(3)] \
        + [_key(0b011, 3, j) for j in range(2)]
    t, res = t.insert(np.asarray(region, np.int32))
    assert (np.asarray(res.status) == 1).all()
    assert _stats(t) == (0, 0), "5 < hi: no proactive split"
    assert int(t.depth()) == 2

    # oscillate strictly INSIDE the band: occupancy 4 <-> 5, combined
    # child-view 4 <-> 5 > lo — the policy must do NOTHING, forever
    probe = np.asarray([region[0]], np.int32)
    for _ in range(25):
        t, _ = t.delete(probe)
        t, _ = t.insert(probe)
    assert _stats(t) == (0, 0), "in-band oscillation must not thrash"
    assert int(t.depth()) == 2
    check_invariants(t.config, t.state)

    # cross the split watermark once: occupancy 6 == hi -> exactly one
    # proactive split; the children (3 + 3) sit ABOVE the merge watermark,
    # so oscillating the same key (5 <-> 6 combined) stays action-free
    sixth = np.asarray([_key(0b011, 3, 7)], np.int32)
    t, _ = t.insert(sixth)
    assert _stats(t) == (1, 0), "hi crossing must split exactly once"
    assert int(t.depth()) == 3
    for _ in range(20):
        t, _ = t.delete(sixth)
        t, _ = t.insert(sixth)
    assert _stats(t) == (1, 0), (
        "boundary oscillation must be absorbed by the hysteresis band")
    assert int(t.depth()) == 3
    check_invariants(t.config, t.state)

    # cross the merge watermark: drain the region to 3 == lo -> the child
    # pair merges back exactly once (depth returns to 2), and replaying
    # the same read-only traffic stays quiet
    t, res = t.delete(np.asarray(region[:3], np.int32))
    assert (np.asarray(res.status) == 1).all()
    t = _nop_round(t, rounds=5)
    assert _stats(t) == (1, 1), "lo crossing must merge exactly once"
    assert int(t.depth()) == 2
    t = _nop_round(t, rounds=10)
    assert _stats(t) == (1, 1)
    check_invariants(t.config, t.state)


# ---------------------------------------------------------------------------
# 3. FROZEN retries during an in-flight merge


@pytest.mark.parametrize("with_policy", [False, True])
def test_frozen_retry_parity_through_merge_window(with_policy):
    pol = ResizePolicy(split_watermark=0.75, merge_watermark=0.3,
                       max_splits=2, max_merges=1, min_depth=2) \
        if with_policy else None
    spec = TableSpec(dmax=6, bucket_size=4, pool_size=64, n_lanes=8,
                     hash_name="identity", initial_depth=2, backend="xla",
                     resize_policy=pol)
    t = Table.create(spec)
    ref = SeqExtHash(6, 4, initial_depth=2, hash_name="identity")

    # one resident key in each depth-2 child of parent prefix-1@1, plus
    # one in an unrelated region
    k_in0 = _key(0b10, 2, 0)     # prefix 2 @ depth 2  (frozen later)
    k_in1 = _key(0b11, 2, 0)     # prefix 3 @ depth 2  (frozen later)
    k_out = _key(0b01, 2, 0)     # prefix 1 @ depth 2  (never frozen)
    seed = np.asarray([k_in0, k_in1, k_out], np.int32)
    seed_vals = np.asarray([11, 22, 33], np.int32)
    t, _ = t.insert(seed, seed_vals)
    for k, v in zip(seed, seed_vals):
        ref.insert(int(k), int(v))

    # an in-flight merge elsewhere has frozen buddies (2,3)@depth2
    st, ok = T.freeze_buddies(t.config, t.state, 1, 1)
    assert bool(ok)
    t = t._replace(state=st)

    # mixed batch: two ops into the freeze window, one outside
    kinds = np.asarray([T.INS, T.DEL, T.INS], np.int32)
    keys = np.asarray([_key(0b10, 2, 5), k_in1, k_out], np.int32)
    vals = np.asarray([111, 0, 222], np.int32)
    t, res = t.apply(kinds, keys, vals)
    st_list = np.asarray(res.status).tolist()
    assert st_list[:2] == [T.FROZEN, T.FROZEN], st_list
    assert st_list[2] == T.FALSE            # upsert of a present key
    ref.insert(int(k_out), 222)             # only the outside op ran
    # the freeze window left no trace: frozen keys unchanged, new key absent
    found, v = t.lookup(np.asarray([k_in0, k_in1, keys[0]], np.int32))
    assert np.asarray(found).tolist() == [True, True, False]
    assert np.asarray(v).tolist()[:2] == [11, 22]

    # the merging thread finishes: unfreeze, then complete the §4.5 merge
    t = t._replace(state=t.state._replace(
        frozen=jnp.zeros_like(t.state.frozen)))
    t, ok = t.merge(1, 1)
    assert bool(ok)
    assert ref.merge(1, 1)
    check_invariants(t.config, t.state)

    # the caller retries the rejected ops: exact oracle parity
    t, res = t.apply(kinds[:2], keys[:2], vals[:2])
    want = [ref.insert(int(keys[0]), 111), ref.delete(int(keys[1]))]
    assert np.asarray(res.status).tolist() == want
    assert to_dict(t.config, t.state) == ref.as_dict()
    check_invariants(t.config, t.state)
    assert not bool(t.state.error)


# ---------------------------------------------------------------------------
# 4. randomized property: invariants + parity under a policy-active facade


@settings(max_examples=10, deadline=None)
@given(st.data())
def test_policy_random_ops_keep_invariants_and_parity(data):
    pol = ResizePolicy(split_watermark=0.75, merge_watermark=0.375,
                       max_splits=4, max_merges=2)
    spec = TableSpec(dmax=7, bucket_size=4, pool_size=256, n_lanes=8,
                     backend="xla", resize_policy=pol)
    t = Table.create(spec)
    ref = SeqExtHash(7, 4)
    universe = list(range(1, 400))
    n_rounds = data.draw(st.integers(4, 8), label="rounds")
    for _ in range(n_rounds):
        m = data.draw(st.integers(1, 20), label="batch")
        kinds, keys, vals, want = [], [], [], []
        for _ in range(m):
            ins = data.draw(st.booleans(), label="ins")
            k = data.draw(st.sampled_from(universe), label="key")
            v = data.draw(st.integers(0, 999), label="val")
            kinds.append(T.INS if ins else T.DEL)
            keys.append(k)
            vals.append(v)
        t, res = t.apply(np.asarray(kinds, np.int32),
                         np.asarray(keys, np.int32),
                         np.asarray(vals, np.int32))
        for kk, k, v in zip(kinds, keys, vals):
            want.append(ref.insert(k, v) if kk == T.INS else ref.delete(k))
        assert np.asarray(res.status).tolist() == want
        check_invariants(t.config, t.state)
        assert to_dict(t.config, t.state) == ref.as_dict()


# ---------------------------------------------------------------------------
# 5. policy observability under sharded placement (subprocess: 8 devices)


HERE = os.path.abspath(__file__)


@pytest.mark.subprocess
def test_policy_stats_and_depth_sharded():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(HERE), "..", "src"))
    proc = subprocess.run(
        [sys.executable, HERE, "--run-sharded"],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, (proc.stdout[-3000:], proc.stderr[-3000:])
    assert "sharded policy stats OK" in proc.stdout


def _sharded_main():
    from repro import compat
    mesh = compat.make_mesh((4, 2), ("data", "model"))
    pol = ResizePolicy(split_watermark=0.75, merge_watermark=0.375,
                       max_splits=8, max_merges=4)
    spec = TableSpec(dmax=8, bucket_size=8, pool_size=256, n_lanes=16,
                     placement="sharded", shard_bits=1, resize_policy=pol)
    t = Table.create(spec, mesh)

    # fresh table: zero counters, zero pressure, initial depth
    s0 = t.policy_stats()
    assert int(s0["splits"]) == 0 and int(s0["merges"]) == 0
    assert float(np.asarray(s0["pressure"])) == 0.0
    d0 = int(t.depth())

    # fill enough to drive proactive splits on BOTH shard states; the
    # stats must be the sum over the stacked shard axis and depth the max
    rng = np.random.default_rng(3)
    keys = rng.choice(np.arange(1, 1 << 20), size=400,
                      replace=False).astype(np.int32)
    t, res = t.insert(keys, keys * 3)
    assert (np.asarray(res.status) == 1).all()
    s1 = t.policy_stats()
    per_shard = np.asarray(t.state.policy_counts).reshape(-1, 2)
    assert per_shard.shape[0] == spec.n_shards == 2
    assert (per_shard[:, 0] > 0).all(), "every shard should have split"
    assert int(s1["splits"]) == int(per_shard[:, 0].sum())
    assert int(s1["merges"]) == int(per_shard[:, 1].sum())
    assert int(t.depth()) == int(np.asarray(t.state.depth).max()) > d0

    # pressure: a float in [0, 1] computed elementwise over shard states;
    # draining most of the table pushes merge-eligibility up
    p1 = float(np.asarray(s1["pressure"]))
    assert 0.0 <= p1 <= 1.0
    t, _ = t.delete(keys[:380])
    p2 = float(np.asarray(t.policy_stats()["pressure"]))
    assert 0.0 <= p2 <= 1.0 and p2 > p1, (p1, p2)
    print("sharded policy stats OK")
    return 0


if __name__ == "__main__":
    if "--run-sharded" in sys.argv:
        sys.exit(_sharded_main())
