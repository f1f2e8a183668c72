"""Snapshot / restore / re-shard: durable table images (DESIGN.md §10).

Round-trip parity across placements (local→local here; the cross-mesh
combos run in a subprocess with 8 forced host devices), canonical-form
invariance, frozen-lane normalization, policy counters surviving the trip,
versioned-header behavior, and the clear-rejection paths (shallow dmax,
undersized slabs, schema mismatch). Restored tables must keep resizing:
post-revive fill must raise the split counter, post-revive drain the merge
counter.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.abspath(__file__)


def _mk(spec, keys, vals=None):
    from repro.table_api import Table

    t = Table.create(spec)
    t, res = t.insert(keys, vals if vals is not None else keys * 3)
    assert not bool(np.asarray(res.error).any())
    return t


def test_empty_table_roundtrip(tmp_path):
    from repro.core.invariants import check_invariants
    from repro.table_api import Table, TableSpec

    spec = TableSpec(dmax=8, pool_size=128, n_lanes=16)
    t = Table.create(spec)
    path = t.save(str(tmp_path / "empty.npz"))
    # restore under a DIFFERENT sizing: an empty image fits anything
    t2 = Table.restore(path, TableSpec(dmax=5, pool_size=32, n_lanes=16))
    assert int(t2.size()) == 0
    check_invariants(t2.config, t2.state)
    t2, res = t2.insert(np.arange(1, 9, dtype=np.int32))
    assert (np.asarray(res.status) == 1).all()


def test_roundtrip_content_parity_vs_reference(tmp_path):
    """Random op stream → table and oracle; the restored table must agree
    with the oracle on the full touched universe (content + size)."""
    from repro.core.invariants import check_invariants
    from repro.core.reference import SeqExtHash
    from repro.table_api import Table, TableSpec

    spec = TableSpec(dmax=10, bucket_size=8, pool_size=512, n_lanes=16)
    t = Table.create(spec)
    ref = SeqExtHash(dmax=10, bucket_size=8)
    rng = np.random.default_rng(11)
    universe = np.arange(1, 4000)
    for _ in range(6):
        m = int(rng.integers(20, 60))
        kinds = rng.integers(1, 3, size=m).astype(np.int32)
        keys = rng.choice(universe, size=m, replace=False).astype(np.int32)
        vals = rng.integers(0, 999, size=m).astype(np.int32)
        t, _ = t.apply(kinds, keys, vals)
        for kk, k, v in zip(kinds, keys, vals):
            (ref.insert(int(k), int(v)) if kk == 1 else ref.delete(int(k)))

    path = t.save(str(tmp_path / "t.npz"))
    t2 = Table.restore(path, spec)
    ref_map = ref.as_dict()
    assert int(t2.size()) == len(ref_map)
    q = universe.astype(np.int32)
    found, vals = t2.lookup(q)
    found, vals = np.asarray(found), np.asarray(vals)
    for i, k in enumerate(q):
        want = ref_map.get(int(k))
        got = int(vals[i]) if found[i] else None
        assert got == want, (int(k), got, want)
    check_invariants(t2.config, t2.state)


def test_canonical_image_is_layout_independent():
    """Same content via different op histories → identical image arrays."""
    from repro.core import snapshot as S
    from repro.table_api import Table, TableSpec

    rng = np.random.default_rng(5)
    keys = rng.choice(np.arange(1, 1 << 20), size=300,
                      replace=False).astype(np.int32)
    spec = TableSpec(dmax=9, pool_size=256, n_lanes=16)
    ta = _mk(spec, keys[100:])
    tb = Table.create(spec)
    tb, _ = tb.insert(keys[::-1], keys[::-1] * 3)     # reversed + extra
    tb, _ = tb.delete(keys[:100])                     # then deleted again
    ia, ib = S.extract_image(ta), S.extract_image(tb)
    np.testing.assert_array_equal(ia.keys, ib.keys)
    np.testing.assert_array_equal(ia.values, ib.values)


def test_frozen_lanes_normalize_away(tmp_path):
    """A mid-freeze table images identically to its unfrozen twin and
    restores unfrozen (tombstone/frozen lanes are not content)."""
    import jax.numpy as jnp

    from repro.core import snapshot as S
    from repro.core import table as T
    from repro.table_api import Table, TableSpec

    spec = TableSpec(dmax=6, bucket_size=4, pool_size=64, n_lanes=16,
                     hash_name="identity")
    # identity hash: keys 1..7 in the top 3 bits grow the directory to
    # depth 3 ({4,5} / {6,7} buddies); deleting 4,5,6 leaves the deepest
    # buddy pair light enough to freeze (combined occupancy 1 <= B)
    keys = ((np.arange(8, dtype=np.uint32) << 28)).astype(np.int32)[1:]
    t = _mk(spec, keys)
    t, _ = t.delete(keys[3:6])
    keys = np.concatenate([keys[:3], keys[6:]])
    assert int(t.depth()) >= 2
    # freeze the buddies of the deepest live bucket's would-be parent
    bdepth = np.asarray(t.state.bdepth)
    live = np.asarray(t.state.live)
    bid = int(np.argmax(np.where(live, bdepth, -1)))
    d = int(bdepth[bid])
    parent_prefix = int(np.asarray(t.state.bprefix)[bid]) >> 1
    st, ok = T.freeze_buddies(t.config, t.state, jnp.int32(parent_prefix),
                              jnp.int32(d - 1))
    assert bool(ok), "test setup: buddies should be freezable"
    frozen_t = t._replace(state=st)
    assert bool(np.asarray(frozen_t.state.frozen).any())

    img_frozen = S.extract_image(frozen_t)
    img_plain = S.extract_image(t)
    np.testing.assert_array_equal(img_frozen.keys, img_plain.keys)
    np.testing.assert_array_equal(img_frozen.values, img_plain.values)

    path = frozen_t.save(str(tmp_path / "f.npz"))
    t2 = Table.restore(path, spec)
    assert not bool(np.asarray(t2.state.frozen).any())
    assert int(t2.size()) == len(keys)
    found, _ = t2.lookup(keys)
    assert np.asarray(found).all()


def test_schema_payload_roundtrip(tmp_path):
    import jax.numpy as jnp

    from repro.table_api import Table, TableSpec

    schema = {"page": jnp.int32, "score": (jnp.float32, (2,))}
    spec = TableSpec(dmax=9, pool_size=256, n_lanes=16, value_schema=schema)
    rng = np.random.default_rng(2)
    keys = rng.choice(np.arange(1, 1 << 20), size=200,
                      replace=False).astype(np.int32)
    pay = {"page": (keys * 5).astype(np.int32),
           "score": np.stack([keys / 2, keys / 4], -1).astype(np.float32)}
    t = _mk(spec, keys, pay)
    t, _ = t.delete(keys[:40])
    path = t.save(str(tmp_path / "s.npz"))
    # restore under a different slab capacity: handles are re-allocated,
    # payloads must still match field-for-field
    t2 = Table.restore(path, TableSpec(
        dmax=9, pool_size=256, n_lanes=16, value_schema=schema,
        slab_capacity=512))
    found, pl = t2.lookup(keys)
    found = np.asarray(found)
    assert (~found[:40]).all() and found[40:].all()
    np.testing.assert_array_equal(np.asarray(pl["page"])[40:],
                                  pay["page"][40:])
    np.testing.assert_allclose(np.asarray(pl["score"])[40:],
                               pay["score"][40:])
    from repro.core.invariants import check_invariants
    check_invariants(t2.config, t2.state)
    assert int(t2.size()) == len(keys) - 40


def test_policy_counters_survive_and_elasticity_resumes(tmp_path):
    """Counters round-trip through the image; a revived table keeps
    auto-splitting under fill and auto-merging under drain."""
    from repro.table_api import Table, TableSpec
    from repro.core.policy import ResizePolicy

    spec = TableSpec(dmax=10, bucket_size=8, pool_size=512, n_lanes=16,
                     resize_policy=ResizePolicy(split_watermark=0.75,
                                                merge_watermark=0.375,
                                                max_splits=8, max_merges=4))
    rng = np.random.default_rng(4)
    keys = rng.choice(np.arange(1, 1 << 24), size=900,
                      replace=False).astype(np.int32)
    t = _mk(spec, keys[:600])
    saved_stats = {k: int(v) for k, v in t.policy_stats().items()}
    assert saved_stats["splits"] > 0
    path = t.save(str(tmp_path / "p.npz"))

    t2 = Table.restore(path, spec)
    stats0 = {k: int(v) for k, v in t2.policy_stats().items()}
    assert stats0 == saved_stats
    depth0 = int(t2.depth())

    # post-revive growth: the split counter must move again
    t2, res = t2.insert(keys[600:], keys[600:])
    assert not bool(np.asarray(res.error).any())
    stats1 = {k: int(v) for k, v in t2.policy_stats().items()}
    assert stats1["splits"] > stats0["splits"]
    depth_peak = int(t2.depth())
    assert depth_peak >= depth0

    # post-revive drain (+ read-only maintenance): merges must fire and
    # the directory must come back down
    t2, _ = t2.delete(keys[:850])
    nop = np.zeros(spec.n_lanes, np.int32)
    for _ in range(30):
        t2, _ = t2.apply(nop, nop)
    stats2 = {k: int(v) for k, v in t2.policy_stats().items()}
    assert stats2["merges"] > stats1["merges"]
    assert int(t2.depth()) < depth_peak


def test_restore_rejections_are_clear(tmp_path):
    import jax.numpy as jnp

    from repro.table_api import Table, TableSpec

    # (a) dmax too shallow: 6 identity-hash keys share the top 4 bits
    ti = Table.create(TableSpec(dmax=8, bucket_size=4, pool_size=64,
                                n_lanes=16, hash_name="identity"))
    kk = ((np.uint32(0xA) << 28)
          | (np.arange(6, dtype=np.uint32) << 22)).astype(np.int32)
    ti, res = ti.insert(kk, kk)
    assert not bool(res.error)
    path = ti.save(str(tmp_path / "i.npz"))
    with pytest.raises(ValueError, match="too shallow.*need dmax >= 8"):
        Table.restore(path, TableSpec(dmax=4, bucket_size=4, pool_size=64,
                                      n_lanes=16, hash_name="identity"))

    # (b) slab store too small for the item count
    spec_s = TableSpec(dmax=10, pool_size=256, n_lanes=16,
                       value_schema={"page": jnp.int32})
    ts = _mk(spec_s, np.arange(1, 101, dtype=np.int32),
             {"page": np.arange(1, 101, dtype=np.int32)})
    path = ts.save(str(tmp_path / "s.npz"))
    with pytest.raises(ValueError, match="slab store too small"):
        Table.restore(path, TableSpec(dmax=10, pool_size=256, n_lanes=16,
                                      value_schema={"page": jnp.int32},
                                      slab_capacity=50))

    # (c) schema mismatch (image typed, target raw)
    with pytest.raises(ValueError, match="value schema mismatch"):
        Table.restore(path, TableSpec(dmax=10, pool_size=256, n_lanes=16))


def test_versioned_header(tmp_path):
    """Future-version images fail with a clear error; corrupt magic too."""
    import io

    from repro.core import snapshot as S
    from repro.table_api import Table, TableSpec

    t = _mk(TableSpec(dmax=8, pool_size=128, n_lanes=16),
            np.arange(1, 33, dtype=np.int32))
    img = S.extract_image(t)
    assert img.header["version"] == S.FORMAT_VERSION
    assert img.header["format"] == S.FORMAT_MAGIC

    img.header["version"] = S.FORMAT_VERSION + 1
    path = S.save_image(img, str(tmp_path / "future.npz"))
    with pytest.raises(ValueError, match="newer than this reader"):
        S.load_image(path)

    img.header["version"] = S.FORMAT_VERSION
    img.header["format"] = "something-else"
    path = S.save_image(img, str(tmp_path / "magic.npz"))
    with pytest.raises(ValueError, match="bad magic"):
        S.load_image(path)

    # not an image at all
    bogus = str(tmp_path / "bogus.npz")
    with open(bogus, "wb") as f:
        buf = io.BytesIO()
        np.savez(buf, a=np.arange(3))
        f.write(buf.getvalue())
    with pytest.raises(ValueError, match="missing header"):
        S.load_image(bogus)


# --- cross-placement re-shard: subprocess with 8 host devices --------------


@pytest.mark.subprocess
def test_reshard_across_meshes():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(HERE), "..", "src"))
    proc = subprocess.run(
        [sys.executable, HERE, "--run-reshard"],
        env=env, capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, (proc.stdout[-3000:], proc.stderr[-3000:])
    out = json.loads(proc.stdout.splitlines()[-1])
    assert out["ok"]
    assert out["sizes"] == [out["sizes"][0]] * len(out["sizes"])


def _reshard_main() -> int:
    """local → sharded(8) → sharded(4), raw and schema modes: identical
    sizes, full content parity vs the sequential reference, per-shard
    structural invariants, and the revived table keeps working."""
    import tempfile

    import jax
    import jax.numpy as jnp

    from repro.core import table as T
    from repro.core.invariants import check_invariants
    from repro.core.reference import SeqExtHash
    from repro.table_api import Table, TableSpec

    rng = np.random.default_rng(21)
    keys = rng.choice(np.arange(1, 1 << 24), size=700,
                      replace=False).astype(np.int32)
    from repro import compat
    mesh8 = compat.make_mesh((1, 8), ("data", "model"))
    mesh4 = compat.make_mesh((2, 4), ("data", "model"))
    schema = {"page": jnp.int32}
    sizes = []

    def check(table, spec, deleted, pay=None):
        found, vals = table.lookup(keys)
        found = np.asarray(found)
        assert (~found[:deleted]).all() and found[deleted:].all()
        if pay is None:
            assert (np.asarray(vals)[deleted:] == keys[deleted:] * 3).all()
        else:
            assert (np.asarray(vals["page"])[deleted:]
                    == pay["page"][deleted:]).all()
        lcfg = spec.table_config()
        st_all = jax.tree.map(np.asarray, table.state)
        n_shards = spec.n_shards if spec.placement == "sharded" else 1
        for s in range(n_shards):
            leaf = (lambda x, s=s: x[s]) if spec.placement == "sharded" \
                else (lambda x: x)
            st = T.TableState(*[jnp.asarray(leaf(x)) for x in st_all])
            check_invariants(lcfg, st)
        sizes.append(int(table.size()))

    for mode in ("raw", "schema"):
        vs = schema if mode == "schema" else None
        pay = ({"page": (keys * 3).astype(np.int32)}
               if mode == "schema" else None)
        lo = Table.create(TableSpec(dmax=12, bucket_size=8, pool_size=512,
                                    n_lanes=16, value_schema=vs))
        lo, r = lo.insert(keys, pay if pay is not None else keys * 3)
        assert not bool(np.asarray(r.error).any())
        lo, _ = lo.delete(keys[:100])
        ref = SeqExtHash(dmax=12, bucket_size=8)
        for k in keys:
            ref.insert(int(k), int(k) * 3)
        for k in keys[:100]:
            ref.delete(int(k))
        sizes.append(len(ref.as_dict()))
        check(lo, lo.spec, 100, pay)

        with tempfile.TemporaryDirectory() as td:
            p = os.path.join(td, "img.npz")
            lo.save(p)
            spec8 = TableSpec(dmax=9, bucket_size=8, pool_size=128,
                              n_lanes=16, placement="sharded", shard_bits=3,
                              value_schema=vs)
            sh8 = Table.restore(p, spec8, mesh8)
            check(sh8, spec8, 100, pay)

            sh8.save(p)
            spec4 = TableSpec(dmax=10, bucket_size=8, pool_size=256,
                              n_lanes=16, placement="sharded", shard_bits=2,
                              value_schema=vs)
            sh4 = Table.restore(p, spec4, mesh4)
            check(sh4, spec4, 100, pay)

            # the revived sharded table still executes transactions
            sh4, res = sh4.insert(keys[:100],
                                  {"page": (keys[:100] * 3).astype(np.int32)}
                                  if pay is not None else keys[:100] * 3)
            assert (np.asarray(res.status) == 1).all()
            assert int(sh4.size()) == len(keys)

    print(json.dumps({"ok": True, "sizes": sizes}))
    return 0


if __name__ == "__main__":
    assert sys.argv[1:] == ["--run-reshard"], sys.argv
    sys.exit(_reshard_main())
