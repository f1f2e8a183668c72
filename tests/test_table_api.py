"""The facade acceptance matrix: ONE test body over every backend/placement.

The same insert/lookup/delete body runs parametrized over
backend ∈ {xla, interpret} × placement ∈ {local, sharded} (the Pallas path
is exercised in interpret mode off-TPU), with a non-trivial pytree value
schema (2 leaves, mixed dtypes, one non-scalar field) and batch lengths
that are NOT multiples of n_lanes. Sharded combos run in a subprocess with
8 forced host devices (device count is process-global).

Also covers the `make_ops` shape-validation satellite (short/over-length
batches raise; `pad_ops` NOP-fills) and the degenerate batch lengths the
serving router leans on (empty and length-1 batches round-trip without a
spurious scan chunk).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.abspath(__file__)
N_LANES = 16
SCHEMA_KEYS = ("page", "score")


def _facade_body(backend: str, placement: str, mesh=None):
    """The shared acceptance body. Pure-python model as the oracle."""
    import jax.numpy as jnp
    from repro.table_api import Table, TableSpec

    schema = {"page": jnp.int32, "score": (jnp.float32, (2,))}
    spec = TableSpec(dmax=8, bucket_size=4, pool_size=256, n_lanes=N_LANES,
                     backend=backend, placement=placement,
                     shard_bits=1 if placement == "sharded" else 1,
                     value_schema=schema)
    t = Table.create(spec, mesh)

    rng = np.random.default_rng(11)
    keys = rng.choice(np.arange(1, 10_000), size=37, replace=False)
    keys = keys.astype(np.int32)              # 37: not a multiple of 16
    pay = {"page": (keys * 5).astype(np.int32),
           "score": np.stack([keys / 3, keys / 7], -1).astype(np.float32)}

    # insert: every key fresh
    t, res = t.insert(keys, pay)
    assert res.status.shape == (37,)
    assert (np.asarray(res.status) == 1).all()
    assert not bool(res.error)
    assert int(t.size()) == 37

    # lookup: payload round-trips; misses zero-filled
    probe = np.concatenate([keys[:5], [9999, 8888]]).astype(np.int32)
    found, val = t.lookup(probe)
    assert np.asarray(found).tolist() == [True] * 5 + [False, False]
    assert (np.asarray(val["page"])[:5] == pay["page"][:5]).all()
    assert np.allclose(np.asarray(val["score"])[:5], pay["score"][:5])
    assert (np.asarray(val["page"])[5:] == 0).all()

    # upsert: overwrite payloads of the first 9 keys (status FALSE)
    t, res = t.insert(keys[:9], {"page": np.full(9, 7, np.int32),
                                 "score": np.zeros((9, 2), np.float32)})
    assert (np.asarray(res.status) == 0).all()
    assert int(t.size()) == 37
    found, val = t.lookup(keys[:10])
    assert (np.asarray(val["page"])[:9] == 7).all()
    assert np.asarray(val["page"])[9] == int(keys[9]) * 5

    # delete 13 (not a lane multiple): status TRUE; absent afterwards
    t, res = t.delete(keys[:13])
    assert (np.asarray(res.status) == 1).all()
    found, _ = t.lookup(keys)
    assert (~np.asarray(found)[:13]).all() and np.asarray(found)[13:].all()
    assert int(t.size()) == 24
    # slab bookkeeping is exact: live payload rows == live items (+trash)
    assert int(np.asarray(t.slab_live).sum()) == 24 + 1

    # delete of absent keys reports FALSE
    t, res = t.delete(keys[:4])
    assert (np.asarray(res.status) == 0).all()
    assert not bool(res.error)
    return True


# --- local combos run in-process ------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_facade_local(backend):
    import jax
    jax.config.update("jax_platform_name", "cpu")
    assert _facade_body(backend, "local")


# --- sharded combos need 8 host devices → subprocess ----------------------

@pytest.mark.subprocess
@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_facade_sharded(backend):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(HERE), "..", "src"))
    proc = subprocess.run(
        [sys.executable, HERE, "--run-sharded", backend],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, (proc.stdout[-3000:], proc.stderr[-3000:])
    assert "sharded facade OK" in proc.stdout


def _sharded_main(backend: str):
    from repro import compat
    mesh = compat.make_mesh((4, 2), ("data", "model"))
    assert _facade_body(backend, "sharded", mesh)
    print("sharded facade OK")
    return 0


@pytest.mark.subprocess
def test_sharded_multichunk_apply_under_ambient_mesh():
    """A batch of several n_lanes chunks on a sharded table, inside
    ``compat.set_mesh``: the scan's stacked [chunks, n_lanes] statuses must
    flatten back to one status per op (under JAX 0.9's default Explicit
    mesh axes that reshape raised ``ShardingTypeError``)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(HERE), "..", "src"))
    proc = subprocess.run(
        [sys.executable, HERE, "--run-multichunk"],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, (proc.stdout[-3000:], proc.stderr[-3000:])
    assert "sharded multichunk OK" in proc.stdout


def _multichunk_main():
    import jax
    from repro import compat
    from repro.table_api import Table, TableSpec

    spec = TableSpec(dmax=8, bucket_size=4, pool_size=256, n_lanes=N_LANES,
                     placement="sharded", shard_bits=1)
    with pytest.raises(AssertionError, match="Auto axis types"):
        Table.create(spec, jax.make_mesh((4, 2), ("data", "model")))
    mesh = compat.make_mesh((4, 2), ("data", "model"))
    t = Table.create(spec, mesh)
    lo = Table.create(TableSpec(dmax=9, bucket_size=4, pool_size=512,
                                n_lanes=N_LANES))
    keys = np.arange(1, 3 * N_LANES + 6, dtype=np.int32)   # 4 chunks
    with compat.set_mesh(mesh):
        t, res = t.insert(keys, keys * 3)
        lo, res_lo = lo.insert(keys, keys * 3)
        assert res.status.shape == keys.shape
        assert (np.asarray(res.status) == 1).all()
        np.testing.assert_array_equal(np.asarray(res.status),
                                      np.asarray(res_lo.status))
        kinds = np.where(keys % 2 == 0, 2, 1).astype(np.int32)  # mixed
        t, res = t.apply(kinds, keys, keys)
        lo, res_lo = lo.apply(kinds, keys, keys)
        np.testing.assert_array_equal(np.asarray(res.status),
                                      np.asarray(res_lo.status))
        found, val = t.lookup(keys)
    assert (np.asarray(found) == (keys % 2 == 1)).all()
    assert (np.asarray(val)[keys % 2 == 1] == keys[keys % 2 == 1]).all()
    assert int(t.size()) == int(lo.size())
    print("sharded multichunk OK")
    return 0


# --- satellite: make_ops validation + pad_ops ------------------------------

def test_make_ops_validates_shapes():
    import jax
    jax.config.update("jax_platform_name", "cpu")
    import jax.numpy as jnp
    from repro.core import table as T

    cfg = T.TableConfig(dmax=6, bucket_size=4, pool_size=64, n_lanes=8)
    state = T.init_table(cfg)
    full = jnp.full((8,), T.INS, jnp.int32)
    keys = jnp.arange(8, dtype=jnp.int32)
    ops = T.make_ops(cfg, state, full, keys, keys)       # exact: fine
    assert ops.kind.shape == (8,)

    short = jnp.full((5,), T.INS, jnp.int32)
    with pytest.raises(ValueError, match="pad_ops"):
        T.make_ops(cfg, state, short, keys[:5], keys[:5])
    over = jnp.full((9,), T.INS, jnp.int32)
    with pytest.raises(ValueError, match="n_lanes"):
        T.make_ops(cfg, state, over, jnp.arange(9, dtype=jnp.int32))
    with pytest.raises(ValueError, match="1-d"):
        T.make_ops(cfg, state, full, keys, keys[:4])

    # pad_ops NOP-fills; padded batch applies identically to a full one
    k, ky, v = T.pad_ops(cfg, short, keys[:5], keys[:5])
    assert k.shape == (8,) and (np.asarray(k)[5:] == T.NOP).all()
    st2, res = T.apply_batch(cfg, state, T.make_ops(cfg, state, k, ky, v))
    assert (np.asarray(res.status)[:5] == 1).all()
    assert int(T.table_size(st2)) == 5
    with pytest.raises(ValueError, match="exceeds n_lanes"):
        T.pad_ops(cfg, over, jnp.arange(9, dtype=jnp.int32))


def test_batch_edge_lengths():
    """Empty and length-1 batches: the degenerate shapes the serving
    router's variable-length dispatch leans on. Empty batches must
    round-trip without dispatching a spurious scan chunk (no seq tick, no
    state change); length-1 batches pad to exactly one chunk."""
    import jax
    jax.config.update("jax_platform_name", "cpu")
    from repro.table_api import Table, TableSpec

    spec = TableSpec(dmax=6, bucket_size=4, pool_size=64, n_lanes=8)
    assert spec.plan_batch(0) == (0, 0)
    assert spec.plan_batch(1) == (1, 8)
    assert spec.plan_batch(8) == (1, 8)
    assert spec.plan_batch(9) == (2, 16)
    t = Table.create(spec)

    # empty apply: status (0,), no transaction dispatched
    empty = np.zeros(0, np.int32)
    seq0 = np.asarray(t.state.applied_seq).copy()
    t, res = t.apply(empty, empty, empty)
    assert res.status.shape == (0,)
    assert not bool(res.error)
    assert (np.asarray(t.state.applied_seq) == seq0).all()
    t2, res = t.insert(empty, empty)
    assert res.status.shape == (0,)
    assert (np.asarray(t2.state.applied_seq) == seq0).all()

    # empty lookup: (0,) found and values, no error
    found, vals = t.lookup(empty)
    assert found.shape == (0,) and vals.shape == (0,)

    # length-1 batches: one chunk, correct result, size tracks
    t, res = t.insert(np.asarray([42], np.int32), np.asarray([7], np.int32))
    assert res.status.shape == (1,) and int(np.asarray(res.status)[0]) == 1
    assert int(t.size()) == 1
    found, vals = t.lookup(np.asarray([42], np.int32))
    assert bool(np.asarray(found)[0]) and int(np.asarray(vals)[0]) == 7
    t, res = t.delete(np.asarray([42], np.int32))
    assert res.status.shape == (1,) and int(np.asarray(res.status)[0]) == 1
    assert int(t.size()) == 0

    # empty batch with a pytree value schema: schema-shaped empty leaves
    import jax.numpy as jnp
    sspec = TableSpec(dmax=6, bucket_size=4, pool_size=64, n_lanes=8,
                      value_schema={"page": jnp.int32,
                                    "score": (jnp.float32, (2,))})
    ts = Table.create(sspec)
    found, vals = ts.lookup(empty)
    assert found.shape == (0,)
    assert vals["page"].shape == (0,)
    assert vals["score"].shape == (0, 2)


def test_frozen_upsert_preserves_payload():
    """A FROZEN (not-executed) upsert must leave the key's payload alone:
    the payload scatter is gated on the transaction's statuses."""
    import jax
    jax.config.update("jax_platform_name", "cpu")
    import jax.numpy as jnp
    from repro.core import table as T
    from repro.table_api import Table, TableSpec

    spec = TableSpec(dmax=6, bucket_size=4, pool_size=64, n_lanes=8,
                     initial_depth=1, backend="xla",
                     value_schema={"v": jnp.int32})
    t = Table.create(spec)
    t, res = t.insert([5], {"v": [111]})
    assert np.asarray(res.status).tolist() == [1]

    # freeze both depth-1 buddies (the paper's freeze-then-merge protocol)
    st, ok = T.freeze_buddies(t.config, t.state, 0, 0)
    assert bool(ok)
    t = t._replace(state=st)

    t, res = t.insert([5], {"v": [222]})
    assert np.asarray(res.status).tolist() == [T.FROZEN]  # op NOT executed
    found, val = t.lookup([5])
    assert bool(np.asarray(found)[0])
    assert np.asarray(val["v"]).tolist() == [111]          # payload intact


def test_facade_threads_through_jit_and_scan():
    """A Table is a pytree: jit arg, scan carry — no special casing."""
    import jax
    jax.config.update("jax_platform_name", "cpu")
    import jax.numpy as jnp
    from repro.table_api import Table, TableSpec

    spec = TableSpec(dmax=7, bucket_size=4, pool_size=128, n_lanes=8,
                     backend="xla", value_schema={"v": jnp.int32})
    t = Table.create(spec)

    @jax.jit
    def ingest(t, batches):
        def body(t, ks):
            t, _ = t.insert(ks, {"v": ks * 2})
            return t, ks.sum()
        return jax.lax.scan(body, t, batches)

    batches = jnp.arange(1, 25, dtype=jnp.int32).reshape(3, 8)
    t2, sums = ingest(t, batches)
    assert int(t2.size()) == 24
    found, val = t2.lookup(jnp.arange(1, 25, dtype=jnp.int32))
    assert np.asarray(found).all()
    assert (np.asarray(val["v"]) == 2 * np.arange(1, 25)).all()


if __name__ == "__main__":
    if sys.argv[1] == "--run-multichunk":
        sys.exit(_multichunk_main())
    assert sys.argv[1] == "--run-sharded", sys.argv
    sys.exit(_sharded_main(sys.argv[2]))
