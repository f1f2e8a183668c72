import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
# ^ 8 host devices for the self-check; run via tests/test_dist_table.py

"""Self-check for the distributed table, through the Table facade: a
(data=4, model=2) mesh runs a random batched workload as a sharded `Table`;
final map + statuses must equal (a) a local `Table` and (b) the paper-
literal sequential reference, lane-for-lane. Exit code 0 = pass."""
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.core import table as T
from repro.core.invariants import to_dict
from repro.core.reference import SeqExtHash
from repro.core.spec import TableSpec
from repro.table_api import Table


def main():
    mesh = compat.make_mesh((4, 2), ("data", "model"))
    n_glob = 16  # 4 data shards × 4 lanes

    # sharded: top hash bit picks the shard, each shard a dmax=8 WF-Ext
    sh_spec = TableSpec(dmax=8, bucket_size=4, pool_size=256, n_lanes=n_glob,
                        placement="sharded", shard_bits=1)
    # local oracle: one dmax=9 table sees the same keyspace partition
    lo_spec = TableSpec(dmax=9, bucket_size=4, pool_size=512, n_lanes=n_glob)

    t_sh = Table.create(sh_spec, mesh)
    t_lo = Table.create(lo_spec)
    ref = SeqExtHash(dmax=9, bucket_size=4)

    rng = np.random.default_rng(0)
    with compat.set_mesh(mesh):
        for step in range(12):
            kinds = rng.integers(1, 3, size=n_glob).astype(np.int32)
            # distinct keys per batch: shard-local linearization order can
            # differ from the reference's lane order for same-key conflicts
            keys = rng.choice(np.arange(1, 4000), size=n_glob,
                              replace=False).astype(np.int32)
            vals = rng.integers(0, 999, size=n_glob).astype(np.int32)
            t_sh, res_sh = t_sh.apply(kinds, keys, vals)
            t_lo, res_lo = t_lo.apply(kinds, keys, vals)
            want = np.asarray([
                ref.insert(int(k), int(v)) if kk == T.INS
                else ref.delete(int(k))
                for kk, k, v in zip(kinds, keys, vals)], np.int8)
            got_sh = np.asarray(res_sh.status)
            got_lo = np.asarray(res_lo.status)
            assert (got_sh == want).all(), (step, got_sh, want)
            assert (got_lo == want).all(), (step, got_lo, want)
            assert not bool(res_sh.error) and not bool(res_lo.error)

            q = rng.choice(np.arange(1, 4000), size=n_glob).astype(np.int32)
            f1, v1 = t_sh.lookup(q)
            f2, v2 = t_lo.lookup(q)
            want_fv = [ref.lookup(int(k)) for k in q]
            assert (np.asarray(f1) == np.asarray(f2)).all(), step
            assert (np.asarray(v1) == np.asarray(v2)).all(), step
            assert (np.asarray(f1) == np.asarray(
                [f for f, _ in want_fv])).all(), step
            assert (np.asarray(v1) == np.asarray(
                [v for _, v in want_fv])).all(), step

    # final content equality: union of shard dicts == local == reference
    got_map = {}
    lcfg = sh_spec.table_config()
    for s in range(sh_spec.n_shards):
        shard_state = jax.tree.map(lambda x: np.asarray(x)[s], t_sh.state)
        got_map.update(to_dict(lcfg, T.TableState(*shard_state)))
    lo_map = to_dict(lo_spec.table_config(), t_lo.state)
    ref_map = ref.as_dict()
    assert got_map == lo_map == ref_map, (
        len(got_map), len(lo_map), len(ref_map))
    print(f"dist table OK: {len(got_map)} items across {sh_spec.n_shards} "
          f"shards, 12 transactions, statuses lane-exact")

    check_compression(mesh)
    return 0


def check_compression(mesh):
    """int8 all-reduce with error feedback: reduced mean within int8 quant
    error of the exact mean, and feedback drives cumulative error → 0."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed.compression import compressed_psum_grads, \
        init_feedback

    world = mesh.shape["data"] * mesh.shape["model"]
    rng = np.random.default_rng(3)
    base = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)

    def body(fb):
        # device-varying gradient: base scaled by (flat device index + 1)
        idx = (jax.lax.axis_index("data") * mesh.shape["model"]
               + jax.lax.axis_index("model")).astype(jnp.float32)
        g = {"w": base * (idx + 1.0)}
        red, fb = compressed_psum_grads(g, fb, ("data", "model"), world)
        red2, fb = compressed_psum_grads(g, fb, ("data", "model"), world)
        return red, red2, fb

    fb0 = init_feedback({"w": base})
    fn = compat.shard_map(
        body, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(), fb0),),
        out_specs=(jax.tree.map(lambda _: P(), {"w": base}),
                   jax.tree.map(lambda _: P(), {"w": base}),
                   jax.tree.map(lambda _: P(), fb0)),
        check_vma=False)
    red, red2, fb = jax.jit(fn)(fb0)
    exact = np.asarray(base) * (sum(range(1, world + 1)) / world)
    err1 = np.abs(np.asarray(red["w"]) - exact).max()
    # two-step mean with feedback is closer than one uncorrected step
    two_step = (np.asarray(red["w"]) + np.asarray(red2["w"])) / 2
    err2 = np.abs(two_step - exact).max()
    scale = np.abs(exact).max()
    assert err1 < 0.05 * scale, err1
    assert err2 <= err1 + 1e-6, (err1, err2)
    print(f"compression OK: one-step err {err1:.4f}, "
          f"two-step feedback err {err2:.4f} (scale {scale:.2f})")


if __name__ == "__main__":
    sys.exit(main())
