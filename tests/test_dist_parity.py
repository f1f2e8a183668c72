"""Facade-level distributed parity: sharded `Table` vs local `Table` vs the
paper-literal Python reference, on a CPU mesh.

The harness in `_parity_main` runs in a subprocess with 8 forced host
devices (XLA device count is process-global and must stay 1 for the other
tests): a (data=4, model=2) mesh carries a 2-shard table; a random mixed
insert/delete workload with variable batch lengths must produce
lane-identical statuses and identical content across all three
implementations, including a pytree value schema (payload parity between
placements).
"""
import os
import subprocess
import sys

import pytest

HERE = os.path.abspath(__file__)


def _parity_main():
    import jax.numpy as jnp
    import numpy as np

    from repro import compat
    from repro.core import table as T
    from repro.core.reference import SeqExtHash
    from repro.core.spec import TableSpec
    from repro.table_api import Table

    mesh = compat.make_mesh((4, 2), ("data", "model"))
    n = 16

    # --- scalar parity: sharded vs local vs sequential reference ---------
    sh = Table.create(TableSpec(dmax=8, bucket_size=4, pool_size=256,
                                n_lanes=n, placement="sharded",
                                shard_bits=1), mesh)
    lo = Table.create(TableSpec(dmax=9, bucket_size=4, pool_size=512,
                                n_lanes=n))
    ref = SeqExtHash(dmax=9, bucket_size=4)
    rng = np.random.default_rng(7)
    universe = np.arange(1, 3000)

    with compat.set_mesh(mesh):
        for step in range(8):
            # variable batch length, NOT a multiple of n_lanes
            m = int(rng.integers(5, 3 * n))
            kinds = rng.integers(1, 3, size=m).astype(np.int32)
            keys = rng.choice(universe, size=m, replace=False).astype(np.int32)
            vals = rng.integers(0, 999, size=m).astype(np.int32)
            sh, res_sh = sh.apply(kinds, keys, vals)
            lo, res_lo = lo.apply(kinds, keys, vals)
            want = np.asarray([
                ref.insert(int(k), int(v)) if kk == T.INS else
                ref.delete(int(k))
                for kk, k, v in zip(kinds, keys, vals)], np.int8)
            assert (np.asarray(res_sh.status) == want).all(), (
                step, np.asarray(res_sh.status), want)
            assert (np.asarray(res_lo.status) == want).all(), step
            assert not bool(res_sh.error) and not bool(res_lo.error)

        # content parity over the whole touched universe
        q = universe.astype(np.int32)
        f_sh, v_sh = sh.lookup(q)
        f_lo, v_lo = lo.lookup(q)
        ref_map = ref.as_dict()
        f_ref = np.asarray([int(k) in ref_map for k in q])
        v_ref = np.asarray([ref_map.get(int(k), -1) for k in q], np.int32)
        assert (np.asarray(f_sh) == f_ref).all()
        assert (np.asarray(f_lo) == f_ref).all()
        assert (np.asarray(v_sh) == v_ref).all()
        assert (np.asarray(v_lo) == v_ref).all()
        assert int(sh.size()) == int(lo.size()) == len(ref_map)

        # --- schema parity: payload pytrees agree across placements -------
        schema = {"page": jnp.int32, "score": (jnp.float32, (2,))}
        sh2 = Table.create(TableSpec(dmax=8, bucket_size=4, pool_size=256,
                                     n_lanes=n, placement="sharded",
                                     shard_bits=1, value_schema=schema),
                           mesh)
        lo2 = Table.create(TableSpec(dmax=9, bucket_size=4, pool_size=512,
                                     n_lanes=n, value_schema=schema))
        keys = rng.choice(universe, size=37, replace=False).astype(np.int32)
        pay = {"page": (keys * 3).astype(np.int32),
               "score": np.stack([keys / 2, keys / 4], -1).astype(np.float32)}
        sh2, r1 = sh2.insert(keys, pay)
        lo2, r2 = lo2.insert(keys, pay)
        assert (np.asarray(r1.status) == np.asarray(r2.status)).all()
        sh2, _ = sh2.delete(keys[:11])
        lo2, _ = lo2.delete(keys[:11])
        fa, pa = sh2.lookup(keys)
        fb, pb = lo2.lookup(keys)
        assert (np.asarray(fa) == np.asarray(fb)).all()
        assert (np.asarray(pa["page"]) == np.asarray(pb["page"])).all()
        assert np.allclose(np.asarray(pa["score"]), np.asarray(pb["score"]))
        assert (~np.asarray(fa)[:11]).all() and np.asarray(fa)[11:].all()
        assert (np.asarray(pa["page"])[11:] == pay["page"][11:]).all()

    print("dist parity OK")
    return 0


@pytest.mark.subprocess
def test_dist_parity_through_facade():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(HERE), "..", "src"))
    proc = subprocess.run(
        [sys.executable, HERE, "--run-parity"],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, (proc.stdout[-3000:], proc.stderr[-3000:])
    assert "dist parity OK" in proc.stdout


if __name__ == "__main__":
    assert sys.argv[1:] == ["--run-parity"], sys.argv
    sys.exit(_parity_main())
