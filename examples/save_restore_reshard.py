"""Durable images + elastic re-shard: local table → 8-way sharded mesh.

Builds a local table, saves it to a canonical on-disk image, then restores
that image as an 8-shard table on a (fake) 8-device mesh — every bucket
re-routes through the ordinary directory math, no migration code. Sizes
and a sample of lookups are parity-checked against the original.

Run: PYTHONPATH=src python examples/save_restore_reshard.py
"""
import os
import tempfile

# fake 8 host devices BEFORE jax initializes (repro imports are lazy)
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import jax                      # noqa: E402
import numpy as np              # noqa: E402

from repro import Table, TableSpec, compat  # noqa: E402
from repro.core.invariants import check_invariants  # noqa: E402

# --- build local: 12 directory bits, ~1500 items ---------------------------
local_spec = TableSpec(dmax=12, bucket_size=8, pool_size=1024, n_lanes=16)
t = Table.create(local_spec)
rng = np.random.default_rng(0)
keys = rng.choice(np.arange(1, 1 << 30), size=1500,
                  replace=False).astype(np.int32)
t, res = t.insert(keys, keys * 7)
assert not bool(res.error)
t, _ = t.delete(keys[:250])
print(f"local:    size={int(t.size()):>5} depth={int(t.depth())} "
      f"placement={t.spec.placement}")

with tempfile.TemporaryDirectory() as td:
    path = t.save(os.path.join(td, "table.npz"))
    print(f"image:    {os.path.getsize(path)} bytes at {path}")

    # --- restore sharded: 8 shards consume 3 hash bits, so per-shard
    # dmax=9 gives the same 12-bit aggregate addressing ---------------------
    mesh = compat.make_mesh((1, 8), ("data", "model"))
    sharded_spec = TableSpec(dmax=9, bucket_size=8, pool_size=256,
                             n_lanes=16, placement="sharded", shard_bits=3)
    t8 = Table.restore(path, sharded_spec, mesh)

print(f"sharded:  size={int(t8.size()):>5} depth={int(t8.depth())} "
      f"shards={t8.spec.n_shards} mesh={dict(t8.mesh.shape)}")
assert int(t8.size()) == int(t.size())

# parity on a sample: deleted keys miss, live keys carry their values
sample = np.concatenate([keys[:50], keys[700:750]])
f_lo, v_lo = t.lookup(sample)
f_sh, v_sh = t8.lookup(sample)
assert (np.asarray(f_lo) == np.asarray(f_sh)).all()
assert (np.asarray(v_lo) == np.asarray(v_sh)).all()
assert not np.asarray(f_sh)[:50].any() and np.asarray(f_sh)[50:].all()

# the revived table is a first-class citizen: transactions keep working
t8, res = t8.insert(keys[:250], keys[:250] * 7)
assert (np.asarray(res.status) == 1).all()   # all fresh re-inserts
assert int(t8.size()) == len(keys)

# every shard of the revived-and-refilled table passes the structural
# invariants (the per-shard config mirrors the shard id's hash_shift)
import jax.numpy as jnp  # noqa: E402
from repro.core.table import TableState  # noqa: E402

lcfg = t8.spec.table_config()
for s in range(t8.spec.n_shards):
    shard = TableState(*[jnp.asarray(np.asarray(x)[s]) for x in t8.state])
    check_invariants(lcfg, shard)
print(f"refilled: size={int(t8.size()):>5} — "
      "local → image → 8-way sharded, content-identical")
