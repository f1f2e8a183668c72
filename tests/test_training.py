"""Training substrate: checkpoint atomicity + elastic restore, data pipeline
determinism, LR schedule, loss sanity over steps."""
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.archs import smoke_config
from repro.training import checkpoint as C
from repro.training.data import Prefetcher, SyntheticLM
from repro.training.optimizer import OptConfig, lr_at
from repro.training.train_step import TrainConfig, init_train_state, make_train_step

jax.config.update("jax_platform_name", "cpu")


def test_loss_decreases_over_steps():
    cfg = smoke_config("smollm-135m")
    state = init_train_state(cfg, jax.random.key(0))
    step = make_train_step(cfg, TrainConfig(opt=OptConfig(lr=3e-3,
                                                          warmup_steps=2)))
    src = SyntheticLM(cfg.vocab_size, seq_len=64, global_batch=4, seed=7)
    losses = []
    batch0 = src.batch_at(0)  # overfit one batch: loss must drop
    for i in range(8):
        state, m = step(state, {k: jnp.asarray(v) for k, v in batch0.items()})
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.05, losses


def test_checkpoint_roundtrip_and_resume(tmp_path):
    cfg = smoke_config("smollm-135m")
    state = init_train_state(cfg, jax.random.key(1))
    step = make_train_step(cfg, TrainConfig())
    src = SyntheticLM(cfg.vocab_size, seq_len=32, global_batch=2, seed=1)
    for i in range(3):
        state, _ = step(state, {k: jnp.asarray(v)
                                for k, v in src.batch_at(i).items()})
    ck = str(tmp_path / "ck")
    C.save(ck, 3, state, extra={"data_step": 3})
    assert C.latest_step(ck) == 3

    # restore into a fresh structure and continue — trajectories must match
    like = jax.eval_shape(lambda: state)
    restored, extra = C.restore(ck, 3, like)
    assert extra["data_step"] == 3
    s_a, s_b = state, restored
    for i in range(3, 5):
        batch = {k: jnp.asarray(v) for k, v in src.batch_at(i).items()}
        s_a, ma = step(s_a, batch)
        s_b, mb = step(s_b, batch)
        np.testing.assert_allclose(float(ma["loss"]), float(mb["loss"]),
                                   rtol=1e-6)


def test_checkpoint_tables_alongside_params(tmp_path):
    """WF-Ext tables checkpoint next to the model params in the same
    atomic step dir and revive under a caller-chosen (possibly re-shaped)
    spec — the table analogue of the elastic param restore."""
    from repro.table_api import Table, TableSpec

    cfg = smoke_config("smollm-135m")
    state = init_train_state(cfg, jax.random.key(4))
    spec = TableSpec(dmax=9, pool_size=256, n_lanes=16)
    keys = np.arange(1, 200, dtype=np.int32)
    t = Table.create(spec)
    t, _ = t.insert(keys, keys * 2)

    ck = str(tmp_path / "ck")
    C.save(ck, 7, state, extra={"data_step": 7}, tables={"kv": t})
    assert C.latest_step(ck) == 7
    assert C.table_names(ck, 7) == ["kv"]

    # params restore untouched by the table sidecar
    restored, extra = C.restore(ck, 7, jax.eval_shape(lambda: state))
    assert extra["data_step"] == 7

    # table revives under a DIFFERENT sizing (elastic re-shard path)
    t2 = C.restore_table(ck, 7, "kv",
                         TableSpec(dmax=11, pool_size=512, n_lanes=16))
    assert int(t2.size()) == len(keys)
    found, vals = t2.lookup(keys)
    assert np.asarray(found).all()
    assert (np.asarray(vals) == keys * 2).all()

    # unknown names fail with the available list
    try:
        C.restore_table(ck, 7, "nope", spec)
        raise AssertionError("should have raised")
    except FileNotFoundError as e:
        assert "kv" in str(e)

    # old checkpoints (no tables) keep loading and report none
    C.save(ck, 8, state)
    assert C.table_names(ck, 8) == []


def test_checkpoint_crash_leaves_no_partial(tmp_path):
    """A .tmp dir (simulated mid-crash) must be invisible to latest_step."""
    cfg = smoke_config("smollm-135m")
    state = init_train_state(cfg, jax.random.key(2))
    ck = str(tmp_path / "ck")
    C.save(ck, 1, state)
    os.makedirs(os.path.join(ck, "step_2.tmp"))  # crashed save
    assert C.latest_step(ck) == 1


def test_elastic_restore_changes_sharding(tmp_path):
    """Restore device_puts against a different sharding tree — the elastic
    path. On 1 CPU device the 'new mesh' is trivial, but the API path (shape
    checks, dtype casts, per-leaf device_put with explicit shardings) is the
    one the multi-pod launcher uses."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    cfg = smoke_config("smollm-135m")
    state = init_train_state(cfg, jax.random.key(3))
    ck = str(tmp_path / "ck")
    C.save(ck, 1, state)
    from repro import compat
    mesh = compat.make_mesh((1,), ("data",))
    shardings = jax.tree.map(
        lambda _: NamedSharding(mesh, P()), jax.eval_shape(lambda: state))
    restored, _ = C.restore(ck, 1, jax.eval_shape(lambda: state), shardings)
    a = jax.tree_util.tree_leaves(state)[0]
    b = jax.tree_util.tree_leaves(restored)[0]
    np.testing.assert_array_equal(np.asarray(a, np.float32),
                                  np.asarray(b, np.float32))


def test_data_pipeline_deterministic_and_prefetch():
    src = SyntheticLM(1000, seq_len=16, global_batch=4, seed=9)
    b1 = src.batch_at(5)
    b2 = src.batch_at(5)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert b1["tokens"].max() < 1000
    # next-token alignment
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["targets"][:, :-1])
    pf = Prefetcher(src, start_step=0, depth=2)
    try:
        first = pf.next()
        np.testing.assert_array_equal(first["tokens"], src.batch_at(0)["tokens"])
    finally:
        pf.close()


def test_lr_schedule_shape():
    oc = OptConfig(lr=1e-3, warmup_steps=10, total_steps=100, min_lr_ratio=0.1)
    assert float(lr_at(oc, jnp.int32(0))) == 0.0
    assert abs(float(lr_at(oc, jnp.int32(10))) - 1e-3) < 1e-9
    assert float(lr_at(oc, jnp.int32(100))) <= 1e-4 + 1e-9
    assert float(lr_at(oc, jnp.int32(55))) < 1e-3
