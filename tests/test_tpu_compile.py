"""Compile every kernel the TPU plan can pick, for a described v5e chip.

Interpret mode checks results but not what Mosaic accepts: tiling
alignment, 1-D layouts, SMEM/VMEM placement and casts are only refused by
the chip's compiler. The TPU compiler is installed here and compiles for a
chip that is described, not attached, so these tests compile (never run)
the kernels with ``interpret=False`` at the sizes ``chip_smoke.py`` runs:

* phase-2 geometry (dmax 17, P 131071 — the fused plan): ``fused_probe``
  and ``fused_apply`` at 16 and 512 lanes;
* phase-3 geometry (dmax 20, P 2**20 — beyond the fused bounds): ``probe``
  over the whole ``[P+1, B]`` pools, and ``grouped_apply``;
* the facade's jitted apply and lookup under an explicitly constructed
  TPU ``KernelPlan``.

Each compiled executable must contain a Mosaic kernel (``tpu_custom_call``).
The topology is described inside a module fixture — never at import — so
only the worker that runs this file loads the TPU library. The persistent
compile cache is off around these compiles: an entry written for a
described chip cannot be read back without one.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import table as T
from repro.core.spec import TableSpec
from repro.kernels import apply as kapply
from repro.kernels import lookup as klookup
from repro.kernels.plan import KernelPlan
from repro.kernels.tuning import TileConfig
from repro.table_api import Table

B = 8
FUSED = dict(dmax=17, pool_size=131071)        # chip_smoke phase 2
LARGE = dict(dmax=20, pool_size=1 << 20)       # chip_smoke phase 3


@pytest.fixture(scope="module")
def chip():
    """A SingleDeviceSharding on the first chip of a described v5e:2x2."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(chip, fn, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _i32(*shape):
    return (shape, jnp.int32)


@pytest.mark.parametrize("n", [16, 512])
def test_fused_probe_compiles(chip, n):
    fn = functools.partial(klookup.fused_probe, dmax=FUSED["dmax"],
                           interpret=False)
    p = FUSED["pool_size"]
    text = _compiled_text(chip, fn, _i32(1 << FUSED["dmax"]), _i32(n),
                          _i32(p, B), _i32(p, B))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n", [16, 512])
def test_fused_apply_compiles(chip, n):
    fn = functools.partial(kapply.fused_apply, dmax=FUSED["dmax"],
                           interpret=False)
    p1 = FUSED["pool_size"] + 1
    text = _compiled_text(chip, fn, _i32(1 << FUSED["dmax"]),
                          ((p1,), jnp.bool_), _i32(n), _i32(n), _i32(n),
                          _i32(p1, B), _i32(p1, B))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n", [512, 2 * 8192 + 5])
def test_probe_compiles(chip, n):
    """The routed probe over the whole [P+1, B] pools: a Mosaic kernel whose
    instruction is named ``probe`` (the name the benchmark files under
    lookup), fed the pools as bitcasts of their HBM layout, not copies.
    The longer batch runs in launches of 8192 queries."""
    fn = functools.partial(klookup.probe, interpret=False)
    p1 = LARGE["pool_size"] + 1
    text = _compiled_text(chip, fn, _i32(n), _i32(n), _i32(p1, B),
                          _i32(p1, B))
    assert re.search(r"%probe(\.\d+)? = .*custom_call_target=\"tpu_custom_call\"",
                     text)
    assert not re.search(r"= s32\[(8,\d{7,}|\d{7,},8)\]\S* copy\(", text)


def test_grouped_apply_compiles(chip):
    fn = functools.partial(kapply.grouped_apply, interpret=False)
    m, p = 512, LARGE["pool_size"]
    text = _compiled_text(chip, fn, _i32(m), _i32(m), _i32(m), _i32(m),
                          _i32(p, B), _i32(p, B))
    assert "tpu_custom_call" in text


def _tpu_table(chip, **geo):
    """A Table on the described chip whose spec carries the plan a TPU host
    resolves: pallas, compiled, fused where the geometry allows."""
    spec = TableSpec(**geo, bucket_size=B, backend="interpret")
    plan = KernelPlan(backend="pallas", interpret=False,
                      fused_lookup=spec.plan().fused_lookup,
                      fused_apply=spec.plan().fused_apply,
                      lookup_tiles=TileConfig(), apply_tiles=TileConfig())
    object.__setattr__(spec, "_plan", plan)
    cfg = spec.table_config()
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        jax.eval_shape(lambda: T.init_table(cfg)))
    seq = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
    return Table(spec, None, state, None, None, seq)


@pytest.mark.parametrize("op", ["apply", "lookup"])
def test_facade_compiles_under_tpu_plan(chip, op):
    # initial depth 9: a geometry no other test file builds, so the facade's
    # jit caches cannot hand back an interpret-mode trace of an equal spec
    t = _tpu_table(chip, **FUSED, n_lanes=16, initial_depth=9)
    assert t.plan().fused_apply and t.plan().fused_lookup
    m = 3 * 16 + 5                       # several chunks, NOP-padded
    ks = jax.ShapeDtypeStruct((m,), jnp.int32, sharding=chip)
    if op == "apply":
        lowered = jax.jit(lambda t, k, x: t.apply(k, x, x)).lower(t, ks, ks)
    else:
        lowered = jax.jit(lambda t, x: t.lookup(x)).lower(t, ks)
    assert "tpu_custom_call" in lowered.compile().as_text()
