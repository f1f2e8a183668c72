"""KernelPlan resolution matrix + measured autotuner cache semantics.

The plan is resolved ONCE at TableSpec construction: every env override
(REPRO_FORCE_INTERPRET, REPRO_FUSED_APPLY, REPRO_AUTOTUNE, REPRO_TILE_*)
is read there and nowhere else — a live table's dispatch is immutable.
These tests pin the resolution matrix (backend × placement × env), the
construction-time-only env semantics, and the autotuner's cold-sweep →
warm-cache-hit contract.

These run on CPU; "native pallas on TPU" rows are asserted via the
resolution function's host-independent parts (interpret flag, guards).
"""
import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.core.spec import TableSpec
from repro.kernels import tuning
from repro.kernels.plan import (KernelPlan, fused_apply_supported,
                                fused_lookup_supported)

jax.config.update("jax_platform_name", "cpu")

ENV_VARS = ("REPRO_FORCE_INTERPRET", "REPRO_FUSED_APPLY", "REPRO_AUTOTUNE",
            "REPRO_TILE_TQ", "REPRO_TILE_PC", "REPRO_TILE_DC")

SMALL = dict(dmax=6, bucket_size=4, pool_size=64, n_lanes=8)


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch, tmp_path):
    """Plan resolution must see a known environment, and the measured
    sweep must never touch the user's real on-disk cache."""
    for var in ENV_VARS:
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "tiles.json"))
    tuning.clear_registry()
    yield
    tuning.clear_registry()


# ---------------------------------------------------------------------------
# resolution matrix: backend × placement × env override


@pytest.mark.parametrize("placement", ["local", "sharded"])
@pytest.mark.parametrize("backend,expect", [
    ("xla", ("xla", False)),
    ("auto", ("xla", False)),          # CPU host, nothing pinned
    ("pallas", ("pallas", True)),      # no TPU → interpret
    ("interpret", ("pallas", True)),
])
def test_resolution_matrix(backend, expect, placement):
    spec = TableSpec(**SMALL, backend=backend, placement=placement)
    plan = spec.plan()
    assert (plan.backend, plan.interpret) == expect
    if plan.backend == "pallas":
        # small geometry is inside both fused guards
        assert plan.fused_lookup and plan.fused_apply
    assert plan.autotune == "off" and plan.source in ("heuristic", "env")


@pytest.mark.parametrize("placement", ["local", "sharded"])
def test_force_interpret_pins_kernels_on_auto(monkeypatch, placement):
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")
    plan = TableSpec(**SMALL, backend="auto", placement=placement).plan()
    assert plan.backend == "pallas" and plan.interpret
    assert plan.fused_apply and plan.fused_lookup
    # explicit xla is a request, not a default — the pin must not override
    assert TableSpec(**SMALL, backend="xla").plan().backend == "xla"


def test_fused_apply_env_kill_switch(monkeypatch):
    monkeypatch.setenv("REPRO_FUSED_APPLY", "0")
    plan = TableSpec(**SMALL, backend="interpret").plan()
    assert plan.backend == "pallas" and not plan.fused_apply
    assert plan.fused_lookup   # the switch is apply-only


def test_env_is_read_at_construction_only(monkeypatch):
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")
    spec = TableSpec(**SMALL, backend="auto")
    assert spec.plan().backend == "pallas"
    monkeypatch.delenv("REPRO_FORCE_INTERPRET")
    # the live spec keeps its resolved plan...
    assert spec.plan().backend == "pallas"
    # ...while a fresh construction — including dataclasses.replace, which
    # re-runs __post_init__ — resolves against the CURRENT environment
    assert TableSpec(**SMALL, backend="auto").plan().backend == "xla"
    assert dataclasses.replace(spec, dmax=7).plan().backend == "xla"


def test_tile_env_override_recorded_as_source(monkeypatch):
    monkeypatch.setenv("REPRO_TILE_PC", "16")
    plan = TableSpec(**SMALL, backend="interpret").plan()
    assert plan.source == "env"
    assert plan.lookup_tiles.pc == 16 and plan.apply_tiles.pc == 16


def test_plan_is_hashable_and_source_free():
    """Plans are jit-static metadata: hashable, and tile PROVENANCE must
    not fork compilation caches — two plans differing only in `source`
    compare (and hash) equal."""
    a = TableSpec(**SMALL, backend="interpret").plan()
    b = dataclasses.replace(a, source="measured")
    assert a == b and hash(a) == hash(b) and len({a, b}) == 1
    assert isinstance(a, KernelPlan)
    # and the spec itself still hashes/compares without the plan attr
    assert TableSpec(**SMALL) == TableSpec(**SMALL)


def test_fused_geometry_guards():
    assert fused_apply_supported(6, 64, 8)
    assert not fused_apply_supported(18, 64, 8)          # lookup's dmax
    assert not fused_apply_supported(6, 1 << 18, 8)      # pool rows
    assert not fused_apply_supported(6, 64, 1024)        # SMEM scalars
    assert fused_apply_supported(6, 64, 512)
    assert not fused_apply_supported(6, 64, 0)
    assert fused_lookup_supported(17, 64)
    assert not fused_lookup_supported(18, 64)
    # a spec outside the apply guard still plans fused lookups
    plan = TableSpec(dmax=6, bucket_size=128, pool_size=64, n_lanes=513,
                     backend="interpret").plan()
    assert plan.fused_lookup and not plan.fused_apply


# ---------------------------------------------------------------------------
# measured autotuner: cold sweep → warm cache hit


def test_autotune_cold_sweep_then_warm_hit(tmp_path):
    key = tuning.tile_key("lookup", dmax=6, pool_size=64, n_lanes=8)
    cands = [tuning.TileConfig(8, 16, 32), tuning.TileConfig(16, 32, 64)]
    calls = []
    path = tmp_path / "cache.json"

    win = tuning.autotune(key, cands, calls.append, iters=2,
                          backend_tag="cpu+interpret", path=path)
    assert win in cands
    assert calls, "cold sweep must invoke the runner"
    n_cold = len(calls)
    assert path.exists()
    entry = json.loads(path.read_text())[f"cpu+interpret::{key}"]
    assert tuning.TileConfig(**entry["tiles"]) == win
    assert entry["iters"] == 2 and entry["mean_s"] >= 0.0

    # warm: the persisted winner is returned WITHOUT running anything,
    # even with the in-process registry wiped (a fresh process)
    tuning.clear_registry()
    win2 = tuning.autotune(key, cands, calls.append, iters=2,
                           backend_tag="cpu+interpret", path=path)
    assert win2 == win and len(calls) == n_cold
    # and the hit re-pinned the registry for env-free pick_tiles reuse
    assert tuning.pick_tiles(8, 64, key=key) == tuning.clamp_tiles(win, 8, 64)


def test_autotune_cache_is_backend_keyed(tmp_path):
    key = tuning.tile_key("apply", dmax=6, pool_size=64, n_lanes=8)
    cands = [tuning.TileConfig(8, 16, 32)]
    calls = []
    path = tmp_path / "cache.json"
    tuning.autotune(key, cands, calls.append, iters=1,
                    backend_tag="cpu+interpret", path=path)
    n = len(calls)
    # a different backend tag is a different machine: full re-measure
    tuning.autotune(key, cands, calls.append, iters=1,
                    backend_tag="tpu", path=path)
    assert len(calls) > n
    assert tuning.cached_tiles(key, "cpu+interpret", path) is not None
    assert tuning.cached_tiles(key, "tpu", path) is not None


def test_autotune_skips_raising_candidates(tmp_path):
    key = tuning.tile_key("lookup", dmax=4, pool_size=16, n_lanes=8)
    good = tuning.TileConfig(8, 8, 16)

    def run(t):
        if t != good:
            raise RuntimeError("illegal tile shape")

    win = tuning.autotune(key, [tuning.TileConfig(64, 64, 64), good], run,
                          iters=1, backend_tag="x",
                          path=tmp_path / "c.json")
    assert win == good


def test_autotune_raises_when_every_candidate_fails(tmp_path):
    """No silent default: a sweep in which every candidate raises (on a
    chip: the compiler refused the kernel) must raise, naming the errors,
    and must persist nothing."""
    key = tuning.tile_key("apply", dmax=4, pool_size=16, n_lanes=8)

    def run(t):
        raise RuntimeError("Mosaic refused the kernel")

    path = tmp_path / "c.json"
    with pytest.raises(RuntimeError, match="every candidate failed"):
        tuning.autotune(key, [tuning.TileConfig(8, 8, 16)], run, iters=1,
                        backend_tag="x", path=path)
    assert not path.exists()


def test_tile_cache_defaults_into_the_checkout(monkeypatch):
    from repro import caches

    monkeypatch.delenv("REPRO_TUNE_CACHE", raising=False)
    assert tuning.cache_path() == caches.TILE_CACHE_PATH
    assert caches.TILE_CACHE_PATH.parent == caches.COMPILE_CACHE_DIR.parent
    assert (caches.CHECKOUT / "src" / "repro" / "caches.py").exists()


def test_compile_cache_rule(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, is JAX's own to read: nothing
    overrides it. Unset, the cache goes to the checkout's fixed path."""
    from repro import caches

    old = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert caches.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == old
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert caches.enable_compile_cache() == str(caches.COMPILE_CACHE_DIR)
        assert (jax.config.jax_compilation_cache_dir
                == str(caches.COMPILE_CACHE_DIR))
    finally:
        jax.config.update("jax_compilation_cache_dir", old)


def test_measured_policy_end_to_end(tmp_path, monkeypatch):
    """autotune='measured' on a tiny geometry: first construction times a
    real interpret-mode sweep (source='measured'), an identical second
    construction resolves purely from the on-disk cache (source='cache')
    with identical tiles."""
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "t.json"))
    geo = dict(dmax=4, bucket_size=2, pool_size=8, n_lanes=8,
               backend="interpret", autotune="measured")
    s1 = TableSpec(**geo)
    assert s1.plan().source == "measured"
    assert s1.plan().autotune == "measured"
    tuning.clear_registry()   # cache survives processes; registry doesn't
    s2 = TableSpec(**geo)
    assert s2.plan().source == "cache"
    assert s2.plan().lookup_tiles == s1.plan().lookup_tiles
    assert s2.plan().apply_tiles == s1.plan().apply_tiles
    assert s1.plan() == s2.plan()   # provenance excluded from equality
    # REPRO_AUTOTUNE overrides the spec field at resolution time
    monkeypatch.setenv("REPRO_AUTOTUNE", "off")
    assert TableSpec(**geo).plan().source in ("heuristic", "env")


# ---------------------------------------------------------------------------
# plan-driven dispatch plumbing


def test_table_facade_exposes_plan():
    from repro.table_api import Table

    t = Table.create(TableSpec(**SMALL, backend="xla"))
    assert t.plan() is t.spec.plan()
    assert t.plan().backend == "xla"


def test_plan_apply_routes_by_plan():
    """plan_apply must pick the executable the plan names — xla plan hits
    the reference transaction, pallas+fused the fused kernel — and both
    agree on the result."""
    from repro.core import table as T
    from repro.kernels import ops as kops

    spec_x = TableSpec(**SMALL, backend="xla")
    spec_f = TableSpec(**SMALL, backend="interpret")
    cfg = spec_x.table_config()
    rng = np.random.default_rng(0)
    kinds = np.ones(8, np.int32)
    keys = rng.integers(1, 99, size=8).astype(np.int32)
    s1 = T.init_table(cfg)
    ops = T.make_ops(cfg, s1, kinds, keys, keys)
    s_x, r_x = kops.plan_apply(spec_x.plan(), cfg, s1, ops)
    s_f, r_f = kops.plan_apply(spec_f.plan(), cfg, T.init_table(cfg), ops)
    np.testing.assert_array_equal(np.asarray(r_f.status),
                                  np.asarray(r_x.status))
    f_x, v_x = kops.plan_lookup(spec_x.plan(), cfg, s_x, ops.key)
    f_f, v_f = kops.plan_lookup(spec_f.plan(), cfg, s_f, ops.key)
    np.testing.assert_array_equal(np.asarray(f_f), np.asarray(f_x))
    np.testing.assert_array_equal(np.asarray(v_f), np.asarray(v_x))
