"""Typed kernel execution plans: backend selection resolved once, up front.

Before this layer, backend choice was scattered: env vars read at every
call site, registry lookups per launch, and ``REPRO_FORCE_INTERPRET``
consulted from several modules. A :class:`KernelPlan` replaces all of that
with one frozen, hashable object resolved at ``TableSpec`` construction —
legal jit static metadata, so the plan travels with the spec through
``jit``/``shard_map`` and two tables with different plans never alias each
other's compiled entry points.

Resolution (:func:`resolve_plan`) is the ONLY place environment overrides
are read:

  ``REPRO_FORCE_INTERPRET=1``  pin the Pallas kernels (interpret mode) as
                               the hot path for ``backend="auto"`` specs on
                               non-TPU hosts (CI's kernels-interpret job);
  ``REPRO_FUSED_APPLY=0``      keep the grouped apply kernel instead of the
                               fully-fused DMA kernel (A/B escape hatch);
  ``REPRO_AUTOTUNE=measured``  force the measured tile sweep regardless of
                               ``spec.autotune`` (``=off`` disables it);
  ``REPRO_TILE_TQ/PC/DC``      force tile shapes (via kernels/tuning.py);
  ``REPRO_TUNE_CACHE``         on-disk autotune cache location.

Changing the environment after a spec is constructed does not change that
spec's plan — construct a new spec (the point: a live table's dispatch is
immutable and inspectable via ``Table.plan()``).

Fused-apply eligibility: the single-launch apply routes in XLA and moves
only the 8-row pool tiles it touches, so its one hard bound is the per-lane
scalars it keeps in SMEM (``n_lanes`` ≤ 512). The plan pairs it with the
fused lookup's geometry (``dmax`` ≤ 17, ``P+1`` ≤ 2**17); beyond that the
grouped apply kernel streams 512-row chunks (and the XLA single-pass
transaction remains the ``xla`` backend). Whether the fused apply should
take the larger geometries too is open until it is measured on the chip.
"""
from __future__ import annotations

import dataclasses
import os

from repro.kernels.lookup import FUSED_DMAX_LIMIT
from repro.kernels.tuning import (TileConfig, autotune, cached_tiles,
                                  default_candidates, pick_tiles, tile_key)

PLAN_BACKENDS = ("xla", "pallas")
AUTOTUNE_POLICIES = ("off", "measured")

# fused-apply guards (see module docstring)
FUSED_APPLY_POOL_LIMIT = 1 << 17   # pool rows (trash row included)
FUSED_APPLY_MAX_LANES = 512        # per-lane scalars in SMEM

_TUNE_ITERS = 3


@dataclasses.dataclass(frozen=True)
class KernelPlan:
    """One table's resolved kernel dispatch, as hashable static metadata.

    ``backend`` is post-resolution ("auto" never survives): ``"xla"`` runs
    the single-pass transaction, ``"pallas"`` the kernels (compiled on TPU,
    ``interpret=True`` elsewhere). ``fused_lookup`` / ``fused_apply``
    select the fully-fused kernels where the geometry guards allow;
    ``lookup_tiles`` / ``apply_tiles`` are upper bounds, clamped to each
    launch. ``source`` records tile provenance ("heuristic" | "env" |
    "measured" | "cache") and is excluded from equality/hash — provenance
    must not fork jit caches.
    """

    backend: str
    interpret: bool
    fused_lookup: bool
    fused_apply: bool
    lookup_tiles: TileConfig
    apply_tiles: TileConfig
    autotune: str = "off"
    source: str = dataclasses.field(default="heuristic", compare=False)

    def __post_init__(self):
        assert self.backend in PLAN_BACKENDS, self.backend
        assert self.autotune in AUTOTUNE_POLICIES, self.autotune


def force_interpret() -> bool:
    """REPRO_FORCE_INTERPRET=1 pins the Pallas kernels (interpret mode) as
    the default hot path on ANY backend. Without it a CPU runner's
    ``backend="auto"`` quietly resolves to the XLA path and the kernel
    bodies never execute — CI's kernels-interpret job sets this so the
    Pallas code paths are really run, not silently skipped."""
    return os.environ.get("REPRO_FORCE_INTERPRET", "") not in ("", "0")


def fused_lookup_supported(dmax: int, pool_size: int) -> bool:
    """Directory-in-VMEM probe: dmax-bounded directory, fp32-exact rows."""
    return dmax <= FUSED_DMAX_LIMIT and pool_size < (1 << 24)


def fused_apply_supported(dmax: int, pool_size: int, n_lanes: int) -> bool:
    return (dmax <= FUSED_DMAX_LIMIT
            and pool_size + 1 <= FUSED_APPLY_POOL_LIMIT
            and 0 < n_lanes <= FUSED_APPLY_MAX_LANES)


def _measured_tiles(kind: str, cfg, backend_tag: str, interpret: bool,
                    n_queries: int) -> TileConfig:
    """Resolve tiles by timing real kernel launches on a scratch state of
    the spec's geometry; winners persist in the on-disk cache. Imports are
    lazy — plan resolution must stay importable from core/spec.py."""
    import jax

    from repro.core import table as T

    key = tile_key(kind, dmax=cfg.dmax, pool_size=cfg.pool_size,
                   n_lanes=n_queries)
    dcap = cfg.dcap if kind == "lookup" else 0
    candidates = default_candidates(n_queries, cfg.pool_size, dcap)

    state = None  # built once, on first (cache-miss) runner call

    def runner(tiles: TileConfig):
        nonlocal state
        if state is None:
            state = T.init_table(cfg)
        if kind == "lookup":
            from repro.kernels import ops as kops
            out = kops._kernel_lookup_impl(
                cfg, state, jax.numpy.arange(n_queries, dtype=jax.numpy.int32),
                fused=fused_lookup_supported(cfg.dmax, cfg.pool_size),
                tq=tiles.tq, pc=tiles.pc, dc=tiles.dc, interpret=interpret)
        else:
            from repro.kernels import apply as kapply
            n = n_queries
            i = jax.numpy.arange(n, dtype=jax.numpy.int32)
            out = kapply.grouped_apply(
                jax.numpy.ones(n, jax.numpy.int32), i, i,
                (i * cfg.pool_size // max(n, 1)).astype(jax.numpy.int32),
                state.keys[:-1], state.vals[:-1],
                pc=tiles.pc, interpret=interpret)
        jax.block_until_ready(out)

    return autotune(key, candidates, runner, iters=_TUNE_ITERS,
                    backend_tag=backend_tag)


def resolve_plan(spec) -> KernelPlan:
    """Resolve a ``TableSpec`` to its :class:`KernelPlan`.

    Called once from ``TableSpec.__post_init__`` — every env override is
    applied here and nowhere else. ``spec`` duck-types: only the geometry
    and ``backend`` / ``autotune`` fields are read."""
    import jax

    host = jax.default_backend()
    req = spec.backend
    if req == "xla":
        backend, interpret = "xla", False
    elif req == "interpret":
        backend, interpret = "pallas", True
    elif req == "pallas":
        backend, interpret = "pallas", host != "tpu"
    else:  # auto: kernels where they compile natively, or when pinned
        if host == "tpu":
            backend, interpret = "pallas", False
        elif force_interpret():
            backend, interpret = "pallas", True
        else:
            backend, interpret = "xla", False

    cfg = spec.table_config()
    fused_lookup = (backend == "pallas"
                    and fused_lookup_supported(cfg.dmax, cfg.pool_size))
    fused_apply = (backend == "pallas"
                   and fused_apply_supported(cfg.dmax, cfg.pool_size,
                                             spec.n_lanes)
                   and os.environ.get("REPRO_FUSED_APPLY", "") != "0")

    policy = os.environ.get("REPRO_AUTOTUNE") or getattr(
        spec, "autotune", "off")
    assert policy in AUTOTUNE_POLICIES, policy

    n_nominal = max(spec.n_lanes, 8)
    lkey = tile_key("lookup", dmax=cfg.dmax, pool_size=cfg.pool_size,
                    n_lanes=n_nominal)
    akey = tile_key("apply", dmax=cfg.dmax, pool_size=cfg.pool_size,
                    n_lanes=n_nominal)
    source = "heuristic"
    if backend == "pallas" and policy == "measured":
        tag = host + ("+interpret" if interpret else "")
        was_cached = (cached_tiles(lkey, tag) is not None
                      and cached_tiles(akey, tag) is not None)
        lookup_tiles = _measured_tiles("lookup", cfg, tag, interpret,
                                       n_nominal)
        apply_tiles = _measured_tiles("apply", cfg, tag, interpret,
                                      n_nominal)
        source = "cache" if was_cached else "measured"
    else:
        from repro.kernels.tuning import _env_override
        lookup_tiles = pick_tiles(n_nominal, cfg.pool_size, cfg.dcap,
                                  key=lkey)
        apply_tiles = pick_tiles(n_nominal, cfg.pool_size, key=akey)
        if _env_override() is not None:
            source = "env"

    return KernelPlan(backend=backend, interpret=interpret,
                      fused_lookup=fused_lookup, fused_apply=fused_apply,
                      lookup_tiles=lookup_tiles, apply_tiles=apply_tiles,
                      autotune=policy, source=source)


__all__ = [
    "KernelPlan",
    "resolve_plan",
    "force_interpret",
    "fused_lookup_supported",
    "fused_apply_supported",
    "FUSED_APPLY_POOL_LIMIT",
    "FUSED_APPLY_MAX_LANES",
    "PLAN_BACKENDS",
    "AUTOTUNE_POLICIES",
]
