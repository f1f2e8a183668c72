"""Tile-size selection for the table kernels: heuristic, env, and measured.

The Pallas kernels tile the (queries × pool) space; the sweet spot depends
on batch width, pool size, directory capacity and the backend's VMEM. This
module centralizes the choice so the plan layer (kernels/plan.py), the
kernel wrappers, and benchmarks share one policy. Resolution layers,
strongest first:

  1. environment — ``REPRO_TILE_TQ`` / ``REPRO_TILE_PC`` / ``REPRO_TILE_DC``
     force a global tile shape (quick A/B sweeps without code edits); read
     at plan-resolution time only — a live table's plan is immutable;
  2. registry — in-process pins per workload key. Keys follow the plan
     schema ``{kind}/d{dmax}/p{pool_size}/n{n_lanes}`` and are validated:
     unknown key forms raise, and re-registering a *different* tile shape
     for the same key raises (collision) unless ``override=True``.
     Direct registry writes are **deprecated** as an application API — let
     :func:`autotune` (which persists winners) or the env overrides drive
     tile choice; ``register_tiles`` remains for the autotuner itself and
     for tests;
  3. heuristic — VMEM-budget-derived defaults matching the kernel module
     docstrings (TQ≤256, PC≤512, DC≤512).

``autotune`` is the **measured** sweep: it times candidate tile shapes with
a caller-supplied runner and persists the winner in an on-disk JSON cache
keyed by ``(backend tag, plan key)`` — so per ``(shape, backend)`` the sweep
runs once per checkout, and every later plan resolution is a cache hit. The
cache lives at ``REPRO_TUNE_CACHE`` (default ``<checkout>/.tile_cache.json``,
next to the compile cache — see ``repro/caches.py``).
"""
from __future__ import annotations

import dataclasses
import json
import os
import re
import time
from pathlib import Path
from typing import Callable, Dict, Iterable, Optional

from repro.caches import TILE_CACHE_PATH


@dataclasses.dataclass(frozen=True)
class TileConfig:
    tq: int = 256   # query-tile rows
    pc: int = 512   # pool-chunk rows
    dc: int = 512   # directory-chunk entries (fused route)


# --------------------------------------------------------------------------
# key schema: one canonical spelling per (kernel kind, spec geometry)

TILE_KINDS = ("lookup", "apply")

_KEY_RE = re.compile(
    r"^(?P<kind>lookup|apply)/d(?P<dmax>\d+)/p(?P<pool>\d+)/n(?P<lanes>\d+)$")


def tile_key(kind: str, *, dmax: int, pool_size: int, n_lanes: int) -> str:
    """Canonical registry/cache key for one kernel-launch geometry."""
    assert kind in TILE_KINDS, kind
    return f"{kind}/d{dmax}/p{pool_size}/n{n_lanes}"


def validate_key(key: str) -> re.Match:
    """Check a key against the plan schema; raise ``ValueError`` otherwise.

    The schema is ``{kind}/d{dmax}/p{pool_size}/n{n_lanes}`` with ``kind``
    in :data:`TILE_KINDS` — the same geometry the plan layer resolves tiles
    for, so a registry entry can never silently miss its lookup."""
    m = _KEY_RE.match(key)
    if m is None:
        raise ValueError(
            f"tile key {key!r} does not match the plan schema "
            "'{kind}/d{dmax}/p{pool}/n{lanes}' with kind in "
            f"{TILE_KINDS} (see kernels.tuning.tile_key)")
    return m


_REGISTRY: Dict[str, TileConfig] = {}


def register_tiles(key: str, tiles: TileConfig, *,
                   override: bool = False) -> None:
    """Pin ``tiles`` for a plan-schema ``key`` (in-process).

    Raises ``ValueError`` for keys outside the plan schema and for
    collisions (an existing entry with a *different* tile shape) unless
    ``override=True``. Deprecated as an application-facing API — prefer
    :func:`autotune` or the ``REPRO_TILE_*`` env overrides; the registry
    remains as the autotuner's in-process landing spot."""
    validate_key(key)
    if not isinstance(tiles, TileConfig):
        raise TypeError(f"expected TileConfig, got {type(tiles).__name__}")
    prev = _REGISTRY.get(key)
    if prev is not None and prev != tiles and not override:
        raise ValueError(
            f"tile registry collision for {key!r}: {prev} is already "
            f"registered, refusing to overwrite with {tiles} "
            "(pass override=True to re-tune)")
    _REGISTRY[key] = tiles


def clear_registry() -> None:
    """Drop all in-process pins (tests / re-tuning)."""
    _REGISTRY.clear()


def _env_override() -> Optional[TileConfig]:
    tq = os.environ.get("REPRO_TILE_TQ")
    pc = os.environ.get("REPRO_TILE_PC")
    dc = os.environ.get("REPRO_TILE_DC")
    if tq is None and pc is None and dc is None:
        return None
    base = TileConfig()
    return TileConfig(tq=int(tq or base.tq), pc=int(pc or base.pc),
                      dc=int(dc or base.dc))


def clamp_tiles(t: TileConfig, n_queries: int, pool_size: int,
                dcap: int = 0) -> TileConfig:
    """Clamp a tile choice to one launch's problem shape (padding beyond
    the arrays wastes whole programs; dc must divide the directory)."""
    tq = min(t.tq, max(8, n_queries))
    pc = min(t.pc, max(8, pool_size))
    dc = min(t.dc, dcap) if dcap else t.dc
    if dcap:
        # dc must divide the directory capacity (a power of two): snap any
        # override down to the nearest power of two instead of crashing
        dc = 1 << (max(dc, 1).bit_length() - 1)
    return TileConfig(tq=tq, pc=pc, dc=dc)


def pick_tiles(n_queries: int, pool_size: int, dcap: int = 0,
               key: str = "") -> TileConfig:
    """Resolve tiles for one kernel launch (env > registry > heuristic).

    ``key``, when given, must follow the plan schema (:func:`tile_key`)."""
    if key:
        validate_key(key)
    env = _env_override()
    if env is not None:
        t = env
    elif key and key in _REGISTRY:
        t = _REGISTRY[key]
    else:
        t = TileConfig()
    return clamp_tiles(t, n_queries, pool_size, dcap)


def default_candidates(n_queries: int, pool_size: int,
                       dcap: int = 0) -> list[TileConfig]:
    """The measured sweep's candidate grid, clamped to the problem and
    deduplicated (tiny problems collapse to one or two candidates)."""
    out = []
    for tq in (128, 256):
        for pc in (256, 512, 1024):
            for dc in (256, 512):
                c = clamp_tiles(TileConfig(tq=tq, pc=pc, dc=dc),
                                n_queries, pool_size, dcap)
                if c not in out:
                    out.append(c)
    return out


# --------------------------------------------------------------------------
# on-disk measurement cache


def cache_path() -> Path:
    """``REPRO_TUNE_CACHE`` or ``<checkout>/.tile_cache.json``."""
    env = os.environ.get("REPRO_TUNE_CACHE")
    if env:
        return Path(env)
    return TILE_CACHE_PATH


def _load_cache(path: Path) -> dict:
    try:
        with open(path) as f:
            data = json.load(f)
        return data if isinstance(data, dict) else {}
    except (OSError, ValueError):
        return {}


def _store_cache(path: Path, data: dict) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(".tmp")
    with open(tmp, "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)
    os.replace(tmp, path)


def cached_tiles(key: str, backend_tag: str,
                 path: Optional[Path] = None) -> Optional[TileConfig]:
    """The persisted winner for ``(backend_tag, key)``, or None."""
    validate_key(key)
    entry = _load_cache(path or cache_path()).get(f"{backend_tag}::{key}")
    if not entry:
        return None
    try:
        return TileConfig(**entry["tiles"])
    except (KeyError, TypeError):
        return None


def autotune(key: str, candidates: Iterable[TileConfig],
             run: Callable[[TileConfig], None], iters: int = 5, *,
             backend_tag: str = "", use_cache: bool = True,
             path: Optional[Path] = None) -> TileConfig:
    """Measured tile sweep with an on-disk cache per ``(backend, key)``.

    On a cache hit the runner is never invoked — the persisted winner is
    registered and returned. On a miss, ``run`` is timed per candidate
    (``run`` must block until the work is done, e.g. via
    ``jax.block_until_ready``; the first call per candidate is warmup),
    and the argmin is registered, persisted, and returned. A candidate that
    raises just loses the sweep (an illegal tile shape is not fatal), but if
    every candidate raises the sweep raises too: on a chip that means the
    compiler refused the kernel, and a default tile would only hide it.
    """
    validate_key(key)
    if not backend_tag:
        import jax
        backend_tag = jax.default_backend()
    path = path or cache_path()
    if use_cache:
        hit = cached_tiles(key, backend_tag, path)
        if hit is not None:
            register_tiles(key, hit, override=True)
            return hit
    best, best_t = None, float("inf")
    errors = []
    for tiles in candidates:
        try:
            run(tiles)  # warmup/compile
            t0 = time.perf_counter()
            for _ in range(max(1, iters)):
                run(tiles)
            dt = (time.perf_counter() - t0) / max(1, iters)
        except Exception as e:  # noqa: BLE001 — illegal tile shapes just lose
            errors.append(f"{tiles}: {type(e).__name__}: {e}")
            continue
        if dt < best_t:
            best, best_t = tiles, dt
    if best is None:
        raise RuntimeError(
            f"autotune {key!r} on {backend_tag!r}: every candidate failed\n"
            + "\n".join(errors))
    register_tiles(key, best, override=True)
    if use_cache:
        data = _load_cache(path)
        data[f"{backend_tag}::{key}"] = {
            "tiles": dataclasses.asdict(best),
            "mean_s": best_t,
            "iters": iters,
            "measured_at": time.time(),
        }
        _store_cache(path, data)
    return best
