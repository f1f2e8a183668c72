"""Production mesh construction (functions, not module constants — importing
this module never touches jax device state).

Target: TPU v5e pods. Single pod = 256 chips as (data=16, model=16);
multi-pod = 2 pods = 512 chips as (pod=2, data=16, model=16) where the
'pod' axis carries only data parallelism (DCN-friendly: gradient all-reduce
only, no TP traffic across pods).
"""
from __future__ import annotations

from repro import compat


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_local_mesh(model: int = 1, data: int = 1):
    """Small mesh for tests on host devices."""
    return compat.make_mesh((data, model), ("data", "model"))
