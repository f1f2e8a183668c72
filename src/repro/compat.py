"""Sharding helpers over the installed JAX (0.9): one spelling per concept.

All repo code goes through this module for meshes and ``shard_map``:

  * :func:`make_mesh` — ``jax.make_mesh`` with every axis ``Auto``. JAX
    0.9 makes axes ``Explicit`` by default (shardings in the types), under
    which e.g. flattening a scan's ``[k, n@data]`` status output has no
    implied sharding and raises. The repo's sharded code relies on
    compiler-propagated shardings, so every mesh it builds is ``Auto``,
    and ``Table.create`` refuses any other.
  * :func:`set_mesh` — context manager establishing the ambient mesh: the
    native ``jax.sharding.set_mesh`` plus a thread-local stack of the
    concrete mesh, so :func:`get_abstract_mesh` can hand ``Table.create``
    a mesh it can place arrays on.
  * :func:`get_abstract_mesh` — the ambient mesh or None (never raises).
  * :func:`shard_map` — ``jax.shard_map`` with ``check_vma``.
  * :func:`with_spec_constraint` — ``with_sharding_constraint`` that
    accepts a bare PartitionSpec plus the ambient mesh.

``getattr`` (not attribute access) is mandatory for optional names:
``jax.sharding`` raises AttributeError through its deprecation machinery
for unknown names.
"""
from __future__ import annotations

import contextlib
import threading

import jax

_local = threading.local()


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with ``Auto`` axis types (see module docstring)."""
    auto = (jax.sharding.AxisType.Auto,) * len(axis_names)
    return jax.make_mesh(axis_shapes, axis_names, axis_types=auto,
                         devices=devices)


def is_auto_mesh(mesh) -> bool:
    return all(t == jax.sharding.AxisType.Auto for t in mesh.axis_types)


def _stack():
    if not hasattr(_local, "mesh_stack"):
        _local.mesh_stack = []
    return _local.mesh_stack


def get_abstract_mesh():
    """Ambient mesh (Mesh or AbstractMesh) or None. Never raises."""
    stk = _stack()
    if stk:
        return stk[-1]
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is not None and not mesh.empty:
        return mesh
    return None


@contextlib.contextmanager
def set_mesh(mesh):
    """Establish ``mesh`` as the ambient mesh for the dynamic extent."""
    _stack().append(mesh)
    try:
        with jax.sharding.set_mesh(mesh):
            yield mesh
    finally:
        _stack().pop()


def shard_map(f, mesh, in_specs, out_specs, check_vma=True, **kw):
    """``jax.shard_map`` with the repo's positional mesh convention."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kw)


def with_spec_constraint(x, mesh, spec):
    """with_sharding_constraint for a bare PartitionSpec.

    Concrete meshes are bound explicitly through NamedSharding; abstract
    meshes fall through to the native spec-based API."""
    if isinstance(mesh, jax.sharding.Mesh):
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(mesh, spec))
    return jax.lax.with_sharding_constraint(x, spec)
