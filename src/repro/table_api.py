"""The unified ``Table`` facade: one typed handle over every backend.

The paper's interface is three operations — Insert / Delete / Search —
behind a single wait-free object. This module is that object for the
reproduction: an immutable, pytree-registered :class:`Table` handle built
from a declarative :class:`~repro.core.spec.TableSpec`, with functional
methods

    ``lookup / insert / delete / update / apply / size / merge``

that (a) accept **any batch length** — short batches are NOP-padded, long
batches are chunked into ``n_lanes``-wide combining transactions under a
``lax.scan``; (b) thread cleanly through ``jit`` / ``scan`` / ``shard_map``
(the spec and mesh ride in the pytree aux data); and (c) route to the XLA
single-pass transaction, the Pallas fused kernels, or the distributed
combining transaction from **one dispatch point** (:func:`_local_fns` /
:func:`_raw_apply`), so resize actions and placement stay implementation
details exactly as in the source paper.

Value schemas (struct-of-slabs side store)
------------------------------------------
When ``spec.value_schema`` is set, each item's payload is a pytree of
fields living in per-field slab arrays ``[slab_rows + 1, *field_shape]``.
The core table keeps storing one i32 word per key — but that word becomes a
**handle**: a stable row index into the slabs. Handles are allocated from a
liveness bitmap at insert, travel with their key through splits / merges /
directory doubling (which therefore never touch payloads), and are freed by
delete. After every transaction the handle liveness is reconciled against a
post-transaction lookup of the batch keys, which makes the bookkeeping
correct under arbitrary intra-batch races (duplicate keys, insert/delete
mixes, frozen buckets): whatever handle the table maps a key to *after* the
transaction is live; every other handle touched by the batch is free.

Example::

    spec = TableSpec(dmax=10, n_lanes=16,
                     value_schema={"page": jnp.int32,
                                   "score": (jnp.float32, ())})
    t = Table.create(spec)
    t, res = t.insert(keys, {"page": pages, "score": scores})
    found, payload = t.lookup(keys)          # payload["page"], ...
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dist as D
from repro.core import table as T
from repro.core.policy import ResizePolicy, resize_pressure, wrap_apply_fn
from repro.core.spec import TableSpec, ValueField, normalize_schema  # noqa: F401 (re-export)
from repro.core.table import NOP, INS, DEL, BatchResult, OpBatch
# imported eagerly (not inside the dispatch functions): module import runs
# jnp constant construction, which must never happen mid-trace
from repro.kernels import ops as kops

__all__ = [
    "Table", "TableSpec", "ValueField", "ResizePolicy", "create",
    "NOP", "INS", "DEL", "BatchResult",
]


# ---------------------------------------------------------------------------
# backend dispatch (the one dispatch point)


def _local_fns(spec: TableSpec):
    """(lookup_fn, apply_fn) for the spec's **plan**, each (cfg, state, x).

    The spec resolved its :class:`~repro.kernels.plan.KernelPlan` once at
    construction (backend, fused-kernel selection, tile shapes, interpret
    override — env vars applied there and nowhere else); dispatch here is
    a pure function of that plan:

    ==============  ====================================================
    plan.backend    resolves to
    ==============  ====================================================
    xla             ``table.lookup`` / ``table.apply_batch`` (single-pass)
    pallas          Pallas kernels: the fully-fused apply + fused probe
                    where ``plan.fused_apply`` / ``plan.fused_lookup``
                    allow, grouped/unfused kernels beyond those bounds;
                    compiled on TPU, interpret mode elsewhere
    ==============  ====================================================
    """
    plan = spec.plan()
    if plan.backend == "xla":
        return T.lookup, T.apply_batch
    return (partial(kops.plan_lookup, plan),
            partial(kops.plan_apply, plan))


def _raw_lookup(spec: TableSpec, mesh, state, queries):
    """(found, i32 word) for any placement/backend; queries [m] (sharded:
    m divisible by the data-axis size — chunk sizes guarantee it)."""
    lookup_fn, _ = _local_fns(spec)
    if spec.placement == "sharded":
        return D.dist_lookup(spec.dist_config(), mesh, state, queries,
                             lookup_fn=lookup_fn)
    return lookup_fn(spec.table_config(), state, queries)


def _raw_apply(spec: TableSpec, mesh, state, ops: OpBatch):
    """One combining transaction for any placement/backend.

    ``spec.resize_policy`` composes onto the per-placement ``apply_fn``
    here — the facade's single wiring point: the policy's split/merge
    maintenance runs right after each transaction, on the local state for
    local placement and per shard inside the shard_map body for sharded
    placement (each shard elastically resizes its own key-space region).
    """
    _, apply_fn = _local_fns(spec)
    if spec.resize_policy is not None:
        apply_fn = wrap_apply_fn(spec.resize_policy, apply_fn)
    if spec.placement == "sharded":
        return D.dist_apply_batch(spec.dist_config(), mesh, state, ops,
                                  apply_fn=apply_fn)
    return apply_fn(spec.table_config(), state, ops)


# ---------------------------------------------------------------------------
# the handle


class Table:
    """Immutable table handle: state + (optional) payload slabs + spec.

    Registered as a pytree whose aux data is ``(spec, mesh)`` — a ``Table``
    is a legal ``jit`` argument, ``scan`` carry, and ``shard_map`` operand,
    and every method is functional (returns a fresh handle).
    """

    __slots__ = ("spec", "mesh", "state", "slabs", "slab_live", "seq")

    def __init__(self, spec: TableSpec, mesh, state, slabs, slab_live, seq):
        object.__setattr__(self, "spec", spec)
        object.__setattr__(self, "mesh", mesh)
        object.__setattr__(self, "state", state)
        object.__setattr__(self, "slabs", slabs)
        object.__setattr__(self, "slab_live", slab_live)
        object.__setattr__(self, "seq", seq)

    def __setattr__(self, name, value):
        raise AttributeError("Table is immutable; methods return new handles")

    def __repr__(self):
        fields = (tuple(f.name for f in self.spec.value_schema)
                  if self.spec.value_schema else "i32")
        return (f"Table(placement={self.spec.placement}, "
                f"backend={self.spec.backend}, dmax={self.spec.dmax}, "
                f"n_lanes={self.spec.n_lanes}, values={fields})")

    def plan(self):
        """The resolved :class:`~repro.kernels.plan.KernelPlan` this table
        dispatches with — backend, fused-kernel selection, tile shapes,
        interpret mode, autotune provenance. Resolved once at spec
        construction; environment changes after that do not affect a live
        table."""
        return self.spec.plan()

    # -- construction ------------------------------------------------------

    @classmethod
    def create(cls, spec: TableSpec, mesh=None) -> "Table":
        """Initialize an empty table for ``spec`` (eager; not jit-safe).

        Sharded placement requires ``mesh`` (or an ambient mesh from
        ``compat.set_mesh``) with the spec's data/model axes, built with
        ``Auto`` axis types (``compat.make_mesh``); the stacked
        per-shard states are placed P(model_axis), slabs replicated.
        """
        if spec.placement == "sharded":
            from repro import compat
            if mesh is None:
                mesh = compat.get_abstract_mesh()
            assert mesh is not None, "sharded placement needs a mesh"
            assert compat.is_auto_mesh(mesh), (
                "sharded placement needs a mesh with Auto axis types "
                "(repro.compat.make_mesh)")
            assert mesh.shape[spec.model_axis] == spec.n_shards, (
                f"mesh axis {spec.model_axis!r}={mesh.shape[spec.model_axis]}"
                f" != n_shards={spec.n_shards}")
            assert spec.n_lanes % mesh.shape[spec.data_axis] == 0, (
                "n_lanes must divide over the data axis")
            state = D.init_dist_table(spec.dist_config(), spec.n_lanes)
            state = jax.device_put(state, jax.tree.map(
                lambda _: jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec(spec.model_axis)),
                state))
        else:
            mesh = None
            state = T.init_table(spec.table_config())
        slabs = slab_live = None
        if spec.value_schema is not None:
            cap = spec.slab_rows
            slabs = {f.name: jnp.zeros((cap + 1,) + f.shape, jnp.dtype(f.dtype))
                     for f in spec.value_schema}
            # row `cap` is the write-trash row and is born (and stays) live
            slab_live = jnp.zeros(cap + 1, bool).at[cap].set(True)
        return cls(spec, mesh, state, slabs, slab_live, jnp.int32(0))

    def _replace(self, **kw) -> "Table":
        vals = {s: kw.get(s, getattr(self, s)) for s in Table.__slots__}
        return Table(**vals)

    @property
    def config(self) -> T.TableConfig:
        """The resolved local/per-shard TableConfig (tests, invariants)."""
        return self.spec.table_config()

    # -- reads -------------------------------------------------------------

    def lookup(self, keys):
        """Rule-A lookup, any batch length. Returns ``(found, values)``
        where values is the schema pytree (zeros where absent) or the raw
        i32 word (-1 where absent)."""
        return _lookup_jit(self, _as_i32(keys))

    def size(self):
        """Live item count (O(pool) read of the incremental counts; sums
        across shards for stacked sharded states)."""
        return T.table_size(self.state)

    def depth(self):
        """Logical directory depth (max over shards for sharded placement)
        — the observable the churn tests/benchmarks track to prove resizes
        actually happened."""
        return jnp.max(self.state.depth)

    def policy_stats(self):
        """Cumulative elastic-policy actions plus the live backpressure
        signal, as ``{"splits", "merges", "pressure"}``.

        ``splits``/``merges`` are summed over shards; reactive overflow
        splits are deliberately not counted. ``pressure`` is
        :func:`repro.core.policy.resize_pressure` — the fraction of live
        buckets within reach of a watermark (f32 in [0, 1]), which the
        serving router uses to shed/defer writes while resize work is
        imminent. All three are zeros when ``spec.resize_policy is
        None``."""
        totals = jnp.sum(jnp.reshape(self.state.policy_counts, (-1, 2)),
                         axis=0)
        pol = self.spec.resize_policy
        pressure = (resize_pressure(self.config, pol, self.state)
                    if pol is not None else jnp.float32(0.0))
        return {"splits": totals[0], "merges": totals[1],
                "pressure": pressure}

    # -- updates (functional: return (table', BatchResult)) ----------------

    def insert(self, keys, values=None):
        """Upsert ``keys`` (any batch length). ``values``: schema pytree of
        ``[m, *field_shape]`` leaves, or i32[m] (raw mode; default zeros).
        Status per lane: TRUE = newly inserted, FALSE = value updated."""
        keys = _as_i32(keys)
        values = _tree_arrays(values)
        return _insert_jit(self, keys, values)

    def delete(self, keys):
        """Delete ``keys``. Status TRUE = was present. Frees payload
        handles (schema mode)."""
        return _delete_jit(self, _as_i32(keys))

    def update(self, keys, values=None):
        """Write ``values`` only where the key is already present
        (insert-if-present). Status: FALSE where the key was absent.

        The presence test is a rule-A snapshot read taken before the
        transaction; within one call, duplicate keys resolve in lane order
        like every other batch."""
        keys = _as_i32(keys)
        found, _ = self.lookup(keys)
        kinds = jnp.where(found, INS, NOP).astype(jnp.int32)
        t2, res = self.apply(kinds, keys, values)
        status = jnp.where(found, res.status, jnp.int8(T.FALSE))
        return t2, BatchResult(status=status, error=res.error)

    def apply(self, kinds, keys, values=None):
        """Generic mixed batch of {NOP, INS, DEL} ops, any length ``m``.

        Pads to a multiple of ``n_lanes`` with NOP lanes and runs one
        combining transaction per chunk (``lax.scan`` when chunked).
        Returns ``(table', BatchResult)`` with ``status[m]``."""
        kinds = _as_i32(kinds)
        keys = _as_i32(keys)
        assert kinds.shape == keys.shape and kinds.ndim == 1, (
            kinds.shape, keys.shape)
        return _apply_jit(self, kinds, keys, _tree_arrays(values))

    def merge(self, parent_prefix, parent_depth):
        """Merge the two buddy buckets of a would-be parent (paper §4.5).
        Local placement only. Returns ``(table', ok)``; payload handles
        travel with their keys, so the slabs are untouched."""
        if self.spec.placement != "local":
            raise NotImplementedError(
                "merge is shard-local; run it per shard (placement='local')")
        st, ok = T.merge_buddies(self.config, self.state,
                                 parent_prefix, parent_depth)
        return self._replace(state=st), ok

    # -- durable images (core/snapshot.py; DESIGN.md §10) ------------------

    def save(self, path: str) -> str:
        """Serialize to a canonical, placement-independent image file.

        The image captures the logical content (items in logical-bucket
        order, payload fields resolved, frozen/tombstone lanes normalized)
        plus the cumulative policy counters under a versioned header —
        host-side work after one device_get; eager, not jit-safe. Returns
        ``path``."""
        from repro.core import snapshot
        return snapshot.save_table(self, path)

    @classmethod
    def restore(cls, path: str, spec: TableSpec, mesh=None) -> "Table":
        """Load an image into a fresh table built for ``spec``.

        ``spec`` may differ from the spec the image was saved under —
        local → sharded, sharded N → M shards, another backend or sizing —
        items re-route through the ordinary directory math (hash → shard →
        directory entry, reactive splits as needed). Infeasible targets
        (``dmax`` too shallow for the image's densest hash-prefix group,
        undersized slab store, mismatched value schema) raise
        ``ValueError`` before any device work. Sharded placement needs
        ``mesh`` exactly as :meth:`create` does."""
        from repro.core import snapshot
        return snapshot.restore_table(path, spec, mesh)


jax.tree_util.register_pytree_node(
    Table,
    lambda t: ((t.state, t.slabs, t.slab_live, t.seq), (t.spec, t.mesh)),
    lambda aux, ch: Table(aux[0], aux[1], ch[0], ch[1], ch[2], ch[3]),
)


def create(spec: TableSpec, mesh=None) -> Table:
    """Module-level alias of :meth:`Table.create`."""
    return Table.create(spec, mesh)


# ---------------------------------------------------------------------------
# implementation


def _as_i32(x):
    """i32 canonicalization without an eager device op on the hot path:
    jnp/tracer inputs pass through (cast at trace time if needed); host
    inputs become numpy (a legal jit leaf)."""
    if isinstance(x, jax.Array):
        return x if x.dtype == jnp.int32 else x.astype(jnp.int32)
    return np.asarray(x, np.int32)


def _leaf_array(v):
    return v if isinstance(v, (jax.Array, np.ndarray)) else np.asarray(v)


def _tree_arrays(values):
    """Arrayify payload leaves (python lists would retrace per element)."""
    if values is None:
        return None
    return {k: _leaf_array(v) for k, v in values.items()} \
        if isinstance(values, dict) else _leaf_array(values)


def _check_values(spec: TableSpec, m: int, values):
    """Normalize/validate per-op values against the spec's schema."""
    if spec.value_schema is None:
        if values is None:
            return jnp.zeros(m, jnp.int32)
        values = _as_i32(values)
        assert values.shape == (m,), (values.shape, m)
        return values
    if values is None:   # pure deletes/NOPs need no payload
        return {f.name: jnp.zeros((m,) + f.shape, jnp.dtype(f.dtype))
                for f in spec.value_schema}
    names = sorted(values)
    want = [f.name for f in spec.value_schema]
    assert names == want, f"schema fields {want}, got {names}"
    out = {}
    for f in spec.value_schema:
        leaf = jnp.asarray(values[f.name], jnp.dtype(f.dtype))
        assert leaf.shape == (m,) + f.shape, (f.name, leaf.shape, (m,) + f.shape)
        out[f.name] = leaf
    return out


def _pad_lanes(spec: TableSpec, kinds, keys, values):
    """NOP-pad to a whole number of ``n_lanes`` chunks."""
    n = spec.n_lanes
    m = kinds.shape[0]
    pad = -m % n
    if pad:
        kinds = jnp.pad(kinds, (0, pad))                 # NOP == 0
        keys = jnp.pad(keys, (0, pad))
        values = jax.tree.map(
            lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)),
            values)
    return kinds, keys, values


def _apply_chunk(spec: TableSpec, mesh, carry, kinds, keys, values):
    """One n_lanes-wide combining transaction (+ slab maintenance).

    carry = (state, slabs, slab_live, seq). Returns (carry', status).
    """
    state, slabs, slab_live, seq = carry
    n = spec.n_lanes
    seq1 = seq + 1
    seqs = jnp.full((n,), seq1, jnp.int32)

    if spec.value_schema is None:
        ops = OpBatch(kind=kinds, key=keys, value=values, seq=seqs)
        st2, res = _raw_apply(spec, mesh, state, ops)
        return (st2, slabs, slab_live, seq1), res.status

    # ---- schema mode: allocate handles, write payload, reconcile --------
    cap = spec.slab_rows
    lane = jnp.arange(n, dtype=jnp.int32)
    found0, h0 = _raw_lookup(spec, mesh, state, keys)
    is_ins = kinds == INS
    same_key = keys[:, None] == keys[None, :]

    # fresh handles: one per distinct new key (first INS lane allocates;
    # later same-key INS lanes share it — last payload writer wins below)
    isn = is_ins & ~found0
    first = isn & ~(same_key & isn[None, :]
                    & (lane[None, :] < lane[:, None])).any(axis=1)
    free_rows = ~slab_live                     # row `cap` is always live
    csum = jnp.cumsum(free_rows.astype(jnp.int32))
    cum_first = jnp.cumsum(first.astype(jnp.int32))
    rows = jnp.clip(jnp.searchsorted(csum, cum_first), 0, cap)
    rows = jnp.where(first, rows, jnp.int32(cap)).astype(jnp.int32)
    exhausted = cum_first[-1] > csum[-1]
    # broadcast each first lane's row to its duplicate-key lanes. Masked-min
    # instead of a gather-by-lane-index: under GSPMD (sharded placement
    # inside scan) a gather whose indices derive from shard_map outputs has
    # been observed to pick up a spurious model-axis all-reduce (doubled
    # values); the elementwise/reduce form partitions correctly.
    handle_new = jnp.where(same_key & first[None, :], rows[None, :],
                           jnp.int32(cap)).min(axis=1)
    handle = jnp.where(is_ins & found0, h0,
                       jnp.where(isn, handle_new, jnp.int32(0)))

    ops = OpBatch(kind=kinds, key=keys, value=handle, seq=seqs)
    st2, res = _raw_apply(spec, mesh, state, ops)

    # payload scatter — AFTER the transaction, gated on its statuses: only
    # an INS that actually applied (TRUE/FALSE) writes; a FROZEN/OVERFLOW
    # upsert must leave the key's existing payload untouched (the table
    # reported the op as not executed). Among applied INS lanes of one key
    # only the LAST writes (upsert: intermediate values are unobservable
    # batch-internally); masked lanes land on the trash row.
    applied_ins = is_ins & ((res.status == jnp.int8(T.TRUE))
                            | (res.status == jnp.int8(T.FALSE)))
    later_ins = (same_key & applied_ins[None, :]
                 & (lane[None, :] > lane[:, None])).any(axis=1)
    write = applied_ins & ~later_ins
    rows_w = jnp.where(write, handle, jnp.int32(cap))
    slabs = {name: slab.at[rows_w].set(
        jnp.asarray(values[name], slab.dtype)) for name, slab in slabs.items()}

    # liveness reconciliation (post-transaction lookup is authoritative):
    # free every handle the batch touched, then re-mark whatever the table
    # still maps each key to — correct under any intra-batch interleaving
    found1, h1 = _raw_lookup(spec, mesh, st2, keys)
    dead_pre = jnp.where(found0, h0, jnp.int32(cap))
    dead_new = jnp.where(first, rows, jnp.int32(cap))
    live_now = jnp.where(found1, h1, jnp.int32(cap))
    slab_live = (slab_live.at[dead_pre].set(False)
                 .at[dead_new].set(False)
                 .at[live_now].set(True)
                 .at[cap].set(True))
    st2 = st2._replace(error=st2.error | exhausted)
    return (st2, slabs, slab_live, seq1), res.status


def _apply_impl(table: Table, kinds, keys, values):
    spec, mesh = table.spec, table.mesh
    m = kinds.shape[0]
    if m == 0:
        # empty batch: no transaction, no seq tick, no spurious scan chunk
        error = (table.state.error if spec.placement == "local"
                 else table.state.error.any())
        return table, BatchResult(status=jnp.zeros(0, jnp.int8), error=error)
    kinds, keys, values = _pad_lanes(spec, kinds, keys, values)
    n = spec.n_lanes
    k = kinds.shape[0] // n
    carry0 = (table.state, table.slabs, table.slab_live, table.seq)
    if k == 1:
        carry, status = _apply_chunk(spec, mesh, carry0, kinds, keys, values)
    else:
        def body(carry, xs):
            c_kinds, c_keys, c_values = xs
            carry, status = _apply_chunk(spec, mesh, carry, c_kinds, c_keys,
                                         c_values)
            return carry, status

        xs = (kinds.reshape(k, n), keys.reshape(k, n),
              jax.tree.map(lambda a: a.reshape((k, n) + a.shape[1:]), values))
        carry, status = jax.lax.scan(body, carry0, xs)
        status = status.reshape(-1)
    state, slabs, slab_live, seq = carry
    t2 = table._replace(state=state, slabs=slabs, slab_live=slab_live, seq=seq)
    error = state.error if spec.placement == "local" else state.error.any()
    if status.shape[0] != m:
        status = status[:m]
    return t2, BatchResult(status=status, error=error)


def _lookup_impl(table: Table, queries):
    """(found, values) for any batch length (see Table.lookup)."""
    spec, mesh = table.spec, table.mesh
    queries = jnp.asarray(queries, jnp.int32)
    m = queries.shape[0]
    if m == 0:
        found = jnp.zeros(0, bool)
        if spec.value_schema is None:
            return found, jnp.zeros(0, jnp.int32)
        return found, {f.name: jnp.zeros((0,) + f.shape, jnp.dtype(f.dtype))
                       for f in spec.value_schema}
    q = queries
    if spec.placement == "sharded":
        pad = -m % spec.n_lanes     # divisible over the data axis
        if pad:
            q = jnp.pad(q, (0, pad))
    found, word = _raw_lookup(spec, mesh, table.state, q)
    if found.shape[0] != m:
        found, word = found[:m], word[:m]
    if spec.value_schema is None:
        return found, word
    cap = spec.slab_rows
    h = jnp.clip(jnp.where(found, word, cap), 0, cap)
    out = {}
    for f in spec.value_schema:
        leaf = table.slabs[f.name][h]
        mask = found.reshape(found.shape + (1,) * len(f.shape))
        out[f.name] = jnp.where(mask, leaf, jnp.zeros((), leaf.dtype))
    return found, out


def _apply_checked(table: Table, kinds, keys, values):
    values = _check_values(table.spec, keys.shape[0], values)
    return _apply_impl(table, jnp.asarray(kinds, jnp.int32),
                       jnp.asarray(keys, jnp.int32), values)


def _insert_impl(table: Table, keys, values):
    kinds = jnp.full(keys.shape, INS, jnp.int32)
    return _apply_checked(table, kinds, keys, values)


def _delete_impl(table: Table, keys):
    kinds = jnp.full(keys.shape, DEL, jnp.int32)
    return _apply_checked(table, kinds, keys, None)


# jitted entry points: the handle's spec/mesh are pytree aux data, so they
# become part of the jit cache key automatically — one compilation per
# (spec, mesh, batch shape), reused across every Table carrying that spec.
# insert/delete get dedicated wrappers so a facade call is ONE jit dispatch
# (kind construction, padding, and validation all happen at trace time).
_apply_jit = jax.jit(_apply_checked)
_lookup_jit = jax.jit(_lookup_impl)
_insert_jit = jax.jit(_insert_impl)
_delete_jit = jax.jit(_delete_impl)
