"""Jit'd public wrappers over the Pallas kernels + table integration.

`plan_lookup` / `plan_apply` are the plan-driven entry points: the facade
resolves a :class:`~repro.kernels.plan.KernelPlan` once per ``TableSpec``
(kernels/plan.py) and partials it in here — no env vars or registry reads
on the hot path. `apply_batch_fused` runs the whole write transaction in
ONE kernel launch (XLA route + frozen check, then probe → slot-assign →
write-back over the touched 8-row pool tiles; kernels/apply.py);
`apply_batch_kernel` is the grouped streaming combiner used for
geometries outside the fused bounds. Both mirror the
paper's fast (ApplyWFOp) / slow (ResizeWF) structure: ops reported ST_FULL
re-enter the reference transaction, which splits.

`table_lookup` / `table_apply` are the legacy auto-dispatchers (pre-plan);
they now answer from a default-constructed plan and remain only for direct
callers and benchmarks — the facade threads plans explicitly.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import table as T
from repro.core.hashing import dir_index
from repro.kernels import apply as kapply
from repro.kernels import lookup as klookup
from repro.kernels.plan import (KernelPlan, force_interpret,  # noqa: F401
                                fused_lookup_supported)
from repro.kernels.ref import ST_FROZEN, ST_FULL
from repro.kernels.tuning import clamp_tiles, pick_tiles, tile_key


def _backend() -> str:
    return jax.default_backend()


def _force_interpret() -> bool:
    """Deprecated alias — env policy lives in kernels/plan.py now."""
    return force_interpret()


def default_interpret() -> bool:
    """Pallas TPU kernels need interpret mode on any non-TPU backend."""
    return _backend() != "tpu"


def kernels_are_default() -> bool:
    """Kernels are the default hot path only where they compile natively
    (or when REPRO_FORCE_INTERPRET pins them for CPU CI coverage)."""
    return _backend() == "tpu" or force_interpret()


# ---------------------------------------------------------------------------
# lookup


@partial(jax.jit, static_argnames=("cfg", "fused", "interpret", "tq", "pc",
                                   "dc"))
def _kernel_lookup_impl(cfg: T.TableConfig, state: T.TableState, queries, *,
                        fused: bool, tq: int, pc: int, dc: int,
                        interpret: bool):
    if fused:
        return klookup.fused_probe(
            state.directory, queries, state.keys[:-1], state.vals[:-1],
            dmax=cfg.dmax, hash_name=cfg.hash_name, hash_shift=cfg.hash_shift,
            tq=tq, pc=pc, dc=dc, interpret=interpret)
    h = cfg.hash_fn(queries)
    bid = state.directory[dir_index(h, cfg.dmax)]
    # the whole pools: only the tiles holding routed rows move, and the
    # directory never routes to the trash row
    return klookup.probe(bid, queries, state.keys, state.vals,
                         interpret=interpret)


def kernel_lookup(cfg: T.TableConfig, state: T.TableState, queries, *,
                  interpret: bool | None = None):
    """Rule-A lookup through the Pallas kernels (plan-less convenience).

    Fused hash→route→probe when the directory fits VMEM (the common case:
    dmax ≤ 17); otherwise the route runs in HBM and only the probe is a
    kernel, which fetches just the pool tiles the routed rows sit in; the
    tiles below shape the fused probe only. Tiles resolve at every eager
    call (registry/env updates take effect immediately — they become
    static args of the inner jit); the facade's plan path
    (:func:`plan_lookup`) resolves them once instead."""
    interpret = default_interpret() if interpret is None else interpret
    tiles = pick_tiles(queries.shape[0], cfg.pool_size, cfg.dcap,
                       key=tile_key("lookup", dmax=cfg.dmax,
                                    pool_size=cfg.pool_size,
                                    n_lanes=max(cfg.n_lanes, 8)))
    return _kernel_lookup_impl(
        cfg, state, queries,
        fused=fused_lookup_supported(cfg.dmax, cfg.pool_size), tq=tiles.tq,
        pc=tiles.pc, dc=tiles.dc, interpret=interpret)


# ---------------------------------------------------------------------------
# apply: grouped (streaming) kernel transaction


@partial(jax.jit, static_argnames=("cfg", "interpret", "pc"),
         donate_argnums=1)
def _apply_batch_kernel_impl(cfg: T.TableConfig, state: T.TableState,
                             ops: T.OpBatch, *, pc: int, interpret: bool):
    fresh = (ops.kind != T.NOP) & (ops.seq > state.applied_seq)
    replay = (ops.kind != T.NOP) & ~fresh

    h = cfg.hash_fn(ops.key)
    bid = state.directory[dir_index(h, cfg.dmax)]
    # frozen buckets block every update (paper §4.5; the grouped kernel is
    # freeze-oblivious): complete those ops here with status FROZEN
    frozen_hit = fresh & state.frozen[bid]
    live = fresh & ~frozen_hit
    kinds = jnp.where(live, ops.kind, 0)
    # lanes go in lane order: the kernel's stable sort by pool chunk keeps
    # it within every bucket (the linearization order)
    pk, pv, status = kapply.grouped_apply(
        kinds, ops.key, ops.value, bid, state.keys[:-1], state.vals[:-1],
        pc=pc, interpret=interpret)

    applied = live & (status != ST_FULL)
    hit = applied & (status == jnp.int8(T.TRUE))
    delta = jnp.where(hit & (ops.kind == T.INS), 1, 0) \
        - jnp.where(hit & (ops.kind == T.DEL), 1, 0)
    counts = state.counts.at[
        jnp.where(applied, bid, jnp.int32(cfg.pool_size))].add(delta)
    counts = counts.at[cfg.pool_size].set(0)

    st = state._replace(
        keys=state.keys.at[:-1].set(pk),
        vals=state.vals.at[:-1].set(pv),
        counts=counts,
        applied_seq=jnp.where(applied | frozen_hit, ops.seq,
                              state.applied_seq),
    )
    return _finish_kernel_apply(cfg, st, ops, status.astype(jnp.int8),
                                live, frozen_hit, replay)


def _finish_kernel_apply(cfg, st, ops, status, live, frozen_hit, replay):
    """Shared tail of both kernel transactions: the ST_FULL slow path and
    the replay/frozen/NOP status overlays.

    Only ops that hit a full bucket re-enter the reference transaction
    (which runs the bounded split rounds — the ResizeWF slow path);
    everyone else is masked to NOP."""
    need_slow = live & (status == ST_FULL)
    slow_ops = T.OpBatch(
        kind=jnp.where(need_slow, ops.kind, T.NOP),
        key=ops.key, value=ops.value, seq=ops.seq)

    def run_slow(st):
        st2, res2 = T.apply_batch(cfg, st, slow_ops)
        return st2, res2.status

    def skip(st):
        return st, status

    st, slow_status = jax.lax.cond(need_slow.any(), run_slow, skip, st)
    final = jnp.where(need_slow, slow_status, status).astype(jnp.int8)
    final = jnp.where(frozen_hit, jnp.int8(T.FROZEN), final)
    final = jnp.where(replay, st.last_status, final)
    final = jnp.where(ops.kind == T.NOP, st.last_status, final)
    st = st._replace(last_status=final)
    return st, T.BatchResult(status=final, error=st.error)


def apply_batch_kernel(cfg: T.TableConfig, state: T.TableState, ops: T.OpBatch,
                       *, interpret: bool | None = None):
    """Fast-path combining transaction via the grouped Pallas apply kernel.

    1. route ops through the directory (announce); frozen-bucket ops
       complete with FROZEN (this kernel is freeze-oblivious);
    2. kernel combiner applies everything that fits (sorted by bucket, lane);
    3. ops reported ST_FULL fall back to the reference transaction, which
       runs the bounded split rounds (the ResizeWF slow path).

    The incremental occupancy counts are maintained from the kernel's
    status codes (TRUE = net ±1 for insert/delete) — no pool recount.
    Tiles resolve at every eager call (see kernel_lookup on staleness).
    """
    interpret = default_interpret() if interpret is None else interpret
    tiles = pick_tiles(cfg.n_lanes, cfg.pool_size,
                       key=tile_key("apply", dmax=cfg.dmax,
                                    pool_size=cfg.pool_size,
                                    n_lanes=max(cfg.n_lanes, 8)))
    return _apply_batch_kernel_impl(cfg, state, ops, pc=tiles.pc,
                                    interpret=interpret)


# ---------------------------------------------------------------------------
# apply: fully-fused single-launch transaction


@partial(jax.jit, static_argnames=("cfg", "interpret"), donate_argnums=1)
def _apply_batch_fused_impl(cfg: T.TableConfig, state: T.TableState,
                            ops: T.OpBatch, *, interpret: bool):
    fresh = (ops.kind != T.NOP) & (ops.seq > state.applied_seq)
    replay = (ops.kind != T.NOP) & ~fresh
    kinds = jnp.where(fresh, ops.kind, T.NOP)

    pk, pv, status, bid = kapply.fused_apply(
        state.directory, state.frozen, kinds, ops.key, ops.value,
        state.keys, state.vals, dmax=cfg.dmax, hash_name=cfg.hash_name,
        hash_shift=cfg.hash_shift, interpret=interpret)

    # the kernel completes frozen-destination ops in-kernel (ST_FROZEN ==
    # table.FROZEN); everything else mirrors the grouped wrapper
    frozen_hit = fresh & (status == ST_FROZEN)
    live = fresh & ~frozen_hit
    applied = live & (status != ST_FULL)
    hit = applied & (status == T.TRUE)
    delta = jnp.where(hit & (ops.kind == T.INS), 1, 0) \
        - jnp.where(hit & (ops.kind == T.DEL), 1, 0)
    counts = state.counts.at[
        jnp.where(applied, bid, jnp.int32(cfg.pool_size))].add(delta)
    counts = counts.at[cfg.pool_size].set(0)

    st = state._replace(
        keys=pk, vals=pv, counts=counts,
        applied_seq=jnp.where(applied | frozen_hit, ops.seq,
                              state.applied_seq),
    )
    return _finish_kernel_apply(cfg, st, ops, status.astype(jnp.int8),
                                live, frozen_hit, replay)


def apply_batch_fused(cfg: T.TableConfig, state: T.TableState, ops: T.OpBatch,
                      *, interpret: bool | None = None):
    """The fully-fused combining transaction: ONE kernel launch for the
    whole fast path (kernels/apply.py ``fused_apply``), with the same
    ST_FULL → reference-transaction slow path as the grouped kernel.

    Requires the plan layer's fused-apply geometry bounds
    (``plan.fused_apply_supported``); callers outside them should use
    :func:`apply_batch_kernel`.
    """
    interpret = default_interpret() if interpret is None else interpret
    return _apply_batch_fused_impl(cfg, state, ops, interpret=interpret)


# ---------------------------------------------------------------------------
# plan-driven entry points (the facade's dispatch target)


def plan_lookup(plan: KernelPlan, cfg: T.TableConfig, state: T.TableState,
                queries):
    """Rule-A lookup under a resolved plan: no env/registry reads here."""
    if plan.backend == "xla":
        return T.lookup(cfg, state, queries)
    t = clamp_tiles(plan.lookup_tiles, queries.shape[0], cfg.pool_size,
                    cfg.dcap)
    return _kernel_lookup_impl(cfg, state, queries, fused=plan.fused_lookup,
                               tq=t.tq, pc=t.pc, dc=t.dc,
                               interpret=plan.interpret)


def plan_apply(plan: KernelPlan, cfg: T.TableConfig, state: T.TableState,
               ops: T.OpBatch):
    """Combining transaction under a resolved plan: the fused single-launch
    kernel where the plan allows, else the grouped kernel, else XLA."""
    if plan.backend == "xla":
        return T.apply_batch(cfg, state, ops)
    if plan.fused_apply:
        return _apply_batch_fused_impl(cfg, state, ops,
                                       interpret=plan.interpret)
    t = clamp_tiles(plan.apply_tiles, cfg.n_lanes, cfg.pool_size)
    return _apply_batch_kernel_impl(cfg, state, ops, pc=t.pc,
                                    interpret=plan.interpret)


# ---------------------------------------------------------------------------
# legacy auto-dispatchers (pre-plan surface; benchmarks + direct callers)


def table_lookup(cfg: T.TableConfig, state: T.TableState, queries, *,
                 use_kernels: bool | None = None,
                 interpret: bool | None = None):
    """Rule-A lookup: Pallas fused kernel on TPU, XLA gather elsewhere.

    Legacy entry point — prefer a spec-resolved plan (``Table.plan()``)
    with :func:`plan_lookup`."""
    if use_kernels is None:
        use_kernels = kernels_are_default()
    if use_kernels:
        return kernel_lookup(cfg, state, queries, interpret=interpret)
    return T.lookup(cfg, state, queries)


def table_apply(cfg: T.TableConfig, state: T.TableState, ops: T.OpBatch, *,
                use_kernels: bool | None = None,
                interpret: bool | None = None):
    """Combining transaction: Pallas kernel combiner on TPU, the XLA
    single-pass transaction elsewhere.

    Legacy entry point — prefer a spec-resolved plan (``Table.plan()``)
    with :func:`plan_apply`."""
    if use_kernels is None:
        use_kernels = kernels_are_default()
    if use_kernels:
        return apply_batch_kernel(cfg, state, ops, interpret=interpret)
    return T.apply_batch(cfg, state, ops)
