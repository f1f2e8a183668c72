"""Pallas TPU kernel: the combining apply (PSim hot path), two launchers.

One kernel (`_apply_kernel`) applies a batch of ops to the pool chunks
they touch. Its per-op scalars (kinds, keys, values, rows) and the per-step
chunk ids and op ranges are scalar-prefetch operands, which land in SMEM
before the body runs: the combiner walks its ops serially (the combiner IS
serial in PSim) and reads each op's scalars from SMEM, while each op's
bucket-row update is a vectorized B-lane op on VMEM. Statuses go back
through an SMEM output.

Ops are stably sorted by pool chunk (PC rows), which keeps lane order
within every bucket — the linearization order. The grid has one step per
chunk that holds ops (at most min(G, M) steps; spare steps revisit the
last chunk with an empty op range), and the pool BlockSpec's index map
reads the step's chunk id from SMEM, so only touched chunks stream through
VMEM, double-buffered by the Pallas pipeline: chunk s+1 streams in while
chunk s combines. Design rule (B) is structural: chunks never touch each
other's rows. Dynamic row addressing uses `pl.ds` dynamic slices
(TPU-legal, unlike gathers). The pool blocks are aliased in/out, so the
"install" is an in-place VMEM update of the chunk — the CAS-free analogue
of PSim's pointer swap — and later ops of a bucket read earlier ops' writes.

**`grouped_apply`** — the streaming combiner over ``[P, B]`` pools with
tunable chunks (PC=512 by default); its caller routes and masks frozen
destinations (kernels/ops.py).

**`fused_apply`** — the whole write transaction in one launch: the XLA
prologue routes (hash → directory entry → bucket) and applies the frozen
check, and the kernel runs over the full ``[P+1, B]`` pools (trash row
included) in chunks of one (8, 128) tile: 8 bucket rows. Per transaction
that moves O(n_lanes·8·B) pool words instead of O(P·B). Manual per-row DMA
is not an option: Mosaic refuses a row slice of an HBM array whose minor
dimension (B=8) is narrower than a 128-lane tile.

The kernel never resizes: ops that hit a full bucket report ST_FULL and
are left for the outer split pass (the paper's FAIL → ResizeWF slow path).
`fused_apply` also completes frozen-bucket ops with ST_FROZEN (paper §4.5).

Memory (v5e: 1 MiB SMEM, 16 MiB default scoped VMEM; an [X, 8] block pads
to 128 lanes): SMEM 7·M·4 B + 3·S·4 B (≤ 20 KiB at M=512); VMEM pool
chunks in+out, double-buffered, 8·PC·128·4 B = 2 MiB at PC=512 and 32 KiB
at PC=8. The plan layer (kernels/plan.py) enforces the lane bound.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hashing import dir_index
from repro.kernels.lookup import _hash_in_kernel, tile_schedule
from repro.kernels.ref import (EMPTY_KEY, ST_FROZEN, ST_FULL,  # noqa: F401
                               ST_IDLE)

_EMPTY = -2147483648  # python int: kernels must not close over traced constants
FUSED_CHUNK = 8       # fused_apply's pool chunk: one (8, 128) VMEM tile


def _apply_kernel(gid_s, lo_s, hi_s, kind_s, key_s, val_s, row_s, pk_in,
                  pv_in, pk_ref, pv_ref, status_ref, *, bsize: int):
    s = pl.program_id(0)

    # the pool chunk travels through aliased in/out blocks; copy it in on
    # the step that first visits it (spare steps revisit the last chunk,
    # whose output block is still resident and holds this call's writes)
    @pl.when((s == 0) | (gid_s[s] != gid_s[jnp.maximum(s - 1, 0)]))
    def _copy_in():
        pk_ref[...] = pk_in[...]
        pv_ref[...] = pv_in[...]

    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, bsize), 1)

    def body(i, _):
        kind = kind_s[i]
        key = key_s[i]
        value = val_s[i]
        local = row_s[i]
        row_k = pk_ref[pl.ds(local, 1), :]                  # [1, B]
        row_v = pv_ref[pl.ds(local, 1), :]
        occ = row_k != _EMPTY
        full = jnp.min(occ.astype(jnp.int32)) > 0
        eq = row_k == key
        exist = jnp.max(eq.astype(jnp.int32)) > 0
        slot_eq = jnp.sum(jnp.where(eq, lanes, 0))
        slot_free = jnp.min(jnp.where(occ, bsize, lanes))

        is_ins = kind == 1
        is_del = kind == 2
        active = is_ins | is_del
        blocked = active & full
        do_write = active & ~full & (is_ins | exist)
        slot = jnp.where(is_ins, jnp.where(exist, slot_eq, slot_free), slot_eq)
        sel = (lanes == slot) & do_write
        pk_ref[pl.ds(local, 1), :] = jnp.where(
            sel, jnp.where(is_ins, key, _EMPTY), row_k)
        pv_ref[pl.ds(local, 1), :] = jnp.where(
            sel, jnp.where(is_ins, value, 0), row_v)

        st = jnp.where(is_ins, (~exist).astype(jnp.int32),
                       exist.astype(jnp.int32))
        st = jnp.where(blocked, ST_FULL, st)
        status_ref[i] = jnp.where(active, st, ST_IDLE)
        return 0

    jax.lax.fori_loop(lo_s[s], hi_s[s], body, 0)


def _launch(kinds, keys, values, bucket_ids, pool_keys, pool_vals, *, pc: int,
            interpret: bool):
    """Sort ops by chunk, build the step schedule, run the kernel over the
    touched chunks, unscatter statuses. Returns (pk', pv', status i32[M])
    with ST_IDLE for idle lanes."""
    M = kinds.shape[0]
    P, B = pool_keys.shape
    p_pad = -P % pc
    pk, pv = pool_keys, pool_vals
    if p_pad:
        pk = jnp.pad(pk, ((0, p_pad), (0, 0)), constant_values=EMPTY_KEY)
        pv = jnp.pad(pv, ((0, p_pad), (0, 0)))
    G = (P + p_pad) // pc
    S = min(G, M)                                  # grid steps

    group = jnp.where(kinds != 0, bucket_ids // pc, G)           # G = idle
    order, gs, valid, lo, hi, gid = tile_schedule(group, G, S)
    rows = jnp.where(valid, bucket_ids[order] - gs * pc, 0)

    pool_spec = pl.BlockSpec((pc, B), lambda s, gid, *_: (gid[s], 0))
    pk, pv, st_sorted = pl.pallas_call(
        functools.partial(_apply_kernel, bsize=B),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(S,),
            in_specs=[pool_spec, pool_spec],
            out_specs=[pool_spec, pool_spec,
                       pl.BlockSpec(memory_space=pltpu.SMEM)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(pk.shape, jnp.int32),
            jax.ShapeDtypeStruct(pv.shape, jnp.int32),
            jax.ShapeDtypeStruct((M,), jnp.int32),
        ],
        input_output_aliases={7: 0, 8: 1},
        interpret=interpret,
    )(gid, lo, hi, kinds[order], keys[order], values[order], rows, pk, pv)

    if p_pad:
        pk, pv = pk[:P], pv[:P]
    st_sorted = jnp.where(valid, st_sorted, ST_IDLE)
    status = jnp.full(M, ST_IDLE, jnp.int32).at[order].set(st_sorted)
    return pk, pv, status


@functools.partial(jax.jit, static_argnames=("pc", "interpret"))
def grouped_apply(kinds, keys, values, bucket_ids, pool_keys, pool_vals, *,
                  pc: int = 512, interpret: bool = True):
    """Combining apply of ops in (bucket, lane) order within each bucket.

    kinds i32[M] (0=idle, 1=insert/upsert, 2=delete), keys/values i32[M],
    bucket_ids i32[M] rows of the [P, B] pools. Returns
    (pool_keys', pool_vals', status i8[M]).
    """
    pk, pv, status = _launch(kinds, keys, values, bucket_ids, pool_keys,
                             pool_vals, pc=pc, interpret=interpret)
    return pk, pv, status.astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("dmax", "hash_name",
                                             "hash_shift", "interpret"))
def fused_apply(directory, frozen, kinds, keys, values, pool_keys, pool_vals,
                *, dmax: int, hash_name: str = "fmix32", hash_shift: int = 0,
                interpret: bool = True):
    """The combining write transaction, one kernel launch.

    directory i32[2**dmax] and frozen bool[P+1] are read by the XLA
    prologue (route + frozen check); pool_keys/pool_vals are the FULL
    [P+1, B] pools (trash row included), of which only the 8-row tiles
    holding routed buckets move. kinds i32[N] (0=idle, 1=insert/upsert,
    2=delete), keys/values i32[N].

    Returns (pool_keys', pool_vals', status i32[N], bucket_ids i32[N]) with
    status in {ST_TRUE, ST_FALSE, ST_FULL, ST_FROZEN, ST_IDLE}. Geometry
    limits are the plan layer's ``fused_apply_supported`` bounds; this
    wrapper asserts them (they are trace-time shapes).
    """
    from repro.kernels.plan import fused_apply_supported

    n = kinds.shape[0]
    p1, b = pool_keys.shape
    assert directory.shape[0] == 1 << dmax, (directory.shape, dmax)
    assert frozen.shape == (p1,), (frozen.shape, p1)
    assert fused_apply_supported(dmax, p1 - 1, n), \
        f"geometry outside fused-apply bounds: dmax={dmax} P={p1 - 1} n={n} B={b}"

    bid = directory[dir_index(_hash_in_kernel(keys, hash_name, hash_shift),
                              dmax)]
    frozen_hit = (kinds != 0) & frozen[bid]
    pk, pv, status = _launch(jnp.where(frozen_hit, 0, kinds), keys, values,
                             bid, pool_keys, pool_vals, pc=FUSED_CHUNK,
                             interpret=interpret)
    return pk, pv, jnp.where(frozen_hit, ST_FROZEN, status), bid
