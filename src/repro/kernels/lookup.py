"""Pallas TPU kernels: the sync-free bucket probe (design rule A hot path).

Lookups are the paper's most frequent operation; its design rule (A) demands
they run with zero synchronization. On TPU the probe is a *gather* problem:
query → pool row → B-way compare. Two kernels solve it in two ways.

**`probe`** — the routed probe. Its caller routes (hash → directory →
bucket id, in XLA), so the bucket ids exist before the grid starts and the
kernel fetches only the pool tiles that hold them, the pattern of the apply
kernels (kernels/apply.py). The pools sit in HBM with the bucket axis minor
(XLA lays a ``[P+1, B]`` array out as ``{0,1:T(8,128)}`` so that B=8 does
not pad to 128 lanes), so the kernel reads them through their transposed
view ``[B, P+1]``, a free bitcast; a row-major block would make XLA
transpose the whole pool into a 128-lane-padded copy on every call. One
``(B, 128)`` VMEM tile then holds the slots of 128 consecutive buckets.
Queries are stably sorted by tile; the step schedule (tile id, query range)
and each query's key and lane are scalar-prefetch operands in SMEM, and the
pool BlockSpecs' index maps read the step's tile id, so only touched tiles
stream through VMEM, double-buffered by the pipeline, and consecutive
queries on one tile fetch it once. The body walks its queries serially: a
lane mask picks the bucket's column, an equality compare the slot, and
found flag and value go to SMEM outputs; the wrapper un-permutes them. No
one-hot and no MXU. The pools go in whole, trash row included (the
directory never routes there).

Memory per launch of N ≤ 8192 queries (v5e: 1 MiB SMEM, 16 MiB default
scoped VMEM): SMEM (2·N + 3·S)·4 B of scalar prefetch (keys, lanes; tile
id, range start and end per step, S = min(tiles, N) steps) and 2·N·4 B of
outputs, ≤ 224 KiB at N = 8192; VMEM two pools × two buffers of one
(8, 128) int32 tile = 16 KiB at B = 8. Longer batches run in launches of
8192 queries.

**`fused_probe`** additionally fuses hash → directory-route into the
kernel, so its bucket ids do not exist before the grid starts and an index
map cannot read them: it sweeps the pool. The directory travels into VMEM
lane-dense as [2**dmax / 128, 128] (512 KiB at dmax=17) and the route is a
two-level gather — a one-hot MXU contraction picks the directory row, a
lane mask picks the entry — chunked over directory rows in a loop. The
probe is a **tiled one-hot contraction on the MXU**: a [TQ, PC] one-hot of
local bucket ids multiplied into the [PC, B] pool chunk materializes the
gathered rows in registers, with the grid tiling the (queries × pool)
space so each chunk's working set sits in VMEM. Exactly one pool chunk
contains a query's row, so per-chunk partial results combine by addition —
the kernel accumulates over the pool-chunk grid dimension. Queries, bucket
ids and results travel as [N, 1] columns (Mosaic has no 1-D vector layouts
worth using), so the per-query scalars broadcast along lanes. Directory
values must stay below 2**24 (exact fp32 integers); the wrapper asserts
this. For dmax > FUSED_DMAX_LIMIT callers fall back to the routed probe
(kernels/ops.py does).

VMEM budget of `fused_probe` (defaults TQ=256, PC=512, B=8, int32; a
[X, 1] or [X, 8] block pads to 128 lanes):
  queries + outs + ids  4·256·128·4   = 512 KiB
  pool    2·512·128·4·2               =   1 MiB (double-buffered)
  one-hot 256·512·4                   = 512 KiB (fp32 operand for the MXU)
  directory                           ≤ 512 KiB
→ ~2.5 MiB, well inside v5e's default scoped VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hashing import HASH_FNS
from repro.kernels.ref import EMPTY_KEY  # noqa: F401 (API re-export)

_EMPTY = -2147483648  # python int: kernels must not close over traced constants
_LANES = 128          # TPU vreg lane width: minor dim of the directory and pool tiles
_MAX_QUERIES = 8192   # routed-probe queries per launch: their scalars in SMEM


def tile_schedule(group, n_groups: int, n_steps: int):
    """Step schedule over the tiles that lanes name, for a kernel whose
    pool BlockSpec reads its tile id from scalar prefetch.

    ``group`` i32[M] is each lane's tile; ``n_groups`` marks an idle lane.
    Lanes are stably sorted by tile (lane order holds within a tile); step
    s visits tile ``gid[s]`` and walks sorted lanes ``lo[s]:hi[s]``. Spare
    steps (fewer touched tiles than ``n_steps``) revisit the last tile
    with an empty range. Returns (order, sorted group, valid, lo, hi, gid).
    """
    M = group.shape[0]
    order = jnp.argsort(group, stable=True)                      # keeps lanes
    gs = group[order]
    valid = gs < n_groups                          # a prefix after the sort
    n_valid = valid.sum().astype(jnp.int32)
    iota = jnp.arange(M, dtype=jnp.int32)
    is_start = valid & jnp.concatenate([jnp.ones(1, bool), gs[1:] != gs[:-1]])
    step = jnp.where(is_start, jnp.cumsum(is_start) - 1, n_steps)
    lo = jnp.full(n_steps, n_valid, jnp.int32).at[step].set(iota, mode="drop")
    hi = jnp.concatenate([lo[1:], n_valid[None]])
    last = jnp.minimum(gs[jnp.maximum(n_valid - 1, 0)], n_groups - 1)
    gid = jnp.full(n_steps, last, jnp.int32).at[step].set(gs, mode="drop")
    return order, gs, valid, lo, hi, gid


def _probe_kernel(gid_s, lo_s, hi_s, key_s, lane_s, pk_ref, pv_ref, found_ref,
                  val_ref):
    s = pl.program_id(0)
    keys = pk_ref[...]                                  # [B, 128] of one tile
    vals = pv_ref[...]
    lanes = jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1)

    def body(i, _):
        key = key_s[i]
        hit = (lanes == lane_s[i]) & (keys == key) & (key != _EMPTY)
        found_ref[i] = jnp.max(hit.astype(jnp.int32))
        val_ref[i] = jnp.sum(jnp.where(hit, vals, 0))
        return 0

    jax.lax.fori_loop(lo_s[s], hi_s[s], body, 0)


def _probe_launch(bucket_ids, queries, pk_t, pv_t, interpret: bool):
    """One launch over N ≤ _MAX_QUERIES queries and the transposed
    [B, P+1] pools. Returns (found i32[N], val i32[N]) in query order."""
    n = queries.shape[0]
    b, p1 = pk_t.shape
    n_tiles = pl.cdiv(p1, _LANES)
    steps = min(n_tiles, n)
    order, gs, _, lo, hi, gid = tile_schedule(bucket_ids // _LANES, n_tiles,
                                              steps)
    lane = bucket_ids[order] - gs * _LANES

    tile = pl.BlockSpec((b, _LANES), lambda s, gid, *_: (0, gid[s]))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    found, val = pl.pallas_call(
        _probe_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(steps,),
            in_specs=[tile, tile],
            out_specs=[smem, smem],
        ),
        out_shape=[jax.ShapeDtypeStruct((n,), jnp.int32)] * 2,
        interpret=interpret,
        name="probe",
    )(gid, lo, hi, queries[order], lane, pk_t, pv_t)

    def unsort(x):
        return jnp.zeros_like(x).at[order].set(x)

    return unsort(found), unsort(val)


@functools.partial(jax.jit, static_argnames=("interpret",))
def probe(bucket_ids: jnp.ndarray, queries: jnp.ndarray, pool_keys: jnp.ndarray,
          pool_vals: jnp.ndarray, *, interpret: bool = True):
    """Probe pool rows for `queries` routed to `bucket_ids`.

    pool_keys/pool_vals are [R, B] pools, the whole ``[P+1, B]`` state
    arrays included; only the tiles of 128 buckets that hold routed rows
    move. Returns (found bool[N], vals i32[N] with -1 for misses).
    """
    pk_t, pv_t = pool_keys.T, pool_vals.T
    n = queries.shape[0]
    if n <= _MAX_QUERIES:
        found, val = _probe_launch(bucket_ids, queries, pk_t, pv_t, interpret)
    else:
        pad = -n % _MAX_QUERIES

        def chunks(x):
            return jnp.pad(x, (0, pad)).reshape(-1, _MAX_QUERIES)

        found, val = jax.lax.map(
            lambda c: _probe_launch(*c, pk_t, pv_t, interpret),
            (chunks(bucket_ids), chunks(queries)))
        found, val = found.reshape(-1)[:n], val.reshape(-1)[:n]
    found = found > 0
    return found, jnp.where(found, val, -1)


# ---------------------------------------------------------------------------
# fused hash → directory-route → probe: the one-hot sweep


def _dot(a, b):
    """Exact fp32 contraction (one-hot operands: a single nonzero term)."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _gather32(onehot, x):
    """Rows of i32 ``x`` [PC, B] selected by ``onehot`` [TQ, PC] → [TQ, B].

    fp32 matmuls are exact only up to 2**24, so the payload is split into
    16-bit halves on int32 (arithmetic shift, then mask: both halves are
    non-negative and below 2**16) and recombined after two contractions."""
    hi = ((x >> 16) & 0xFFFF).astype(jnp.float32)
    lo = (x & 0xFFFF).astype(jnp.float32)
    ghi = _dot(onehot, hi).astype(jnp.int32)
    glo = _dot(onehot, lo).astype(jnp.int32)
    return (ghi << 16) | glo


def _probe_tile(q, b, pk_ref, pv_ref, found_ref, val_ref, j, pc: int):
    """Accumulate one pool chunk's hits for a query tile of `fused_probe`.

    ``q`` and ``b`` are [TQ, 1] columns. One-hot gather via the MXU:
    [TQ, PC] @ [PC, B] → [TQ, B]."""
    local = b - j * pc                                   # [TQ, 1]
    in_chunk = (local >= 0) & (local < pc)
    tq = q.shape[0]
    iota = jax.lax.broadcasted_iota(jnp.int32, (tq, pc), 1)
    onehot = (iota == local).astype(jnp.float32)         # 0-row off-chunk
    rows_k = _gather32(onehot, pk_ref[...])
    rows_v = _gather32(onehot, pv_ref[...])
    eq = in_chunk & (rows_k == q) & (q != _EMPTY)        # [TQ, B]
    found_ref[...] += jnp.max(eq.astype(jnp.int32), axis=1, keepdims=True)
    val_ref[...] += jnp.sum(jnp.where(eq, rows_v, 0), axis=1, keepdims=True)


def _pad_queries(x, n_pad, fill):
    return jnp.pad(x, (0, n_pad), constant_values=fill)[:, None]


def _pad_pool(pool_keys, pool_vals, pc):
    p_pad = -pool_keys.shape[0] % pc
    pk = jnp.pad(pool_keys, ((0, p_pad), (0, 0)), constant_values=EMPTY_KEY)
    pv = jnp.pad(pool_vals, ((0, p_pad), (0, 0)))
    return pk, pv


def _column_spec(tq: int):
    return pl.BlockSpec((tq, 1), lambda i, j: (i, 0))


# beyond this directory depth the directory block outgrows a comfortable
# VMEM slice (2**17 entries = 512 KiB) and callers should route in HBM
FUSED_DMAX_LIMIT = 17


def _hash_in_kernel(q, hash_name: str, hash_shift: int):
    """cfg.hash_fn inside the kernel: HASH_FNS are pure jnp ops over python
    constants, so the canonical implementations trace fine in a kernel body
    (hash_name/hash_shift arrive as static args)."""
    h = HASH_FNS[hash_name](q)
    if hash_shift:
        h = h << hash_shift
    return h


def _directory_shape(dcap: int):
    """Lane-dense [rows, lanes] view of a 2**dmax directory."""
    lanes = min(_LANES, dcap)
    return dcap // lanes, lanes


def _fused_probe_kernel(q_ref, dir_ref, pk_ref, pv_ref, found_ref, val_ref,
                        bid_ref, *, pc: int, rc: int, dmax: int,
                        hash_name: str, hash_shift: int):
    j = pl.program_id(1)

    # --- route: top-dmax hash bits → directory entry → bucket id ---------
    # Depends only on the query tile, so it runs once per tile (the pool
    # grid dim j is innermost — the bid scratch persists across j) and the
    # remaining pool chunks reuse the stashed ids. Entry e sits at
    # (e // lanes, e % lanes) of the lane-dense directory: a one-hot MXU
    # contraction gathers the row, RC directory rows per loop step, and a
    # lane mask picks the entry. Directory values < 2**24 are exact in fp32.
    @pl.when(j == 0)
    def _route():
        found_ref[...] = jnp.zeros_like(found_ref)
        val_ref[...] = jnp.zeros_like(val_ref)
        q = q_ref[...]                                    # [TQ, 1]
        tq = q.shape[0]
        rows, lanes = dir_ref.shape
        h = _hash_in_kernel(q, hash_name, hash_shift)
        e = (h >> jnp.uint32(32 - dmax)).astype(jnp.int32)
        shift = lanes.bit_length() - 1
        row = e >> shift
        lane = e & (lanes - 1)

        def chunk(c, acc):
            start = pl.multiple_of(c * rc, rc)
            d = dir_ref[pl.ds(start, rc), :].astype(jnp.float32)   # [RC, L]
            iota = jax.lax.broadcasted_iota(jnp.int32, (tq, rc), 1)
            onehot = (iota == row - start).astype(jnp.float32)
            return acc + _dot(onehot, d)

        g = jax.lax.fori_loop(0, rows // rc, chunk,
                              jnp.zeros((tq, lanes), jnp.float32))
        lane_iota = jax.lax.broadcasted_iota(jnp.int32, (tq, lanes), 1)
        b = jnp.sum(jnp.where(lane_iota == lane, g, 0.0), axis=1,
                    keepdims=True)
        bid_ref[...] = b.astype(jnp.int32)

    # --- probe: one-hot sweep, bucket ids from the scratch stash ---------
    _probe_tile(q_ref[...], bid_ref[...], pk_ref, pv_ref, found_ref, val_ref,
                j, pc)


@functools.partial(jax.jit, static_argnames=("dmax", "hash_name", "hash_shift",
                                             "tq", "pc", "dc", "interpret"))
def fused_probe(directory: jnp.ndarray, queries: jnp.ndarray,
                pool_keys: jnp.ndarray, pool_vals: jnp.ndarray, *, dmax: int,
                hash_name: str = "fmix32", hash_shift: int = 0, tq: int = 256,
                pc: int = 512, dc: int = 512, interpret: bool = True):
    """Single-kernel lookup: hash, directory route, and bucket probe fused.

    directory i32[2**dmax] travels whole into VMEM (lane-dense); bucket ids
    never touch HBM. ``dc`` is the route's chunk size in directory entries
    (rounded to whole, sublane-aligned directory rows). Returns
    (found bool[N], vals i32[N] with -1 for misses).
    """
    n = queries.shape[0]
    p = pool_keys.shape[0]
    dcap = directory.shape[0]
    assert dcap == 1 << dmax and dmax <= FUSED_DMAX_LIMIT
    assert p < (1 << 24), "bucket ids must be exact in fp32"
    rows, lanes = _directory_shape(dcap)
    rc = min(rows, max(8, dc // lanes))
    assert rows % rc == 0, (rows, rc)
    n_pad = -n % tq
    q = _pad_queries(queries, n_pad, EMPTY_KEY)
    pk, pv = _pad_pool(pool_keys, pool_vals, pc)
    b = pk.shape[1]
    grid = ((n + n_pad) // tq, pk.shape[0] // pc)

    found, val = pl.pallas_call(
        functools.partial(_fused_probe_kernel, pc=pc, rc=rc, dmax=dmax,
                          hash_name=hash_name, hash_shift=hash_shift),
        grid=grid,
        in_specs=[
            _column_spec(tq),                                # queries
            pl.BlockSpec((rows, lanes), lambda i, j: (0, 0)),  # directory
            pl.BlockSpec((pc, b), lambda i, j: (j, 0)),       # pool keys
            pl.BlockSpec((pc, b), lambda i, j: (j, 0)),       # pool vals
        ],
        out_specs=[_column_spec(tq), _column_spec(tq)],
        out_shape=[
            jax.ShapeDtypeStruct((n + n_pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((n + n_pad, 1), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((tq, 1), jnp.int32)],   # routed bucket ids
        interpret=interpret,
    )(q, directory.reshape(rows, lanes), pk, pv)
    found = found[:n, 0] > 0
    return found, jnp.where(found, val[:n, 0], -1)
