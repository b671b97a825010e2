"""Pallas TPU kernel: scalar-prefetch gather + fused squared-L2 distance.

This is the TPU-native form of the paper's dominant cost: fetching candidate
vectors from the slow tier during graph traversal (`d * t_v` in Eq. 7).  On
the paper's hardware that is a random 4 KB SSD read per neighbor; here it is
a data-dependent HBM->VMEM DMA selected by a prefetched neighbor id, with
the distance computation fused into the same pass so each fetched row is
touched exactly once (fetch+compute fusion — the kernel-level analogue of
DiskANN's "load only the best candidates").

Grid: (B,) — one program per query.  The table stays in HBM
(`memory_space=ANY`); each program reads its K candidate ids from the
scalar-prefetch operand and DMAs exactly those K rows into VMEM, one row
per copy (an int8 table comes in the 8-row groups HBM tiles it in, the
finest slice Mosaic takes) — all copies are started before the first
wait, so the DMA engine overlaps them.  The K distances are then one
reduction into the query's (K, 1) output block.  Rows are padded to a
multiple of 128 lanes.  Filtered-out candidates (id < 0) are redirected to
row 0 and masked to +inf afterwards — the DMA still happens but its result
is ignored.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _row_group(dtype) -> int:
    """Rows per DMA: exactly the candidate's row for 32-bit tables.  HBM
    tiles packed types in 8-row groups and Mosaic slices no finer, so an
    int8 row comes with the 7 others of its group (1 KiB at d = 128)."""
    return 1 if jnp.dtype(dtype).itemsize == 4 else 8


def _fetch_rows(ids_ref, table_ref, rows_ref, sems) -> None:
    """DMA the K row groups this program's query names (HBM -> VMEM
    [K, g, d]), one group per copy: start every copy, then wait on each."""
    i = pl.program_id(0)
    k, g, _ = rows_ref.shape

    def copy(j):
        first = pl.multiple_of(ids_ref[i * k + j] // g * g, g)
        return pltpu.make_async_copy(table_ref.at[pl.ds(first, g)],
                                     rows_ref.at[j], sems.at[j])

    def start(j, c):
        copy(j).start()
        return c

    def wait(j, c):
        copy(j).wait()
        return c

    jax.lax.fori_loop(0, k, start, 0)
    jax.lax.fori_loop(0, k, wait, 0)


def _row_dists(q_ref, rows: jax.Array, pos_ref, o_ref) -> None:
    """Squared L2 of the query to each fetched row [K, g, d], keeping the
    row at `pos` within each group (an exact select) -> (K, 1) block."""
    q = q_ref[0].astype(jnp.float32)                    # [1, d]
    diff = q[None] - rows                               # [K, g, d]
    d = jnp.sum(diff * diff, axis=2)                    # [K, g]
    sel = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1) == pos_ref[0]
    o_ref[0] = jnp.sum(jnp.where(sel, d, 0.0), axis=1, keepdims=True)


def _gather_l2_kernel(ids_ref, q_ref, table_ref, pos_ref, o_ref, rows_ref,
                      sems):
    _fetch_rows(ids_ref, table_ref, rows_ref, sems)
    _row_dists(q_ref, rows_ref[...].astype(jnp.float32), pos_ref, o_ref)


def _gather_call(kernel, b: int, k: int, d: int, dtype, n_side: int,
                 interpret: bool):
    """pallas_call over grid (B,): ids prefetched to SMEM, the query
    block, the table left in HBM, `n_side` per-query (K, 1) side blocks,
    and the query's (K, 1) output block."""
    per_query_col = pl.BlockSpec((1, k, 1), lambda i, ids_ref: (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, 1, d), lambda i, ids_ref: (i, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)]
                 + [per_query_col] * n_side,
        out_specs=per_query_col,
        scratch_shapes=[pltpu.VMEM((k, _row_group(dtype), d), dtype),
                        pltpu.SemaphoreType.DMA((k,))],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, k, 1), jnp.float32),
        interpret=interpret)


def _prefetch_ids(ids: jax.Array, dtype):
    """Flat ids for scalar prefetch (sentinels redirected to row 0) and
    each id's row position inside its DMA group, as (B, K, 1)."""
    safe = jnp.maximum(ids, 0)
    b, k = ids.shape
    return safe, safe.reshape(-1), (safe % _row_group(dtype)).reshape(b, k, 1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_l2_pallas(queries: jax.Array, table: jax.Array, ids: jax.Array,
                     *, interpret: bool = False) -> jax.Array:
    """queries [B, d], table [N, d], ids int32[B, K] -> f32[B, K].

    d must be a multiple of 128 (callers pad; `ops.py` handles it).
    """
    b, d = queries.shape
    _, k = ids.shape
    assert d % 128 == 0, "pad dim to a lane multiple"

    _, flat_ids, pos = _prefetch_ids(ids, table.dtype)
    out = _gather_call(_gather_l2_kernel, b, k, d, table.dtype, 1,
                       interpret)(flat_ids, queries.reshape(b, 1, d), table,
                                  pos)
    return jnp.where(ids >= 0, out[..., 0], jnp.inf)


def _gather_l2_q8_kernel(ids_ref, q_ref, table_ref, pos_ref, s_ref, o_ref,
                         rows_ref, sems):
    # Dequantize in-register: the int8 rows arrive by the same DMAs, so
    # reconstruction fuses with the distance pass — the cold lane never
    # materializes an f32 row in HBM.  The K per-row scales are
    # pre-gathered per query into a (K, 1) block.
    _fetch_rows(ids_ref, table_ref, rows_ref, sems)
    rows = rows_ref[...].astype(jnp.float32) * s_ref[0][:, :, None]
    _row_dists(q_ref, rows, pos_ref, o_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_l2_q8_pallas(queries: jax.Array, qtable: jax.Array,
                        scales: jax.Array, ids: jax.Array,
                        *, interpret: bool = False) -> jax.Array:
    """Cold-lane gather: queries [B, d], qtable int8[N, d], scales f32[N],
    ids int32[B, K] -> f32[B, K].  Same grid/prefetch structure as
    `gather_l2_pallas`; the K per-row scales of each query are gathered
    up front into a (K, 1) block.
    """
    b, d = queries.shape
    _, k = ids.shape
    assert d % 128 == 0, "pad dim to a lane multiple"

    safe, flat_ids, pos = _prefetch_ids(ids, qtable.dtype)
    # the last row group must lie inside the table
    tail = -qtable.shape[0] % _row_group(qtable.dtype)
    if tail:
        qtable = jnp.pad(qtable, ((0, tail), (0, 0)))
    out = _gather_call(_gather_l2_q8_kernel, b, k, d, qtable.dtype, 2,
                       interpret)(flat_ids, queries.reshape(b, 1, d), qtable,
                                  pos, scales[safe].reshape(b, k, 1))
    return jnp.where(ids >= 0, out[..., 0], jnp.inf)
