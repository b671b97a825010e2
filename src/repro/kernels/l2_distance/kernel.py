"""Pallas TPU kernel: tiled batched squared-L2 distance.

The distance-computation phase of ANN search (Fig. 1 of the paper).  On TPU
the ||q||^2 + ||c||^2 - 2 q.c^T decomposition turns the bulk of the work
into an MXU matmul; the rank-1 norm corrections ride on the VPU.

Tiling: grid (Q/bq, N/bn).  Each program holds a (bq, d) query tile and a
(bn, d) candidate tile in VMEM and emits a (bq, bn) distance tile.  bq/bn
default to 128 (MXU-aligned); d is kept whole per tile — embedding dims in
this system are 128-1024 so a full row fits VMEM comfortably
(128 x 1024 x 4 B = 512 KB per operand tile).  The cross term runs at
full f32 precision: the decomposition subtracts nearly equal terms, so a
single bf16 MXU pass would reorder near neighbors.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _l2_kernel(q_ref, c_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)
    q2 = jnp.sum(q * q, axis=1, keepdims=True)              # [bq, 1]
    c2 = jnp.sum(c * c, axis=1, keepdims=True).T            # [1, bn]
    cross = jax.lax.dot_general(
        q, c, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)                 # [bq, bn] on MXU
    o_ref[...] = jnp.maximum(q2 + c2 - 2.0 * cross, 0.0)


@functools.partial(jax.jit, static_argnames=("block_q", "block_n", "interpret"))
def l2_distance_pallas(queries: jax.Array, candidates: jax.Array,
                       *, block_q: int = 128, block_n: int = 128,
                       interpret: bool = False) -> jax.Array:
    """queries [Q, d] x candidates [N, d] -> squared L2 [Q, N] (f32).

    Q and N must be multiples of the block sizes (callers pad; `ops.py`
    handles ragged shapes).
    """
    q_tot, d = queries.shape
    n_tot, _ = candidates.shape
    assert q_tot % block_q == 0 and n_tot % block_n == 0

    return pl.pallas_call(
        _l2_kernel,
        grid=(q_tot // block_q, n_tot // block_n),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((q_tot, n_tot), jnp.float32),
        interpret=interpret,
    )(queries, candidates)
