"""The plain reference: exact k-NN by brute force over the live set,
and exact distances, written here in straightforward `jax.numpy` and
NumPy.  It imports nothing of the program and takes nothing the program
made: the vectors are the ones the clients sent, and the live set is
rebuilt from the order in which the backend applied batches.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 128


def table_and_live(log: list, base: np.ndarray) -> tuple:
    """Replay the proxy's log: the vector of every internal id ever
    allocated (build rows first, then inserts in the order they were
    applied), and for each search call the live mask it saw.

    Returns (table [n_alloc, dim], {search call index: live mask})."""
    rows = [base]
    n = len(base)
    for c in log:
        if c.kind == "insert":
            ids = np.asarray(c.ids)
            if not np.array_equal(ids, np.arange(n, n + len(ids))):
                raise ValueError(f"insert ids {ids[:4]}... not allocated in "
                                 f"order after {n}")
            rows.append(np.asarray(c.rows, np.float32))
            n += len(ids)
    table = np.concatenate(rows)
    live = np.zeros(len(table), bool)
    live[:len(base)] = True
    masks = {}
    for i, c in enumerate(log):
        if c.kind == "insert":
            live[c.ids] = True
        elif c.kind == "delete":
            ids = np.asarray(c.ids)
            live[ids[ids >= 0]] = False
        elif c.kind == "search":
            masks[i] = live.copy()
    return table, masks


def _split(a):
    hi = a.astype(jnp.bfloat16)
    return hi, (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _dot(q, x, precision: str):
    """q @ x.T in float32.  ``highest`` is full float32; ``high`` is the
    three-pass bfloat16 product (hi*hi + hi*lo + lo*hi) that a TPU runs
    for `Precision.HIGH`, written out so it reads the same on any
    backend."""
    if precision == "highest":
        return jnp.matmul(q, x.T, precision="highest")
    mm = lambda a, b: jnp.matmul(a, b.T, preferred_element_type=jnp.float32)
    qh, ql = _split(q)
    xh, xl = _split(x)
    if precision == "high":
        return mm(qh, xh) + (mm(qh, xl) + mm(ql, xh))
    raise ValueError(f"unknown precision {precision!r}")


def knn(table, queries: np.ndarray, live: np.ndarray, k: int, *,
        precision: str = "highest") -> tuple:
    """Exact k nearest live rows of `table` for each query, in blocks of
    queries: (ids [Q, k], squared distances [Q, k]), as
    |q|^2 + |x|^2 - 2 q.x.  `precision` is that of the cross term (the
    control runs the same code one step lower)."""
    x = jnp.asarray(table, jnp.float32)
    xn = jnp.sum(x * x, axis=1)
    ok = jnp.asarray(live)
    ids, dists = [], []
    for s in range(0, len(queries), BLOCK):
        q = jnp.asarray(queries[s:s + BLOCK], jnp.float32)
        qn = jnp.sum(q * q, axis=1)
        d = qn[:, None] + xn[None, :] - 2.0 * _dot(q, x, precision)
        d = jnp.where(ok[None, :], jnp.maximum(d, 0.0), jnp.inf)
        neg, idx = jax.lax.top_k(-d, k)
        ids.append(np.asarray(idx))
        dists.append(-np.asarray(neg))
    return np.concatenate(ids), np.concatenate(dists)


def exact_dists(table: np.ndarray, queries: np.ndarray,
                ids: np.ndarray) -> np.ndarray:
    """Squared L2 in float64 between each query and the rows it names
    (NaN where the id is -1 or outside the table)."""
    ok = (ids >= 0) & (ids < len(table))
    rows = table[np.where(ok, ids, 0)].astype(np.float64)
    d = np.sum((rows - queries[:, None, :].astype(np.float64)) ** 2, axis=-1)
    return np.where(ok, d, np.nan)


def recall(found: np.ndarray, truth: np.ndarray) -> np.ndarray:
    """Per-query recall: |found ∩ truth| / k (-1 never matches)."""
    k = truth.shape[1]
    hit = (found[:, :k, None] == truth[:, None, :]) & (truth[:, None, :] >= 0)
    return hit.any(axis=1).sum(axis=1) / k
