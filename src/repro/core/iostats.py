"""I/O accounting + the paper's cost model (Eq. 7-9).

The traversal engine counts *accesses*, not seconds: how many adjacency
rows were read from the LSM tree (`n_adj`, the paper's `T` pays `t_n`
each) and how many full vectors were fetched from the slow tier (`n_vec`,
pays `t_v` each).  `n_filtered` counts neighbors the SimHash filter
skipped — the saving Delta of Eq. 9.

The cost model that ships is `DISK`, the paper's hardware (NVMe 4 KB
random reads).  Time on the TPU is measured on the chip, not modelled.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp


class IOStats(NamedTuple):
    n_adj: jnp.ndarray        # adjacency-row (neighbor list) reads
    n_vec: jnp.ndarray        # full-vector fetches from the slow tier
    n_filtered: jnp.ndarray   # neighbor evaluations skipped by sampling
    n_hops: jnp.ndarray       # beam expansions (visited nodes T)

    @staticmethod
    def zero() -> "IOStats":
        z = jnp.zeros((), jnp.int32)
        return IOStats(z, z, z, z)

    def __add__(self, other: "IOStats") -> "IOStats":
        return IOStats(*(a + b for a, b in zip(self, other)))


class CostModel(NamedTuple):
    t_n: float   # seconds per neighbor-list fetch
    t_v: float   # seconds per vector fetch


# NVMe random 4KB read ~= 100 us; neighbor lists are similar-size reads.
DISK = CostModel(t_n=100e-6, t_v=100e-6)


def search_cost(stats: IOStats, model: CostModel) -> jnp.ndarray:
    """Eq. 7/8: T * t_n + (fetched vectors) * t_v.

    With sampling off, fetched = T * d and this reduces to Eq. 7; with
    sampling, fetched ~= rho * T * d (Eq. 8).
    """
    return stats.n_adj * model.t_n + stats.n_vec * model.t_v


def sampling_saving(stats: IOStats, model: CostModel) -> jnp.ndarray:
    """Eq. 9: Delta = (skipped vector fetches) * t_v."""
    return stats.n_filtered * model.t_v
