"""Data of a deployment, made from `--seed`.

`clustered` is the benchmark's own copy of the repository's SIFT-like
generator (`repro.data.synth.make_clustered_vectors`): a Gaussian
mixture whose centres come from the configuration's fixed
`center_seed`.  It is copied so that the yardstick cannot move with the
program.

A configuration is one data set, as SIFT1M is: its vectors are drawn
from the configuration's own `data_seed`, the same in every run, and
the run's `--seed` chooses only the order in which the held-out queries
are sent.  So every seed offers the same work, in another order.
"""

from __future__ import annotations

import zlib

import numpy as np


def stream(seed: int, name: str) -> np.random.Generator:
    """An independent generator per (run seed, purpose): any whole
    number is a valid seed, negative or past 64 bits included."""
    return np.random.default_rng(np.random.SeedSequence(
        entropy=int(seed) % (1 << 64), spawn_key=(zlib.crc32(name.encode()),)))


def small_seed(seed: int, name: str) -> int:
    """A 31-bit seed for APIs that take a plain int (JAX keys)."""
    return int(stream(seed, name).integers(0, 1 << 31))


def centers(data: dict, dim: int) -> np.ndarray:
    crng = np.random.default_rng(data["center_seed"])
    return crng.normal(0.0, data["scale"], (data["clusters"], dim))


def clustered(rng: np.random.Generator, assign: np.ndarray,
              ctr: np.ndarray, noise: float) -> np.ndarray:
    """float32 [len(assign), dim]: centre of each row's cluster plus
    isotropic Gaussian noise."""
    return (ctr[assign] + rng.normal(0.0, noise, (len(assign), ctr.shape[1]))
            ).astype(np.float32)


class Deployment:
    """Every vector one run uses, drawn up front: the base set, the
    held-out queries (in the order `seed` gives them), and set-up's own
    warm-up queries.  Each row's cluster is drawn independently."""

    def __init__(self, config: dict, seed: int, *, n_base: int,
                 n_queries: int, n_warm: int = 0):
        d = config["data"]
        ctr = centers(d, config["dim"])
        k = d["clusters"]
        fixed = d["data_seed"]

        def draw(name: str, n: int) -> np.ndarray:
            rng = stream(fixed, name)
            return clustered(rng, rng.integers(0, k, n), ctr, d["noise"])
        self.base = draw("base", n_base)
        self.queries = draw("queries", n_queries)[
            stream(seed, "query-order").permutation(n_queries)]
        self.warm_queries = draw("warm-queries", n_warm)
        self.build_seed = small_seed(fixed, "build")
