"""The control: the plain reference put in the program's place, one
step below the precision the configuration states (float32 at
`highest` -> `high`, the three-pass bfloat16 product), answering the
cell's queries at the cell's own size over the live set the cell
serves.  Its answers go through the same comparison as the program's;
the comparison has to find them not correct.

    python -m harness.control <cell> <seed> [<seed> ...]   # from bench/, on the chip

prints one line of readings per seed: the numbers a run compares.
"""

from __future__ import annotations

import json
import sys

import numpy as np

from harness import check, reference, spec
from harness.data import Deployment


def readings(cell: spec.Cell, seed: int, *, n_queries: int,
             n_base: int | None = None, precision: str = "high") -> dict:
    c = cell.config
    n = n_base or c["n_base"]
    dep = Deployment(c, seed, n_base=n, n_queries=n_queries)
    live = np.ones(n, bool)
    k = c["k"]
    ids, dists = reference.knn(dep.base, dep.queries, live, k,
                               precision=precision)
    truth, _ = reference.knn(dep.base, dep.queries, live, k)
    exact = reference.exact_dists(dep.base, dep.queries, ids)
    a = {"ids": ids, "dists": dists.astype(np.float64), "exact": exact,
         "truth": truth, "calls": np.zeros(len(ids), np.int64),
         "not_live": (~live[ids]).sum(axis=1)}
    nums = check.numbers(c, a, unanswered=0)
    return {"seed": seed, "precision": precision, "n_base": n,
            "n_queries": n_queries, "correct": all(x.ok for x in nums),
            **{x.name: x.value for x in nums}}


if __name__ == "__main__":
    import jax
    cell = spec.load(sys.argv[1])
    for s in sys.argv[2:]:
        r = readings(cell, int(s), n_queries=cell.traffic["query_pool"])
        r["platform"] = jax.devices()[0].platform
        print(json.dumps(r), flush=True)
