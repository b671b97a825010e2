"""Quickstart: build an LSM-VEC index, update it, search it.

    PYTHONPATH=src python examples/quickstart.py
"""

import sys

sys.path.insert(0, "src")

import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import DISK, HNSWConfig, LSMVecIndex, SearchParams  # noqa: F401
from repro.core.index import brute_force_knn, recall_at_k
from repro.data.synth import make_clustered_vectors


def main():
    dim, n = 64, 2048
    data = make_clustered_vectors(n, dim=dim, seed=0)
    cfg = HNSWConfig(cap=4096, dim=dim, M=12, M_up=6, num_upper=2,
                     ef_search=48, ef_construction=48, k=10,
                     rho=0.8, eps=0.1, use_filter=True)

    print(f"building LSM-VEC over {n} x {dim} vectors ...")
    idx = LSMVecIndex.build(cfg, data)

    queries = make_clustered_vectors(32, dim=dim, seed=7)
    res = idx.search(queries, k=10)           # typed SearchResult
    # knobs ride a typed SearchParams instead of kwargs, e.g.
    #   idx.search(queries, k=10, params=SearchParams(rho=0.7))
    ids = res.ids
    truth = brute_force_knn(jnp.asarray(data), jnp.asarray(queries), 10)
    print(f"recall 10@10 = {recall_at_k(ids, truth):.3f}")
    print(f"I/O stats: {int(idx.io_stats.n_adj)} adjacency reads, "
          f"{int(idx.io_stats.n_vec)} vector fetches, "
          f"{int(idx.io_stats.n_filtered)} skipped by sampling")
    print(f"modeled search cost (paper disk constants): "
          f"{idx.io_cost(DISK) * 1e3 / len(queries):.2f} ms/query")

    # dynamic updates: insert a new cluster, delete some old points
    new_vecs = make_clustered_vectors(16, dim=dim, seed=99) + 30.0
    new = idx.insert_batch(new_vecs)          # typed UpdateResult
    found = idx.search(new_vecs, k=1).ids
    print(f"inserted {new.n_applied}; self-recall of new vectors: "
          f"{(found[:, 0] == np.asarray(new.ids)).mean():.2f}")

    idx.delete_batch(ids[0][:3].tolist())
    ids2 = idx.search(queries[:1], k=10).ids
    assert not set(ids[0][:3]) & set(ids2[0]), "deleted ids must not return"
    print("deletes verified (tombstoned + relinked).")

    print(f"memory-resident footprint: {idx.memory_bytes()/1e6:.2f} MB "
          f"(vectors on 'disk': {idx.state.vectors.nbytes/1e6:.1f} MB)")

    # maintenance: every op goes through the uniform maintain() entry
    # (connectivity-aware reordering here, paper §3.4) and returns one
    # typed MaintenanceReport
    rep = idx.maintain("reorder", window=8, lam=1.0)
    assert rep.applied and rep.perm is not None
    ids3 = idx.search(queries, k=10).ids
    gt3 = brute_force_knn(idx.state.vectors[:idx.state.count],
                          jnp.asarray(queries), 10)
    print(f"post-reorder recall = {recall_at_k(ids3, gt3):.3f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
