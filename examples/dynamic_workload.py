"""Dynamic-workload demo on the online serving engine: the paper's
balanced insert-delete churn (Fig. 5 protocol) interleaved with queries,
driven through `repro.serve` micro-batching (DESIGN.md §8), with
non-blocking (double-buffered) consolidation overlapping the query
stream (DESIGN.md §13).

Each batch round submits individual insert/delete/query requests like
independent clients; the engine coalesces them into fixed-shape padded
micro-batches, serves queries from the cached LSM snapshot, and runs
threshold-triggered compaction in the background.  Per round it prints
recall, modeled update/search latency, memory, and engine stats.

The engine programs against the `VectorBackend` protocol (DESIGN.md
§10), so the same script serves a hash-partitioned multi-shard backend
unchanged:

    PYTHONPATH=src python examples/dynamic_workload.py
    PYTHONPATH=src python examples/dynamic_workload.py --shards 4
"""

import sys

sys.path.insert(0, "src")

import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import DISK, HNSWConfig, LSMVecIndex
from repro.core.distributed import ShardedBackend
from repro.core.index import brute_force_knn, recall_at_k
from repro.data.synth import make_clustered_vectors
from repro.serve import MaintenancePolicy, ServeConfig, ServeEngine


def main(n_base=1024, dim=48, n_batches=5, n_shards=1):
    base = make_clustered_vectors(n_base, dim=dim, seed=0)
    fresh = make_clustered_vectors(512, dim=dim, seed=1)
    queries = make_clustered_vectors(32, dim=dim, seed=7)
    cfg = HNSWConfig(cap=4096 // max(n_shards, 1) + 512, dim=dim, M=12,
                     M_up=6, num_upper=2, ef_search=48, ef_construction=48,
                     k=10, rho=0.8, use_filter=True)
    if n_shards > 1:
        backend = ShardedBackend(cfg, n_shards).build(base)
    else:
        backend = LSMVecIndex.build(cfg, base)
    engine = ServeEngine(backend, ServeConfig(
        query_batch=32, insert_batch=16, delete_batch=16,
        maintenance=MaintenancePolicy(tombstone_ratio=0.15, check_every=2,
                                      # overlapped consolidation is the
                                      # default; False = stop-the-world
                                      overlap=True)))

    allv = [base.copy()]
    live = np.ones(n_base, bool)
    rng = np.random.default_rng(3)
    cursor = 0
    batch_n = max(8, n_base // 100)

    print(f"serving over {type(backend).__name__}"
          + (f" ({n_shards} shards)" if n_shards > 1 else ""))
    print("batch,recall,update_ms,search_ms,memory_mb,n_live,maintenance")
    for b in range(n_batches):
        backend.reset_stats()
        for _ in range(batch_n // 2):          # 50% inserts
            x = fresh[cursor]
            cursor += 1
            engine.submit_insert(x)
            allv = [np.concatenate(allv + [x[None]])]
            live = np.append(live, True)
        victims = rng.choice(np.flatnonzero(live), batch_n // 2,
                             replace=False)
        for v in victims:                      # 50% deletes
            engine.submit_delete(int(v))
            live[v] = False
        engine.drain()
        upd_ms = backend.io_cost(DISK) * 1e3 / batch_n

        backend.reset_stats()
        tickets = [engine.submit_query(q) for q in queries]
        engine.drain()
        ids = np.stack([t.result().ids for t in tickets])
        srch_ms = backend.io_cost(DISK) * 1e3 / len(queries)
        truth = brute_force_knn(jnp.asarray(allv[0]), jnp.asarray(queries),
                                10, live=jnp.asarray(live))
        rec = recall_at_k(ids, truth)
        maint = dict(engine.metrics.maintenance_runs)
        print(f"{b},{rec:.3f},{upd_ms:.2f},{srch_ms:.2f},"
              f"{backend.memory_bytes()/1e6:.2f},{int(live.sum())},"
              f"{maint}")
    # settle any still-in-flight overlapped repair before final stats
    engine.maintenance.barrier()

    m = engine.metrics.snapshot()
    st = backend.stats()
    windows = [round(m[o]["window_ms"], 3)
               for o in ("query", "insert", "delete")]
    print(f"\nengine: {m['query']['batches']} query / "
          f"{m['insert']['batches']} insert / {m['delete']['batches']} "
          f"delete micro-batches, {m['snapshot_resolves']} snapshot "
          f"resolves, adaptive windows {windows} ms")
    print(f"backend: {st.size} live, {st.n_tombstones} tombstones, "
          f"{len(st.shards)} shard(s) "
          f"{[(s.size, s.n_tombstones) for s in st.shards]}")


if __name__ == "__main__":
    enable_compile_cache()
    shards = 1
    if "--shards" in sys.argv:
        shards = int(sys.argv[sys.argv.index("--shards") + 1])
    main(n_shards=shards)
