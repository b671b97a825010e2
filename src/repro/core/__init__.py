"""LSM-VEC core: the paper's contribution as composable JAX modules.

- lsm        — functional LSM-tree storing bottom-layer adjacency
- simhash    — sign-random-projection codes + Hoeffding filter (Eq. 4-6)
- hnsw       — hybrid memory/disk hierarchical graph (Alg. 1-2)
- traversal  — sampling-guided beam search (§3.3)
- reorder    — connectivity-aware relayout (§3.4, Eq. 10-12)
- iostats    — the paper's I/O cost model (Eq. 7-9)
- backend    — the VectorBackend protocol + typed results (§10): the
  boundary everything above the core programs against
- index      — LSMVecIndex, the single-device backend
- distributed— ShardedBackend (hash-partitioned shard-per-device
  serving) + exact flat sharded search
- baselines  — DiskANN-like and SPFresh-like comparison systems
"""

from repro.core.backend import (
    BackendStats,
    MaintenanceReport,
    SearchHandle,
    SearchParams,
    SearchResult,
    ShardStats,
    UpdateResult,
    VectorBackend,
)
from repro.core.hnsw import HNSWConfig, HNSWState
from repro.core.index import LSMVecIndex, brute_force_knn, recall_at_k
from repro.core.iostats import DISK, CostModel, IOStats

__all__ = [
    "HNSWConfig", "HNSWState", "LSMVecIndex", "brute_force_knn",
    "recall_at_k", "IOStats", "CostModel", "DISK",
    "VectorBackend", "BackendStats", "ShardStats", "SearchResult",
    "UpdateResult", "SearchParams", "SearchHandle", "MaintenanceReport",
]
