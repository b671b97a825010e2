"""Fig. 6 reproduction: resident memory vs recall across the tier sweep.

The paper's headline systems claim is a 66.2% smaller resident footprint
than DiskANN at scale.  This benchmark makes the claim first-class for
our reproduction (DESIGN.md §12): build one index over a clustered
corpus, serve a head-skewed query workload to accumulate traversal
heat, then sweep the tier policy's hot-fraction budget.  For each
budget the benchmark demotes the cold tail into the int8 lane and
measures

  - resident bytes (the full per-component `MemoryBreakdown`: vector
    lanes, upper graph + cache, simhash codes, memtable, tombstone
    lane, insert overlay, id maps), and
  - recall 10@10 on the *same* query workload against the dense
    baseline (the pre-demotion index, every routable node in the f32
    lane).

Criteria (the `tier-smoke` CI gate):
  - at hot_frac=0.25 the tiered resident bytes are <= 50% of dense;
  - at hot_frac=0.25 recall is >= 0.95x the dense baseline.

Results go to ``BENCH_memory.json``.  ``--smoke`` runs the small CI
instance; ``--check`` exits non-zero unless both criteria hold.
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from _util import write_bench_json
from repro.compile_cache import enable_compile_cache
from repro.core import hnsw
from repro.core.backend import SearchParams
from repro.core.index import LSMVecIndex, brute_force_knn, recall_at_k
from repro.data.synth import make_clustered_vectors
from repro.tier import TierPolicy

SCHEMA = {
    "meta": ("mode", "backend", "n", "dim", "n_queries", "head_frac",
             "hot_fracs", "config"),
    "dense": ("recall", "bytes", "breakdown"),
    "sweep": (),          # per-hot_frac dicts, validated separately
    "criteria": ("tiered_bytes_le_50pct_dense_at_hot25",
                 "recall_ge_95pct_dense_at_hot25"),
}

SWEEP_FIELDS = ("hot_frac", "recall", "bytes", "bytes_vs_dense",
                "recall_vs_dense", "n_hot", "n_cold", "demoted",
                "promoted", "rerank_fetches_per_query")

GATE_HOT_FRAC = 0.25


def validate_schema(doc: dict) -> None:
    """Raise ValueError unless `doc` matches the BENCH_memory schema."""
    for section, fields in SCHEMA.items():
        if section not in doc:
            raise ValueError(f"missing section {section!r}")
        for f in fields:
            if f not in doc[section]:
                raise ValueError(f"missing field {section}.{f}")
    if not isinstance(doc["sweep"], list) or not doc["sweep"]:
        raise ValueError("sweep must be a non-empty list")
    for row in doc["sweep"]:
        for f in SWEEP_FIELDS:
            if f not in row:
                raise ValueError(f"missing sweep field {f!r}")
            v = row[f]
            if not isinstance(v, (int, float)) or not np.isfinite(v):
                raise ValueError(f"non-finite sweep.{f}: {v!r}")
    for f, v in doc["criteria"].items():
        if not isinstance(v, bool):
            raise ValueError(f"criteria.{f} must be bool, got {v!r}")


def _cfg(dim: int, cap: int) -> hnsw.HNSWConfig:
    # dim is deliberately large (vectors dominate a real deployment's
    # footprint, and the fixed serving overheads — insert overlay, id
    # maps, memtable — weigh the ratio toward 1 at toy sizes) and
    # level_scale puts <1% of nodes in the upper layers — the paper's
    # regime — so the resident upper-layer vector cache doesn't swamp
    # the lane accounting it routes for.
    return hnsw.HNSWConfig(
        cap=cap, dim=dim, M=12, M_up=6, num_upper=2, ef_search=48,
        ef_construction=48, k=10, m_bits=64, rho=1.0, eps=0.1,
        use_filter=False, lsm_mem_cap=256, lsm_levels=2, lsm_fanout=8,
        tier=True, rerank=32, level_scale=0.2)


def _skewed_queries(base: np.ndarray, n_queries: int, head_frac: float,
                    seed: int) -> np.ndarray:
    """Head-skewed query workload: 80% of queries perturb vectors from
    the `head_frac` head of the corpus, 20% from the tail — the traffic
    shape that makes a hot/cold split pay (percolate-node's premise)."""
    rng = np.random.default_rng(seed)
    n = len(base)
    n_head = max(1, int(n * head_frac))
    n_hot_q = int(n_queries * 0.8)
    head_ids = rng.integers(0, n_head, n_hot_q)
    tail_ids = rng.integers(0, n, n_queries - n_hot_q)
    picks = base[np.concatenate([head_ids, tail_ids])]
    noise = rng.normal(0.0, 0.1, picks.shape).astype(np.float32)
    return (picks + noise).astype(np.float32)


def run(*, n: int, dim: int, n_queries: int, head_frac: float,
        hot_fracs: list, warm_rounds: int, seed: int, mode: str) -> dict:
    cfg = _cfg(dim, cap=n + 64)
    base = make_clustered_vectors(n, dim=dim, seed=seed)
    queries = _skewed_queries(base, n_queries, head_frac, seed + 1)
    truth = brute_force_knn(jnp.asarray(base), jnp.asarray(queries), cfg.k)

    idx0 = LSMVecIndex.build(cfg, base)

    # dense baseline: every routable node in the f32 lane (pre-demotion
    # state of the very same index, so graph and level draws are shared
    # with every tiered arm).  The searches double as heat warmup.
    for _ in range(warm_rounds):
        ids_d = idx0.search(queries, k=cfg.k,
                            params=SearchParams(record_heat=True)).ids
    recall_dense = recall_at_k(np.asarray(ids_d), truth)
    mem_dense = idx0.memory_breakdown()
    print(f"fig6,dense,recall={recall_dense:.4f},"
          f"bytes={mem_dense.total}", flush=True)

    sweep = []
    for hf in hot_fracs:
        idx = idx0.clone()
        pol = TierPolicy(hot_frac=hf, ewma=0.5, hysteresis=0.05,
                         max_demote=cfg.cap, max_promote=cfg.cap)
        moved = idx.tier_maintain(pol)
        moved2 = idx.tier_maintain(pol)   # EWMA settles, hysteresis holds
        idx.reset_stats()
        ids_t = idx.search(queries, k=cfg.k,
                           params=SearchParams(record_heat=False)).ids
        rerank_fetches = int(idx.io_stats.n_vec) / n_queries
        recall_t = recall_at_k(np.asarray(ids_t), truth)
        mem_t = idx.memory_breakdown()
        row = {
            "hot_frac": hf,
            "recall": round(recall_t, 4),
            "bytes": int(mem_t.total),
            "bytes_vs_dense": round(mem_t.total / max(mem_dense.total, 1), 4),
            "recall_vs_dense": round(recall_t / max(recall_dense, 1e-9), 4),
            "n_hot": mem_t.n_hot,
            "n_cold": mem_t.n_cold,
            "demoted": moved["demoted"] + moved2["demoted"],
            "promoted": moved["promoted"] + moved2["promoted"],
            "rerank_fetches_per_query": round(rerank_fetches, 2),
            "breakdown": mem_t.as_dict(),
        }
        sweep.append(row)
        print(f"fig6,hot_frac={hf},recall={recall_t:.4f},"
              f"bytes={mem_t.total} ({100 * row['bytes_vs_dense']:.1f}% "
              f"of dense),n_hot={mem_t.n_hot},n_cold={mem_t.n_cold}",
              flush=True)
        del idx

    gate = next(r for r in sweep
                if abs(r["hot_frac"] - GATE_HOT_FRAC) < 1e-9)
    crit_bytes = gate["bytes_vs_dense"] <= 0.50
    crit_recall = gate["recall_vs_dense"] >= 0.95
    print(f"check,tiered_bytes_le_50pct_dense_at_hot25,"
          f"{'PASS' if crit_bytes else 'FAIL'}")
    print(f"check,recall_ge_95pct_dense_at_hot25,"
          f"{'PASS' if crit_recall else 'FAIL'}")

    return {
        "meta": {
            "mode": mode, "backend": jax.default_backend(),
            "n": n, "dim": dim, "n_queries": n_queries,
            "head_frac": head_frac, "hot_fracs": hot_fracs,
            "config": dict(cfg._asdict()),
        },
        "dense": {
            "recall": round(recall_dense, 4),
            "bytes": int(mem_dense.total),
            "breakdown": mem_dense.as_dict(),
        },
        "sweep": sweep,
        "criteria": {
            "tiered_bytes_le_50pct_dense_at_hot25": bool(crit_bytes),
            "recall_ge_95pct_dense_at_hot25": bool(crit_recall),
        },
    }


def full_args(seed: int) -> dict:
    return dict(n=4096, dim=384, n_queries=256, head_frac=0.2,
                hot_fracs=[0.5, 0.25, 0.1], warm_rounds=3, seed=seed,
                mode="full")


def smoke_args(seed: int) -> dict:
    return dict(n=768, dim=384, n_queries=64, head_frac=0.2,
                hot_fracs=[0.5, 0.25, 0.1], warm_rounds=2, seed=seed,
                mode="smoke")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small CI instance")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero unless both tier criteria pass")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None,
                    help="output JSON path (default BENCH_memory.json, "
                    "or ci-bench/... under --smoke)")
    args = ap.parse_args(argv)

    kw = smoke_args(args.seed) if args.smoke else full_args(args.seed)
    doc = run(**kw)
    validate_schema(doc)
    out = args.out or ("ci-bench/BENCH_memory.smoke.json" if args.smoke
                       else "BENCH_memory.json")
    write_bench_json(out, doc)
    if args.check and not all(doc["criteria"].values()):
        print("tier memory/recall gate FAILED")
        return 1
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
