"""Fig. 7 reproduction: recall-vs-latency frontier (search and update).

Sweeps the per-system quality knob (LSM-VEC/DiskANN: ef; SPFresh: n_probe)
on a static index and reports Recall 10@10 against modeled per-query I/O
cost.  Paper claim validated: at matched recall, LSM-VEC's search cost is
below DiskANN's (the sampling filter skips fetches), and SPFresh's recall
ceiling sits below the graph systems'.
"""

from __future__ import annotations

import jax.numpy as jnp

from benchmarks.common import DISK, default_cfg
from repro.compile_cache import enable_compile_cache
from repro.core import iostats
from repro.core.backend import SearchParams
from repro.core.baselines import DiskANNIndex, SPFreshIndex
from repro.core.index import LSMVecIndex, brute_force_knn, recall_at_k
from repro.data.synth import make_clustered_vectors


def main(n_base: int = 4096, dim: int = 64, n_queries: int = 64):
    base = make_clustered_vectors(n_base, dim=dim, seed=0)
    queries = make_clustered_vectors(n_queries, dim=dim, seed=777)
    truth = brute_force_knn(jnp.asarray(base), jnp.asarray(queries), 10)

    print("\nfig7,system,knob,recall,query_cost_ms")
    frontier = {}
    lv = LSMVecIndex.build(default_cfg(dim, n_base + 16), base)
    for ef in (16, 32, 48, 96):
        lv.reset_stats()
        ids = lv.search(queries, k=10, params=SearchParams(ef=ef)).ids
        cost = float(iostats.search_cost(lv.io_stats, DISK)) * 1e3 / n_queries
        rec = recall_at_k(ids, truth)
        frontier.setdefault("lsmvec", []).append((rec, cost))
        print(f"fig7,lsmvec,ef={ef},{rec:.3f},{cost:.3f}")

    for ef in (16, 32, 48, 96):
        dk = DiskANNIndex.build(base, M=12, ef=ef)
        dk.reset_stats()
        ids, _ = dk.search(queries, k=10)
        cost = float(iostats.search_cost(dk.io_stats, DISK)) * 1e3 / n_queries
        rec = recall_at_k(ids, truth)
        frontier.setdefault("diskann", []).append((rec, cost))
        print(f"fig7,diskann,ef={ef},{rec:.3f},{cost:.3f}")

    sp = SPFreshIndex.build(base, posting_cap=64, n_probe=3)
    for probe in (2, 4, 8, 16):
        sp.n_probe = probe
        sp.reset_stats()
        ids, _ = sp.search(queries, k=10)
        cost = float(iostats.search_cost(sp.io_stats, DISK)) * 1e3 / n_queries
        rec = recall_at_k(ids, truth)
        frontier.setdefault("spfresh", []).append((rec, cost))
        print(f"fig7,spfresh,probe={probe},{rec:.3f},{cost:.3f}")

    # claim: at its best recall point, lsmvec's cost < diskann's cost at
    # comparable-or-lower recall; if diskann never reaches lsmvec's
    # recall, lsmvec dominates the frontier outright
    best_lv = max(frontier["lsmvec"])
    dk_at_least = [c for r, c in frontier["diskann"] if r >= best_lv[0]-0.02]
    if dk_at_least:
        ok = best_lv[1] < min(dk_at_least)
    else:
        ok = best_lv[1] < max(c for _, c in frontier["diskann"])
    print(f"check,lsmvec cheaper than diskann at matched recall,"
          f"{'PASS' if ok else 'FAIL'}")
    ceiling_ok = max(r for r, _ in frontier["spfresh"]) <= \
        max(r for r, _ in frontier["lsmvec"]) + 0.02
    print(f"check,spfresh recall ceiling below graph systems,"
          f"{'PASS' if ceiling_ok else 'FAIL'}")
    return frontier, ok and ceiling_ok


if __name__ == "__main__":
    enable_compile_cache()
    main()
