"""Churn-tolerance benchmark: lazy deletion + consolidation vs eager.

ROADMAP flags that recall under heavy insert/delete churn degrades on
every path because eager `delete`/`delete_batch` relink-and-tombstone
nodes immediately, severing routes through deleted regions.  The lazy
two-phase protocol (DESIGN.md §9) keeps deleted nodes *routable but not
returnable* until `consolidate` splices them out.  This benchmark sweeps
churn ratios on the `serve_load` instance shape and records, per ratio:

  - **recall_eager**    — recall 10@10 after churn through the eager
    Algorithm-2 delete path (`lazy_delete=False`), the paper baseline;
  - **recall_lazy**     — same churn through tombstone-only deletes,
    queried *before* consolidation (tombstones still routable);
  - **recall_consolidated** — after `consolidate()` reclaims the slots;
  - **qps_pre / qps_lazy / qps_consolidated** — fixed-batch query
    throughput before churn, with tombstones resident, and after
    consolidation (consolidation must restore QPS: the clean graph
    should serve within 10% of the pre-churn index).

Results go to ``BENCH_churn.json``.  ``--smoke`` runs a tiny instance
and validates the schema (the CI mode); ``--check`` additionally
compares the measured smoke recalls against the committed floors in
``BENCH_churn.json`` and exits non-zero on regression — the CI
recall-regression gate (no other job measures recall at all).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

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

SCHEMA = {
    "meta": ("mode", "backend", "n_base", "dim", "batch", "n_eval",
             "churn_ratios", "config"),
    "sweep": (),          # list of per-ratio dicts, validated separately
    "criteria": ("lazy_beats_eager_by_0p05_at_30pct",
                 "consolidation_restores_qps_within_10pct",
                 "consolidated_tombstone_free"),
    "floors": ("smoke_recall_lazy", "smoke_recall_consolidated",
               "smoke_churn"),
}

SWEEP_FIELDS = ("churn", "n_deleted", "n_inserted", "tombstone_ratio",
                "recall_eager", "recall_lazy", "recall_consolidated",
                "qps_pre", "qps_lazy", "qps_consolidated",
                "slots_reclaimed")

#: margin subtracted from the measured smoke recall to form the committed
#: CI floor — wide enough to absorb cross-platform jax numeric drift,
#: tight enough that a returnable-mask or consolidation regression
#: (which costs far more recall than this) still trips the gate
FLOOR_MARGIN = 0.08

TRIALS = 2   # best-of-N per timed section (container jitter)


def validate_schema(doc: dict) -> None:
    """Raise ValueError unless `doc` matches the BENCH_churn schema."""
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


def _cfg(dim: int, cap: int, *, lazy: bool) -> hnsw.HNSWConfig:
    # the BENCH_serve instance shape, so the numbers are comparable
    return hnsw.HNSWConfig(
        cap=cap, dim=dim, M=12, M_up=6, num_upper=2, ef_search=48,
        ef_construction=48, k=10, m_bits=64, rho=1.0, eps=0.1,
        use_filter=False, lsm_mem_cap=256, lsm_levels=2, lsm_fanout=8,
        n_expand=1, batch_expand=4, lazy_delete=lazy)


def _fixed_batch_qps(idx: LSMVecIndex, pool: np.ndarray, batch: int,
                     k: int) -> float:
    """Best-of-TRIALS fixed-shape search throughput (the PR-1 path)."""
    nb = len(pool) // batch
    idx.search(pool[:batch], k=k,
               params=SearchParams(record_heat=False))      # compile
    dt = float("inf")
    for _ in range(TRIALS):
        t0 = time.monotonic()
        for b in range(nb):
            idx.search(pool[b * batch:(b + 1) * batch], k=k,
                       params=SearchParams(record_heat=False))
        jax.block_until_ready(idx.state.count)
        dt = min(dt, time.monotonic() - t0)
    return nb * batch / dt


def _apply_churn(idx: LSMVecIndex, victims: np.ndarray, fresh: np.ndarray,
                 batch: int) -> None:
    """Interleaved delete/insert batches — the serving write pattern."""
    for s in range(0, max(len(victims), len(fresh)), batch):
        dv = victims[s:s + batch]
        if len(dv):
            idx.delete_batch(dv, pad_to=batch)
        fv = fresh[s:s + batch]
        if len(fv):
            idx.insert_batch(fv, pad_to=batch)


def run(*, n_base: int, batch: int, dim: int, seed: int,
        churn_ratios: list, n_eval: int, mode: str) -> dict:
    rng = np.random.default_rng(seed)
    max_churn = max(churn_ratios)
    n_fresh_max = int(n_base * max_churn)
    cap = n_base + n_fresh_max + 4 * batch + 64
    base = make_clustered_vectors(n_base, dim=dim, seed=seed)
    eval_q = make_clustered_vectors(n_eval, dim=dim, seed=seed + 3)
    qpool = base[rng.integers(0, n_base, size=max(8, 512 // batch) * batch)]

    cfg_lazy = _cfg(dim, cap, lazy=True)
    cfg_eager = _cfg(dim, cap, lazy=False)
    k = cfg_lazy.k

    # one bulk build; every arm starts from a copy (the lazy_delete flag
    # is config-static, the state arrays are identical) — donated jits
    # consume their input state, hence the copies
    state0 = LSMVecIndex.build(cfg_lazy, base).state

    def fork(cfg):
        return LSMVecIndex(cfg, state=jax.tree.map(jnp.copy, state0))

    # pre-churn reference QPS, measured once on a clean index
    qps_pre = _fixed_batch_qps(fork(cfg_lazy), qpool, batch, k)

    sweep = []
    tombstone_free = True
    for churn in churn_ratios:
        n_churn = int(n_base * churn)
        victims = rng.choice(n_base, n_churn, replace=False).astype(np.int32)
        fresh = make_clustered_vectors(max(n_churn, 1), dim=dim,
                                       seed=seed + 17)[:n_churn]
        live = np.ones(n_base + n_churn, bool)
        live[victims] = False
        allv = np.concatenate([base, fresh]) if n_churn else base
        truth = brute_force_knn(jnp.asarray(allv), jnp.asarray(eval_q), k,
                                live=jnp.asarray(live))
        deleted = set(victims.tolist())

        # ---- eager baseline (the paper's Algorithm-2 delete) -------------
        idx_e = fork(cfg_eager)
        _apply_churn(idx_e, victims, fresh, batch)
        ids_e = idx_e.search(eval_q, k=k).ids
        recall_eager = recall_at_k(ids_e, truth)
        del idx_e

        # ---- lazy: tombstones routable, then consolidated ----------------
        idx_l = fork(cfg_lazy)
        _apply_churn(idx_l, victims, fresh, batch)
        nt = idx_l.n_tombstones
        tomb_ratio = nt / max(idx_l.size + nt, 1)
        ids_l = idx_l.search(eval_q, k=k).ids
        recall_lazy = recall_at_k(ids_l, truth)
        if set(ids_l.flatten().tolist()) & deleted:
            raise AssertionError("tombstoned id returned pre-consolidation")
        qps_lazy = _fixed_batch_qps(idx_l, qpool, batch, k)

        reclaimed = idx_l.consolidate()
        ids_c = idx_l.search(eval_q, k=k).ids
        recall_cons = recall_at_k(ids_c, truth)
        if (set(ids_c.flatten().tolist()) & deleted) \
                or idx_l.n_tombstones != 0:
            tombstone_free = False
        qps_cons = _fixed_batch_qps(idx_l, qpool, batch, k)
        del idx_l

        sweep.append({
            "churn": churn,
            "n_deleted": n_churn,
            "n_inserted": n_churn,
            "tombstone_ratio": round(tomb_ratio, 4),
            "recall_eager": round(recall_eager, 4),
            "recall_lazy": round(recall_lazy, 4),
            "recall_consolidated": round(recall_cons, 4),
            "qps_pre": round(qps_pre, 1),
            "qps_lazy": round(qps_lazy, 1),
            "qps_consolidated": round(qps_cons, 1),
            "slots_reclaimed": reclaimed,
        })

    heavy = [r for r in sweep if r["churn"] >= 0.3] or sweep
    lazy_wins = all(r["recall_lazy"] >= r["recall_eager"] + 0.05
                    for r in heavy)
    qps_restored = all(r["qps_consolidated"] >= 0.9 * r["qps_pre"]
                       for r in sweep)

    # floors for the CI recall-regression gate: committed from a full run,
    # compared against fresh smoke numbers by `--check`
    smoke_row = sweep[-1] if mode == "smoke" else None
    doc = {
        "meta": {
            "mode": mode, "backend": jax.default_backend(),
            "n_base": n_base, "dim": dim, "batch": batch, "n_eval": n_eval,
            "churn_ratios": churn_ratios,
            "config": {kk: vv for kk, vv in cfg_lazy._asdict().items()},
        },
        "sweep": sweep,
        "criteria": {
            "lazy_beats_eager_by_0p05_at_30pct": bool(lazy_wins),
            "consolidation_restores_qps_within_10pct": bool(qps_restored),
            "consolidated_tombstone_free": bool(tombstone_free),
        },
        "floors": {
            "smoke_churn": smoke_row["churn"] if smoke_row else 0.0,
            "smoke_recall_lazy": round(
                max(smoke_row["recall_lazy"] - FLOOR_MARGIN, 0.0), 4)
            if smoke_row else 0.0,
            "smoke_recall_consolidated": round(
                max(smoke_row["recall_consolidated"] - FLOOR_MARGIN, 0.0), 4)
            if smoke_row else 0.0,
        },
    }
    return doc


def smoke_args(seed: int) -> dict:
    return dict(n_base=384, batch=16, dim=16, seed=seed,
                churn_ratios=[0.3], n_eval=32, mode="smoke")


def check_floors(doc: dict, committed_path: str) -> int:
    """CI recall-regression gate: fresh smoke recalls vs committed floors."""
    if not os.path.exists(committed_path):
        print(f"check: no committed {committed_path}; nothing to gate "
              "against (write one with a full run first)")
        return 1
    with open(committed_path) as f:
        committed = json.load(f)
    floors = committed.get("floors", {})
    row = doc["sweep"][-1]
    failures = []
    for field, floor_key in (("recall_lazy", "smoke_recall_lazy"),
                             ("recall_consolidated",
                              "smoke_recall_consolidated")):
        floor = floors.get(floor_key)
        if floor is None:
            failures.append(f"committed floors missing {floor_key}")
            continue
        got = row[field]
        status = "PASS" if got >= floor else "FAIL"
        print(f"  {status} {field}: {got:.4f} >= floor {floor:.4f}")
        if got < floor:
            failures.append(
                f"{field} {got:.4f} regressed below floor {floor:.4f}")
    if failures:
        print("recall-regression gate FAILED:")
        for msg in failures:
            print(f"  - {msg}")
        return 1
    print("recall-regression gate passed")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny run; validate the JSON schema only")
    ap.add_argument("--check", action="store_true",
                    help="compare smoke recall against the committed "
                         "floors in BENCH_churn.json; non-zero exit on "
                         "regression (the CI gate)")
    ap.add_argument("--out", default=None,
                    help="output path (default: <repo>/BENCH_churn.json)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = args.out or os.path.join(root, "BENCH_churn.json")

    if args.check and not args.smoke:
        # a full run regenerates the committed floors from *current*
        # code; gating against floors it just rewrote would pass any
        # regression, so the combination is refused outright
        ap.error("--check requires --smoke (the gate compares a fresh "
                 "smoke replay against the committed floors)")

    if args.smoke:
        doc = run(**smoke_args(args.seed))
    else:
        doc = run(n_base=4096, batch=64, dim=64, seed=args.seed,
                  churn_ratios=[0.1, 0.3, 0.5], n_eval=64, mode="full")
        # the committed floors come from the smoke instance so the CI
        # gate replays the exact configuration it compares against
        smoke_doc = run(**smoke_args(args.seed))
        doc["floors"] = smoke_doc["floors"]

    validate_schema(doc)
    print(json.dumps(doc, indent=1))
    if args.smoke:
        print("smoke: schema OK (perf criteria not enforced)")
        if args.out:
            # CI uploads the smoke measurement it actually produced; the
            # committed BENCH_churn.json (floors) is never overwritten
            # in smoke mode, so gate comparisons stay against main
            write_bench_json(args.out, doc)
        rc = 0
        if args.check:
            rc = check_floors(doc, os.path.join(root, "BENCH_churn.json"))
        return rc

    write_bench_json(out, doc)
    rc = 0
    for name, ok in doc["criteria"].items():
        print(f"  {'PASS' if ok else 'FAIL'} {name}")
        rc = rc if ok else 1
    return rc


if __name__ == "__main__":
    enable_compile_cache()
    raise SystemExit(main())
