"""Sustained mixed-workload serving benchmark (DESIGN.md §8, §10).

Drives the `repro.serve` engine with an interleaved 80/10/10
query/insert/delete stream in saturation (every request pre-enqueued,
relaxed coalescing) and records:

  - **serve_qps** — queries completed / total drain wall, i.e. query
    throughput *while also absorbing the write stream* and any
    threshold-triggered LSM compactions;
  - **fixed_batch_qps** — the PR-1 reference path measured in-run: the
    SAME op stream dispatched directly as fixed-shape batches (no
    scheduler; arrival-order runs of `batch` per op) on the same machine
    from the same starting index, so both sides pay the identical write
    stream and the ratio isolates the serving layer rather than the
    box's read/write cost balance;
  - **zero-retrace proof** — jit trace counts per entry point are
    snapshotted after warmup and must not grow during the load phase
    (fixed pad shapes mean ragged micro-batches reuse one traced shape);
  - **recall parity** — a held-out query set evaluated through the engine
    vs the same op stream applied per-item to a bare index (the
    sequential baseline), both against brute force over the final live
    set.

Results go to ``BENCH_serve.json``.  ``--smoke`` runs a tiny instance and
validates the schema only (the CI mode), like ``throughput.py``.

``--shards P`` serves the identical protocol through a
`ShardedBackend` of P hash-partitioned `LSMVecIndex` shards (DESIGN.md
§10) — the engine code path is unchanged, only the backend differs.
The smoke instance scales ``n_base`` by P so per-shard scale matches
the single-device smoke; run under
``XLA_FLAGS=--xla_force_host_platform_device_count=P`` to give each
shard its own device.  The recall criterion relaxes from the strict
±0.01 band to a 0.95× floor of the sequential single-device baseline
(cross-shard merge is a different, recall-guarded execution).

**Durability** (DESIGN.md §11): every run also reports a ``durability``
section.  An A/B probe drives an identical closed-loop insert stream
with and without a group-committed WAL and reports acked-insert p50/p99
for both arms — the criterion ``wal_overhead_within_15pct`` gates the
fsync tax at ≤15% on p50.  ``--wal`` additionally runs the *main* serve
drain with the WAL on (acks then imply durability and the headline
qps absorbs the commit cost); ``--ckpt-every N`` layers covering
checkpoints every N write batches on top.  ``--crash-recovery`` runs
the failure-injection matrix instead of the load benchmark: kill at
each injection point, restart via `ServeEngine.recover`, and gate on
zero acknowledged-write loss plus a recall floor against an
uninterrupted run of the same op stream (the CI job's mode).

**Fused beam search** (DESIGN.md §15): every run also reports a
``fused`` section — an A/B probe of the beam-search megakernel path
(``HNSWConfig.fused_beam``) against the `while_loop` path: query-batch
p50 per arm, id bit-parity, recall ratio, and a zero-retrace check.
``--fused-beam`` additionally serves the *main* drain through the
fused path and binds the full criterion (p50 at or below the while
arm, within a 1.05x noise band on CPU hosts where both arms lower to
the same HLO).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from _util import write_bench_json
from repro.compile_cache import enable_compile_cache
from repro.core import hnsw
from repro.core.backend import SearchParams, shard_of_seq
from repro.core.distributed import ShardedBackend, ShardedDispatch
from repro.core.index import LSMVecIndex, brute_force_knn, recall_at_k
from repro.data.synth import make_clustered_vectors
from repro.ft import FailureInjector, RestartPolicy, run_with_recovery, verify_acked_writes
from repro.serve import MaintenancePolicy, ServeConfig, ServeEngine, WalConfig
from repro.tier import TierPolicy

SCHEMA = {
    "meta": ("mode", "backend", "shards", "tier", "n_base", "n_ops", "mix",
             "dim", "batch", "n_expand", "serve_query_batch",
             "serve_n_expand", "config"),
    "serve": ("qps", "insert_ops_s", "delete_ops_s", "query_p50_ms",
              "query_p99_ms", "mean_query_batch", "snapshot_resolves",
              "compactions", "tier_passes", "tier_demoted", "tier_promoted",
              "wall_s"),
    "baseline": ("fixed_batch_qps", "qps_ratio"),
    "recall": ("serve", "sequential", "delta"),
    "retraces": ("after_warmup", "after_load", "new_during_load"),
    "durability": ("wal_enabled", "ckpt_every", "wal_records", "wal_commits",
                   "checkpoints", "probe_n", "acked_insert_p50_ms",
                   "acked_insert_p99_ms", "nowal_insert_p50_ms",
                   "nowal_insert_p99_ms", "overhead_p50_pct"),
    "fanout": ("shards", "batch", "seq_ms", "async_ms", "ratio", "parity",
               "host_cores"),
    "overlap": ("p99_nomaint_ms", "p99_overlap_ms", "ratio",
                "consolidations", "write_holds", "host_cores"),
    "fused": ("enabled", "while_p50_ms", "fused_p50_ms", "p50_ratio",
              "parity", "recall_ratio", "zero_retraces", "host_cores"),
    "criteria": ("zero_retraces_after_warmup", "qps_within_10pct_of_fixed",
                 "recall_within_0p01", "wal_overhead_within_15pct",
                 "fanout_dispatch_leq_0p7x", "overlap_p99_leq_1p3x",
                 "fused_parity_p50_leq_while"),
}


def validate_schema(doc: dict) -> None:
    """Raise ValueError unless `doc` matches the BENCH_serve schema."""
    for section, fields in SCHEMA.items():
        if section not in doc:
            raise ValueError(f"missing section {section!r}")
        for f in fields:
            if f not in doc[section]:
                raise ValueError(f"missing field {section}.{f}")
    for section in ("serve", "baseline", "recall", "fanout", "overlap",
                    "fused"):
        for f, v in doc[section].items():
            if isinstance(v, bool):
                continue
            if not isinstance(v, (int, float)) or not np.isfinite(v):
                raise ValueError(f"non-finite {section}.{f}: {v!r}")
    for f, v in doc["retraces"].items():
        if not isinstance(v, dict) and not isinstance(v, int):
            raise ValueError(f"retraces.{f} must be dict|int, got {v!r}")
    dur = doc["durability"]
    if not isinstance(dur["wal_enabled"], bool):
        raise ValueError(f"durability.wal_enabled must be bool, "
                         f"got {dur['wal_enabled']!r}")
    if dur["ckpt_every"] is not None \
            and not isinstance(dur["ckpt_every"], int):
        raise ValueError(f"durability.ckpt_every must be int|None, "
                         f"got {dur['ckpt_every']!r}")
    for f, v in dur.items():
        if f in ("wal_enabled", "ckpt_every"):
            continue
        if not isinstance(v, (int, float)) or not np.isfinite(v):
            raise ValueError(f"non-finite durability.{f}: {v!r}")
    for f, v in doc["criteria"].items():
        if not isinstance(v, bool):
            raise ValueError(f"criteria.{f} must be bool, got {v!r}")


def _cfg(dim: int, cap: int) -> hnsw.HNSWConfig:
    # the BENCH_throughput instance shape, so qps numbers are comparable
    return hnsw.HNSWConfig(
        cap=cap, dim=dim, M=12, M_up=6, num_upper=2, ef_search=48,
        ef_construction=48, k=10, m_bits=64, rho=1.0, eps=0.1,
        use_filter=False, lsm_mem_cap=256, lsm_levels=2, lsm_fanout=8,
        n_expand=1, batch_expand=4)


def make_stream(rng, n_ops: int, n_base: int, fresh: np.ndarray,
                base: np.ndarray):
    """80/10/10 interleaved stream; deletes target distinct base ids."""
    stream = []
    victims = list(rng.permutation(n_base))
    fi = 0
    for _ in range(n_ops):
        r = rng.random()
        if r < 0.8 or (r >= 0.9 and not victims) or (r < 0.9 and
                                                     fi >= len(fresh)):
            stream.append(("q", base[rng.integers(0, n_base)]))
        elif r < 0.9:
            stream.append(("i", fresh[fi]))
            fi += 1
        else:
            stream.append(("d", int(victims.pop())))
    return stream


SERVE_TRIALS = 2  # best-of-N full load drains (fresh index copy each):
                  # the reference takes its best trial, so the serve side
                  # must get the same chance against container jitter


def durability_probe(*, n: int, batch: int, dim: int, seed: int,
                     work_dir: str) -> dict:
    """A/B-measure the group-commit tax on acked-insert latency.

    Both arms drive the identical closed-loop insert stream (submit one
    batch, drain, repeat — so each latency sample is one micro-batch's
    execution, not queue depth) through identically configured engines;
    the only difference is ``ServeConfig.wal``.  With the WAL on, every
    batch's record is fsync'd before its tickets resolve (the default
    ``group_commit_n=1``), so the p50 delta *is* the durability cost an
    acked insert pays.  Best-of-``SERVE_TRIALS`` per arm: trial 0
    absorbs compilation.
    """
    cfg = _cfg(dim, n + 4 * batch + 64)
    base = make_clustered_vectors(batch, dim=dim, seed=seed + 21)
    vecs = make_clustered_vectors(n, dim=dim, seed=seed + 22)
    idx0 = LSMVecIndex.build(cfg, base)
    arms = {}
    for arm in ("nowal", "wal"):
        best = None
        for trial in range(SERVE_TRIALS):
            wal_cfg = None
            if arm == "wal":
                wal_cfg = WalConfig(dir=os.path.join(
                    work_dir, f"probe_{arm}_t{trial}"))
            eng = ServeEngine(idx0.clone(), ServeConfig(
                query_batch=batch, insert_batch=batch, delete_batch=batch,
                adaptive_windows=False, query_window=0.0, insert_window=0.0,
                delete_window=0.0, strict_order=False, wal=wal_cfg))
            for b in range(0, n, batch):
                for v in vecs[b:b + batch]:
                    eng.submit_insert(v)
                eng.drain()
            m = eng.metrics.snapshot()
            eng.close()
            cur = {"p50": m["insert"]["p50_ms"], "p99": m["insert"]["p99_ms"]}
            if best is None or cur["p50"] < best["p50"]:
                best = cur
        arms[arm] = best
    p50_wal, p50_raw = arms["wal"]["p50"], arms["nowal"]["p50"]
    return {
        "probe_n": n,
        "acked_insert_p50_ms": round(p50_wal, 3),
        "acked_insert_p99_ms": round(arms["wal"]["p99"], 3),
        "nowal_insert_p50_ms": round(p50_raw, 3),
        "nowal_insert_p99_ms": round(arms["nowal"]["p99"], 3),
        "overhead_p50_pct": round(
            (p50_wal - p50_raw) / max(p50_raw, 1e-9) * 100.0, 1),
    }



def _host_cores() -> int:
    """CPU cores actually available to this process.

    The wall-clock halves of the §13 gates (fanout speedup, overlapped
    p99) measure *parallelism*: on a single-core host every device
    stream timeslices one core and no dispatch order can beat the sum
    of the work, so the probes record the measured ratio alongside
    this count and the boolean gates only bind where >=2 cores can
    express the overlap (CI pins 4-core runners)."""
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:          # pragma: no cover - non-linux
        return os.cpu_count() or 1


class _Collected:
    """A pre-collected per-shard result wrapped as a `SearchHandle` —
    the sequential arm of the fanout probe reuses the exact production
    merge (`ShardedDispatch.collect`) over results it already blocked
    for one at a time."""

    def __init__(self, res):
        self._res = res

    def is_ready(self) -> bool:
        return True

    def collect(self):
        return self._res


def fanout_probe(*, n_base: int, dim: int, batch: int, seed: int,
                 shards: int = 4, reps: int = 8) -> dict:
    """Sequential vs two-phase shard fan-out on one P-shard backend.

    Both arms run the identical stable host merge; the sequential arm
    blocks on each shard before dispatching the next (the pre-§13
    fan-out), the async arm enqueues every shard's device work first
    and collects once, paying max-shard instead of sum-of-shard
    latency.  Results must be bit-identical between the arms on every
    trial.  Meaningful speedups need one device per shard (CI forces
    ``--xla_force_host_platform_device_count``); on fewer devices the
    device stream serializes and the ratio approaches 1.
    """
    cfg = _cfg(dim, -(-n_base // shards) + 64)
    base = make_clustered_vectors(n_base, dim=dim, seed=seed + 31)
    be = ShardedBackend(cfg, shards).build(base, seed=seed)
    queries = make_clustered_vectors(batch, dim=dim, seed=seed + 32)

    def seq_search():
        done = []
        for sh in be.shards:
            # dispatch + immediate collect: shard s+1's device work only
            # starts after shard s's results reach the host
            done.append(_Collected(sh.dispatch_search(queries,
                                                      k=cfg.k).collect()))
        return ShardedDispatch(done, cfg.cap, cfg.k).collect()

    be.search(queries, k=cfg.k)     # compile both arms' shapes
    seq_search()
    t_seq = t_async = float("inf")
    parity = True
    for _ in range(SERVE_TRIALS):
        t0 = time.monotonic()
        for _ in range(reps):
            r_seq = seq_search()
        t_seq = min(t_seq, time.monotonic() - t0)
        t0 = time.monotonic()
        for _ in range(reps):
            r_async = be.search(queries, k=cfg.k)
        t_async = min(t_async, time.monotonic() - t0)
        parity = parity and bool(
            np.array_equal(r_seq.ids, r_async.ids)
            and np.allclose(r_seq.dists, r_async.dists,
                            rtol=1e-6, atol=1e-6))
    seq_ms = t_seq / reps * 1e3
    async_ms = t_async / reps * 1e3
    return {"shards": shards, "batch": batch,
            "seq_ms": round(seq_ms, 3), "async_ms": round(async_ms, 3),
            "ratio": round(async_ms / max(seq_ms, 1e-9), 3),
            "parity": parity, "host_cores": _host_cores()}


def fused_probe(*, n_base: int, dim: int, batch: int, seed: int,
                reps: int = 16, enabled: bool = False) -> dict:
    """Fused megakernel vs `while_loop` beam search, A/B on one corpus.

    Two identically seeded builds — one with ``fused_beam`` on — serve
    the same snapshot query batch after the same tombstone churn, with
    ``record_heat=False`` on both arms (a capability the fused path
    introduced; the while path ignores the flag, DESIGN.md §15).  The
    probe reports query-batch p50 per arm (best of ``SERVE_TRIALS``
    passes of ``reps`` timed calls), bit-parity of the returned ids,
    the brute-force recall ratio, and a zero-retrace check on the fused
    arm.  The p50 half of the criterion binds only under
    ``--fused-beam`` (the 1.05x band absorbs CPU-oracle-route noise —
    on a CPU host both arms lower to `while_loop` HLO, so the ratio
    hovers at 1.0; on TPU the megakernel's single launch must win).
    """
    cfg_w = _cfg(dim, n_base + 64)
    cfg_f = cfg_w._replace(fused_beam=True)
    base = make_clustered_vectors(n_base, dim=dim, seed=seed + 51)
    queries = make_clustered_vectors(batch, dim=dim, seed=seed + 52)
    dels = np.arange(0, n_base // 8, dtype=np.int64)
    ix_w = LSMVecIndex.build(cfg_w, base, seed=seed)
    ix_f = LSMVecIndex.build(cfg_f, base, seed=seed)
    for ix in (ix_w, ix_f):
        ix.delete(dels)
    p = SearchParams(use_snapshot=True, pad_to=batch, record_heat=False)
    r_w = ix_w.search(queries, k=cfg_w.k, params=p)       # also warmup
    r_f = ix_f.search(queries, k=cfg_w.k, params=p)
    parity = bool(np.array_equal(np.asarray(r_w.ids), np.asarray(r_f.ids)))
    warm = dict(ix_f.trace_counts())
    live = np.ones(n_base, bool)
    live[dels] = False
    truth = brute_force_knn(base, queries, cfg_w.k, live=live)
    rec_w = recall_at_k(np.asarray(r_w.ids), truth)
    rec_f = recall_at_k(np.asarray(r_f.ids), truth)

    def measure(ix):
        best = None
        for _ in range(SERVE_TRIALS):
            lat = []
            for _ in range(reps):
                t0 = time.monotonic()
                res = ix.search(queries, k=cfg_w.k, params=p)
                np.asarray(res.ids)                       # force host sync
                lat.append((time.monotonic() - t0) * 1e3)
            p50 = float(np.percentile(lat, 50))
            best = p50 if best is None else min(best, p50)
        return best

    while_p50 = measure(ix_w)
    fused_p50 = measure(ix_f)
    return {"enabled": bool(enabled),
            "while_p50_ms": round(while_p50, 3),
            "fused_p50_ms": round(fused_p50, 3),
            "p50_ratio": round(fused_p50 / max(while_p50, 1e-9), 3),
            "parity": parity,
            "recall_ratio": round(rec_f / max(rec_w, 1e-9), 4),
            "zero_retraces": dict(ix_f.trace_counts()) == warm,
            "host_cores": _host_cores()}


def overlap_probe(*, n_base: int, n_ops: int, batch: int, dim: int,
                  seed: int) -> dict:
    """Query p99 while consolidating (overlapped) vs no maintenance.

    A 30%-churn stream (70/15/15 query/insert/delete) over a
    lazy-delete index.  The ``nomaint`` arm never consolidates — the
    tail an undisturbed server shows; the ``overlap`` arm triggers the
    double-buffered repair aggressively (low ratio, tight cadence).
    The §13 claim under test: because the repair's device work runs
    while queries keep serving from the live state — the cutover is a
    pointer swap at a poll or write barrier — the query tail must not
    stretch beyond 1.3x the undisturbed arm's p99.  Both arms replay
    the identical stream from clones of one built index, including the
    same warmup (which pre-traces the repair in the overlap arm so
    compilation never lands in the timed region).
    """
    cap = n_base + max(n_ops // 4, 8) + 4 * batch + 64
    cfg = _cfg(dim, cap)._replace(lazy_delete=True)
    rng = np.random.default_rng(seed + 41)
    base = make_clustered_vectors(n_base, dim=dim, seed=seed + 42)
    fresh = make_clustered_vectors(max(n_ops // 4, 8), dim=dim,
                                   seed=seed + 43)
    stream, victims, fi = [], list(rng.permutation(n_base // 2)), 0
    for _ in range(n_ops):
        r = rng.random()
        if r < 0.7 or (r >= 0.85 and not victims) or (r < 0.85 and
                                                      fi >= len(fresh)):
            stream.append(("q", base[rng.integers(0, n_base)]))
        elif r < 0.85:
            stream.append(("i", fresh[fi]))
            fi += 1
        else:
            stream.append(("d", int(victims.pop())))
    idx0 = LSMVecIndex.build(cfg, base)
    warm_del = [int(v) for v in
                rng.permutation(np.arange(n_base // 2, n_base))[:n_base // 8]]
    pols = {
        "nomaint": MaintenancePolicy(tombstone_ratio=None,
                                     consolidate_ratio=None,
                                     heat_budget=None),
        "overlap": MaintenancePolicy(tombstone_ratio=None,
                                     consolidate_ratio=0.05,
                                     heat_budget=None, check_every=2,
                                     overlap=True),
    }
    arms = {}
    for arm, pol in pols.items():
        best = None
        for _ in range(SERVE_TRIALS):
            eng = ServeEngine(idx0.clone(), ServeConfig(
                query_batch=batch, insert_batch=batch, delete_batch=batch,
                adaptive_windows=False, query_window=0.0,
                insert_window=0.0, delete_window=0.0, strict_order=False,
                maintenance=pol))
            # warmup: compile every serving shape AND (overlap arm) the
            # background repair — enough deletes to cross the trigger,
            # then a forced maintenance pass claimed to completion
            for i in range(4):
                eng.submit_query(base[i])
            for v in fresh[:4]:
                eng.submit_insert(v)
            eng.drain()
            for v in warm_del:
                eng.submit_delete(v)
            eng.drain()
            eng.maintenance.run_if_due(force=True)
            eng.maintenance.barrier()
            # the cutover left the search snapshot stale: insert now to
            # compile the *plain* insert path (no snapshot to patch),
            # then query to re-resolve — in the timed region a
            # consolidation-then-insert sequence replays exactly this
            for v in fresh[4:8]:
                eng.submit_insert(v)
            eng.drain()
            eng.submit_query(base[0])
            eng.drain()
            eng.backend.sync()
            eng.metrics = type(eng.metrics)()   # timed region starts clean
            for op, payload in stream:
                if op == "q":
                    eng.submit_query(payload)
                elif op == "i":
                    eng.submit_insert(payload)
                else:
                    eng.submit_delete(payload)
            eng.drain()
            eng.backend.sync()
            m = eng.metrics.snapshot()
            cur = {"p99": m["query"]["p99_ms"],
                   "cons": eng.maintenance.consolidations,
                   "holds": m["write_holds"]}
            eng.close()
            if best is None or cur["p99"] < best["p99"]:
                best = cur
        arms[arm] = best
    p99_no, p99_ov = arms["nomaint"]["p99"], arms["overlap"]["p99"]
    return {"p99_nomaint_ms": round(p99_no, 3),
            "p99_overlap_ms": round(p99_ov, 3),
            "ratio": round(p99_ov / max(p99_no, 1e-9), 3),
            "consolidations": arms["overlap"]["cons"],
            "write_holds": arms["overlap"]["holds"],
            "host_cores": _host_cores()}


def run(*, n_base: int, n_ops: int, batch: int, dim: int, seed: int,
        n_expand: int, mode: str, shards: int = 1, wal: bool = False,
        ckpt_every: int | None = None, tier: bool = False,
        fused: bool = False, work_dir: str | None = None) -> dict:
    rng = np.random.default_rng(seed)
    n_fresh = max(n_ops // 8, 8)
    cap = n_base + n_fresh + 4 * batch + 64
    cfg = _cfg(dim, cap)
    # per-shard id space: the shard's slice of the corpus plus slack for
    # routed inserts and hash imbalance
    cfg_shard = _cfg(dim, -(-(n_base + n_fresh) // shards) + 4 * batch + 64)
    if tier:
        # --tier: two-lane store under live churn (DESIGN.md §12); the
        # sequential recall baseline below shares the config but never
        # runs maintenance, so it stays all-hot (≡ dense)
        cfg = cfg._replace(tier=True, level_scale=0.25)
        cfg_shard = cfg_shard._replace(tier=True, level_scale=0.25)
    if fused:
        # --fused-beam: the main drain serves snapshot queries through
        # the megakernel path (DESIGN.md §15); the sequential recall
        # baseline keeps the while_loop path, so the recall criterion
        # doubles as a cross-path guard
        cfg = cfg._replace(fused_beam=True)
        cfg_shard = cfg_shard._replace(fused_beam=True)
    base = make_clustered_vectors(n_base, dim=dim, seed=seed)
    fresh = make_clustered_vectors(n_fresh, dim=dim, seed=seed + 1)
    stream = make_stream(rng, n_ops, n_base, fresh, base)
    mix = {op: round(sum(1 for o, _ in stream if o == op) / n_ops, 3)
           for op in ("q", "i", "d")}

    # Serving configuration: query micro-batches coalesce 2x wider than
    # the write pad width (at saturation the scheduler's advantage is
    # filling large fixed shapes from the backlog — but going wider still
    # loses more to pad-lane waste on partial batches than it gains in
    # dispatch amortization), and beams expand 2x
    # wider than the reference path — on a churn-damaged graph the
    # vmapped batch runs as long as its slowest lane, and wider expansion
    # halves the straggler trip count.  Recall is guarded by the
    # sequential-baseline criterion below.
    serve_cfg = ServeConfig(
        query_batch=2 * batch, insert_batch=batch, delete_batch=batch,
        query_window=0.0, insert_window=0.0, delete_window=0.0,
        strict_order=False,
        search=SearchParams(n_expand=2 * n_expand),
        maintenance=MaintenancePolicy(
            tombstone_ratio=0.25, heat_budget=None,
            # tier mode checks more often so demotion actually engages
            # within the smoke's short write stream
            check_every=2 if tier else 8,
            tier_policy=TierPolicy(hot_frac=0.25, max_demote=cap,
                                   max_promote=64) if tier else None))
    if work_dir is None:
        work_dir = tempfile.mkdtemp(prefix="serve_durability_")
    if shards > 1:
        backend0 = ShardedBackend(cfg_shard, shards).build(base, seed=seed)
    else:
        backend0 = LSMVecIndex.build(cfg, base)
    # warmup must compile every serving shape on every shard: extend the
    # warm insert run until the deterministic hash router has touched
    # each shard at least once (their deletes then cover the delete path
    # on the same shards; queries fan out to all shards regardless)
    n_warm = 3
    while shards > 1 and len(set(np.asarray(shard_of_seq(
            np.arange(n_base, n_base + n_warm), shards)))) < shards:
        n_warm += 1
    warm_vecs = make_clustered_vectors(n_warm, dim=dim, seed=seed + 9)
    # a second shard-covering wave, inserted while every shard's query
    # snapshot is current, compiles the incremental snapshot-patch path
    # (DESIGN.md §13) — its start seq is n_base + n_warm, so the cover
    # must be recomputed from there
    n_warm2 = 3
    while shards > 1 and len(set(np.asarray(shard_of_seq(
            np.arange(n_base + n_warm, n_base + n_warm + n_warm2),
            shards)))) < shards:
        n_warm2 += 1
    warm_vecs2 = make_clustered_vectors(n_warm2, dim=dim, seed=seed + 10)

    wall = float("inf")
    eng = warm_traces = load_traces = None
    for trial in range(SERVE_TRIALS):
        # fresh copy: the previous trial's donated jits consumed its state
        idx_t = backend0.clone()
        serve_cfg_t = serve_cfg
        if wal:
            # --wal: the headline drain runs durable — per-trial WAL (and
            # checkpoint, under --ckpt-every) directories, so trials never
            # replay each other's records
            serve_cfg_t = dataclasses.replace(
                serve_cfg,
                wal=WalConfig(dir=os.path.join(work_dir,
                                               f"serve_wal_t{trial}")),
                ckpt_dir=(os.path.join(work_dir, f"serve_ckpt_t{trial}")
                          if ckpt_every else None),
                maintenance=dataclasses.replace(
                    serve_cfg.maintenance, checkpoint_every=ckpt_every))
        eng_t = ServeEngine(idx_t, serve_cfg_t)

        # warmup: compile every serving shape outside the timed region.
        # The warmup inserts are deleted again right away, so the index
        # content entering the load phase is exactly `base` (only the id
        # space advanced by n_warm + n_warm2) — recall accounting relies
        # on it.
        warm_ids = [eng_t.submit_insert(v) for v in warm_vecs]
        for i in range(5):
            eng_t.submit_query(base[i])
        eng_t.drain()
        for t in warm_ids:
            eng_t.submit_delete(t.result())
        eng_t.drain()
        # patch wave: queries resolve every shard's snapshot, then a
        # covering insert run compiles the per-shard snapshot-patch jit
        for i in range(5):
            eng_t.submit_query(base[i])
        eng_t.drain()
        warm_ids2 = [eng_t.submit_insert(v) for v in warm_vecs2]
        eng_t.drain()
        for t in warm_ids2:
            eng_t.submit_delete(t.result())
        eng_t.drain()
        idx_t.sync()
        warm_t = dict(idx_t.trace_counts())

        # the load phase: saturation drain of the interleaved stream
        for op, payload in stream:
            if op == "q":
                eng_t.submit_query(payload)
            elif op == "i":
                eng_t.submit_insert(payload)
            else:
                eng_t.submit_delete(payload)
        t0 = time.monotonic()
        eng_t.drain()
        idx_t.sync()
        wall_t = time.monotonic() - t0
        if wall_t < wall:
            wall = wall_t
        # keep the last trial's artifacts for the recall/reference phases
        eng = eng_t
        warm_traces, load_traces = warm_t, dict(idx_t.trace_counts())

    new_traces = {k: load_traces[k] - warm_traces.get(k, 0)
                  for k in load_traces if load_traces[k]
                  != warm_traces.get(k, 0)}

    # ---- fixed-batch reference QPS (the PR-1 path): the SAME op stream
    # dispatched directly as fixed-shape batches — arrival-order runs of
    # `batch` per op, no scheduler, reference beam shape — from the same
    # starting index, best of SERVE_TRIALS passes.  Both sides pay the
    # identical write stream, so the ratio isolates the serving layer
    # (coalescing + padding + snapshot reads + scheduling) from the
    # box's read/write cost balance: a read-only reference flips the
    # criterion with the hardware — on a box with cheap batched reads
    # it penalizes the serve drain for write time no scheduler can
    # avoid, on one with dear reads it flatters it.
    n_stream_q = sum(1 for o, _ in stream if o == "q")
    n_stream_i = sum(1 for o, _ in stream if o == "i")
    n_stream_d = sum(1 for o, _ in stream if o == "d")
    gids0 = np.asarray(backend0.initial_ids(), np.int64)
    dt_fixed = float("inf")
    for _ in range(SERVE_TRIALS):
        idx_f = backend0.clone()
        # compile this clone's shapes outside the timed region (clone()
        # gives fresh jit caches), mirroring the serve trials' warmup:
        # the same warm inserts (shard-covering under --shards) are
        # deleted again, so only the id space advances before timing
        wid = np.asarray(idx_f.insert_batch(warm_vecs, pad_to=batch).ids,
                         np.int64)
        idx_f.delete_batch(wid, pad_to=batch)
        ref_params = SearchParams(n_expand=n_expand, record_heat=False,
                                  pad_to=batch)
        idx_f.search(base[:batch], k=cfg.k, params=ref_params)
        # insert against the current snapshot: compile the patch path
        # outside the timed region, mirroring the serve warmup
        wid2 = np.asarray(idx_f.insert_batch(warm_vecs2, pad_to=batch).ids,
                          np.int64)
        idx_f.delete_batch(wid2, pad_to=batch)
        idx_f.sync()
        bufs = {"q": [], "i": [], "d": []}

        def _flush(op, idx_f=idx_f, bufs=bufs):
            items = bufs[op]
            if not items:
                return
            if op == "q":
                idx_f.search(np.stack(items), k=cfg.k, params=ref_params)
            elif op == "i":
                idx_f.insert_batch(np.stack(items), pad_to=batch)
            else:
                idx_f.delete_batch(gids0[np.asarray(items, np.int64)],
                                   pad_to=batch)
            items.clear()

        t0 = time.monotonic()
        for op, payload in stream:
            bufs[op].append(payload)
            if len(bufs[op]) == batch:
                _flush(op)
        for op in ("q", "i", "d"):
            _flush(op)
        idx_f.sync()
        dt_fixed = min(dt_fixed, time.monotonic() - t0)
    fixed_qps = n_stream_q / dt_fixed

    m = eng.metrics.snapshot()
    serve_qps = n_stream_q / wall

    # ---- recall: engine vs the sequential per-item baseline --------------
    # Same op stream applied one-by-one to a bare index (the sequential
    # reference), then one shared eval query set through both.  The serve
    # index's id space carries the 3 (deleted) warmup inserts, so its
    # ground truth is built in its own id space.
    idx_seq = LSMVecIndex.build(cfg, base)
    live = np.ones(n_base + n_fresh, bool)
    n_ins = 0
    for op, payload in stream:
        if op == "i":
            idx_seq.insert(payload)
            n_ins += 1
        elif op == "d":
            idx_seq.delete(payload)
            live[payload] = False
    live_all = live[:n_base + n_ins].copy()
    eval_q = make_clustered_vectors(64, dim=dim, seed=seed + 3)
    allv_seq = np.concatenate([base, fresh[:n_ins]])
    truth_seq = brute_force_knn(allv_seq, eval_q, cfg.k, live=live_all)
    recall_seq = recall_at_k(idx_seq.search(eval_q, k=cfg.k).ids,
                             truth_seq)

    serve_tickets = [eng.submit_query(q) for q in eval_q]
    eng.drain()
    ids_serve = np.stack([t.result().ids for t in serve_tickets])
    allv_serve = np.concatenate([base, warm_vecs, warm_vecs2,
                                 fresh[:n_ins]])
    live_serve = np.concatenate(
        [live_all[:n_base], np.zeros(n_warm + n_warm2, bool),
         live_all[n_base:]])
    truth_serve = brute_force_knn(allv_serve, eval_q, cfg.k,
                                  live=live_serve)
    recall_serve = recall_at_k(ids_serve, truth_serve)

    # ---- durability: group-commit overhead A/B probe (DESIGN.md §11) -----
    probe = durability_probe(n=64 if mode == "smoke" else 512, batch=batch,
                             dim=dim, seed=seed, work_dir=work_dir)

    # ---- async serving spine probes (DESIGN.md §13) ----------------------
    fanout = fanout_probe(
        n_base=256 if mode == "smoke" else 2048, dim=dim, batch=2 * batch,
        seed=seed, shards=4, reps=8 if mode == "smoke" else 32)
    overlap = overlap_probe(
        n_base=256 if mode == "smoke" else 1024,
        n_ops=192 if mode == "smoke" else 1024,
        batch=batch, dim=dim, seed=seed)

    # ---- fused megakernel A/B probe (DESIGN.md §15) ----------------------
    fusedp = fused_probe(
        n_base=256 if mode == "smoke" else 2048, dim=dim, batch=batch,
        seed=seed, reps=8 if mode == "smoke" else 24, enabled=fused)

    doc = {
        "meta": {
            "mode": mode, "backend": jax.default_backend(),
            "shards": shards, "tier": bool(tier),
            "n_base": n_base, "n_ops": n_ops, "mix": mix, "dim": dim,
            "batch": batch, "n_expand": n_expand,
            # the serving layer's own knobs (the reference path runs the
            # PR-1 shape `batch`/`n_expand` above; wider coalescing and
            # beams are the scheduler's prerogative, recall-guarded)
            "serve_query_batch": serve_cfg.query_batch,
            "serve_n_expand": serve_cfg.search.n_expand,
            "config": {k: v for k, v in
                       (cfg_shard if shards > 1 else cfg)
                       ._asdict().items()},
        },
        "serve": {
            "qps": round(serve_qps, 1),
            "insert_ops_s": round(n_stream_i / wall, 1),
            "delete_ops_s": round(n_stream_d / wall, 1),
            "query_p50_ms": m["query"]["p50_ms"],
            "query_p99_ms": m["query"]["p99_ms"],
            "mean_query_batch": m["query"]["mean_batch"],
            "snapshot_resolves": m["snapshot_resolves"],
            "compactions": eng.maintenance.compactions,
            "tier_passes": eng.maintenance.tier_passes,
            "tier_demoted": eng.maintenance.tier_demoted,
            "tier_promoted": eng.maintenance.tier_promoted,
            "wall_s": round(wall, 3),
        },
        "baseline": {
            "fixed_batch_qps": round(fixed_qps, 1),
            "qps_ratio": round(serve_qps / fixed_qps, 3),
        },
        "recall": {
            "serve": round(recall_serve, 4),
            "sequential": round(recall_seq, 4),
            "delta": round(recall_serve - recall_seq, 4),
        },
        "retraces": {
            "after_warmup": warm_traces,
            "after_load": load_traces,
            "new_during_load": new_traces,
        },
        "fanout": fanout,
        "overlap": overlap,
        "fused": fusedp,
        "durability": {
            # main-drain accounting (zeros unless --wal): records appended
            # vs group commits fsync'd, and covering checkpoints written
            "wal_enabled": bool(wal),
            "ckpt_every": ckpt_every,
            "wal_records": m["wal"]["records"],
            "wal_commits": m["wal"]["commits"],
            "checkpoints": m["maintenance"]["checkpoint"],
            **probe,
        },
        "criteria": {
            "zero_retraces_after_warmup": not new_traces,
            "qps_within_10pct_of_fixed": bool(
                serve_qps >= 0.9 * fixed_qps),
            # one-sided: serving must not LOSE recall vs the sequential
            # per-item reference; exceeding it (batched inserts with
            # multi-expansion candidate search + intra-batch links build a
            # better-connected graph) is a win, not a violation.  Under
            # sharding the execution differs structurally (cross-shard
            # merge over hash partitions), so the gate is the 0.95x
            # floor of the single-device sequential baseline instead of
            # the ±0.01 band (DESIGN.md §10); same under --tier, where
            # cold candidates route through the quantized lane + exact
            # rerank while the sequential baseline stays all-hot
            "recall_within_0p01": bool(
                recall_serve >= recall_seq - 0.01
                if shards == 1 and not tier
                else recall_serve >= 0.95 * recall_seq),
            "wal_overhead_within_15pct": bool(
                probe["overhead_p50_pct"] <= 15.0),
            # the §13 gates: two-phase fan-out must beat blocking
            # per-shard dispatch by >=30% (needs one device per shard —
            # CI forces 4 host devices), and overlapped consolidation
            # must hold the query tail within 1.3x of an undisturbed
            # server's.  Bit-parity between the arms is folded into the
            # fanout gate: a fast merge that changes results is a fail.
            # The wall-clock halves bind only on hosts with >=2 cores
            # (see `_host_cores`): a single core serializes every
            # device stream, so no dispatch order can show the overlap
            # — the measured ratios are still recorded above.
            "fanout_dispatch_leq_0p7x": bool(
                fanout["parity"] and (fanout["ratio"] <= 0.7
                                      or fanout["host_cores"] < 2)),
            "overlap_p99_leq_1p3x": bool(
                overlap["consolidations"] >= 1
                and (overlap["ratio"] <= 1.3
                     or overlap["host_cores"] < 2)),
            # the §15 gate: the fused path must return bit-identical
            # ids, hold the recall ratio, and never retrace — always;
            # the p50 half (fused at or below while_loop, with a 1.05x
            # noise band for the CPU oracle route where both arms lower
            # to the same while_loop HLO) binds only when the drain
            # actually served fused (--fused-beam)
            "fused_parity_p50_leq_while": bool(
                fusedp["parity"] and fusedp["zero_retraces"]
                and fusedp["recall_ratio"] >= 0.999
                and (fusedp["p50_ratio"] <= 1.05 or not fused)),
        },
    }
    return doc


# ---------------------------------------------------------------------------
# crash-recovery mode (the CI `crash-recovery-smoke` job, DESIGN.md §11)
# ---------------------------------------------------------------------------

CRASH_MATRIX = (("pre_commit", 3), ("post_commit_pre_apply", 3),
                ("mid_checkpoint", 2), ("mid_consolidation", 1))


def _crash_ops(rng, n_ops: int, dim: int):
    """70/15/15 insert/delete/query client stream for the harness."""
    ops, n_ins = [], 0
    for _ in range(n_ops):
        r = rng.random()
        if r < 0.7 or n_ins < 5:
            ops.append(("insert",
                        rng.standard_normal(dim).astype(np.float32)))
            n_ins += 1
        elif r < 0.85:
            ops.append(("delete", int(rng.integers(0, n_ins))))
        else:
            ops.append(("query",
                        rng.standard_normal(dim).astype(np.float32)))
    return ops


def _expected_live(ops, acked):
    """Replay the acked subset into {ext_id: vector} (the survivor set)."""
    live = {}
    for i, (kind, payload) in enumerate(ops):
        if i not in acked:
            continue
        if kind == "insert":
            live[int(acked[i])] = np.asarray(payload, np.float32)
        elif kind == "delete":
            live.pop(int(payload), None)
    return live


def _recovered_recall(engine, live: dict, k: int, eval_q) -> float:
    """Recall of the recovered engine against brute force over its own
    acked live set.  Duplicate-tolerant: a client retry whose original
    record was durable-but-unacked leaves two copies of one vector under
    two external ids (at-least-once delivery), so a truth slot counts as
    hit when *any* external id carrying the same vector is returned."""
    exts = list(live.keys())
    allv = np.stack([live[e] for e in exts])
    gkey = [v.tobytes() for v in allv]
    ext2g = {e: gkey[i] for i, e in enumerate(exts)}
    truth = brute_force_knn(allv, eval_q, k)
    tickets = [engine.submit_query(q) for q in eval_q]
    engine.drain()
    hits = 0
    for row, t in zip(truth, tickets):
        got = {ext2g.get(int(e)) for e in np.asarray(t.result().ids)}
        got.discard(None)
        hits += sum(1 for j in row if gkey[int(j)] in got)
    return hits / (k * len(eval_q))


def run_crash_recovery(*, n_ops: int, dim: int, seed: int,
                       work_dir: str) -> dict:
    """Kill at every injection point, restart, prove nothing acked was
    lost and recall holds a floor against an uninterrupted run."""
    cfg = _cfg(dim, n_ops + 128)
    ops = _crash_ops(np.random.default_rng(seed), n_ops, dim)
    eval_q = np.random.default_rng(seed + 5).standard_normal(
        (32, dim)).astype(np.float32)
    maint_default = MaintenancePolicy(checkpoint_every=4)

    def recover(root, maint, injector=None):
        scfg = ServeConfig(
            query_batch=8, insert_batch=8, delete_batch=8,
            adaptive_windows=False, query_window=0.0, insert_window=0.0,
            delete_window=0.0,
            wal=WalConfig(dir=os.path.join(root, "wal")),
            ckpt_dir=os.path.join(root, "ckpt"), maintenance=maint)
        return ServeEngine.recover(
            scfg, fresh_backend=lambda: LSMVecIndex(cfg, seed=1),
            restore_backend=lambda d: LSMVecIndex.restore(cfg, d),
            injector=injector)

    # uninterrupted reference: same stream, no injector — its recall is
    # the floor every crashed-and-recovered run must hold
    ref_root = os.path.join(work_dir, "reference")
    ref = run_with_recovery(
        policy=RestartPolicy(ckpt_dir=os.path.join(ref_root, "ckpt")),
        make_engine=lambda inj: recover(ref_root, maint_default),
        ops=ops, chunk=10)
    ref_recall = _recovered_recall(
        ref["engine"], _expected_live(ops, ref["acked"]), cfg.k, eval_q)

    points, ok = {}, True
    for point, hit in CRASH_MATRIX:
        maint = maint_default
        if point == "mid_consolidation":
            # consolidation must actually trigger for the hook to fire
            maint = MaintenancePolicy(checkpoint_every=4, check_every=2,
                                      consolidate_ratio=0.05)
        root = os.path.join(work_dir, point)
        injector = FailureInjector(fail_points={point: hit})
        out = run_with_recovery(
            policy=RestartPolicy(ckpt_dir=os.path.join(root, "ckpt"),
                                 wal_dir=os.path.join(root, "wal")),
            make_engine=lambda inj, r=root, m=maint: recover(r, m, inj),
            ops=ops, injector=injector, chunk=10)
        try:
            summary = verify_acked_writes(out["engine"], ops, out["acked"])
            zero_loss = True
        except AssertionError as e:
            summary = {"live": 0, "deleted": 0, "searched": 0,
                       "lost": str(e)}
            zero_loss = False
        recall = _recovered_recall(
            out["engine"], _expected_live(ops, out["acked"]), cfg.k, eval_q)
        fired = out["restarts"] >= 1
        recall_ok = recall >= ref_recall - 0.05
        p_ok = fired and zero_loss and recall_ok
        ok = ok and p_ok
        points[point] = {
            "fired": fired, "restarts": out["restarts"],
            "retried": out["retried"], "zero_acked_loss": zero_loss,
            "recall": round(recall, 4), "recall_ok": recall_ok,
            "ok": p_ok, **summary,
        }
    return {"mode": "crash-recovery", "n_ops": n_ops, "dim": dim,
            "seed": seed, "reference_recall": round(ref_recall, 4),
            "points": points, "ok": ok}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny run; validate the JSON schema only")
    ap.add_argument("--out", default=None,
                    help="output path (default: <repo>/BENCH_serve.json)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shards", type=int, default=1,
                    help="serve through a ShardedBackend of P shards "
                         "(1 = single-device LSMVecIndex)")
    ap.add_argument("--wal", action="store_true",
                    help="run the main serve drain with the group-"
                         "committed WAL on (acks imply durability)")
    ap.add_argument("--tier", action="store_true",
                    help="serve a two-lane tiered store: background "
                         "maintenance demotes cold nodes to the int8 "
                         "lane while the drain runs (DESIGN.md §12)")
    ap.add_argument("--fused-beam", action="store_true",
                    help="serve the main drain through the fused beam-"
                         "search megakernel path (DESIGN.md §15) and "
                         "bind the fused A/B criterion, p50 half "
                         "included, even under --smoke")
    ap.add_argument("--ckpt-every", type=int, default=None,
                    help="with --wal: write a covering checkpoint every "
                         "N write batches during the main drain")
    ap.add_argument("--crash-recovery", action="store_true",
                    help="run the failure-injection matrix instead of "
                         "the load benchmark; exit nonzero on any "
                         "acked-write loss or recall-floor breach")
    ap.add_argument("--gate-async", action="store_true",
                    help="enforce the DESIGN.md \u00a713 criteria (fanout "
                         "dispatch <=0.7x, overlapped-consolidation p99 "
                         "<=1.3x) even under --smoke; exit nonzero on "
                         "breach")
    ap.add_argument("--workdir", default=None,
                    help="directory for WAL/checkpoint artifacts "
                         "(default: a fresh temp dir); CI uploads it on "
                         "failure")
    args = ap.parse_args(argv)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = args.out or os.path.join(root, "BENCH_serve.json")
    work_dir = args.workdir or tempfile.mkdtemp(prefix="serve_durability_")
    os.makedirs(work_dir, exist_ok=True)

    if args.crash_recovery:
        if args.smoke:
            doc = run_crash_recovery(n_ops=96, dim=16, seed=args.seed,
                                     work_dir=work_dir)
        else:
            doc = run_crash_recovery(n_ops=192, dim=32, seed=args.seed,
                                     work_dir=work_dir)
        print(json.dumps(doc, indent=1))
        for point, res in doc["points"].items():
            print(f"  {'PASS' if res['ok'] else 'FAIL'} {point} "
                  f"(restarts={res['restarts']} live={res['live']} "
                  f"searched={res['searched']} recall={res['recall']})")
        if args.out:
            write_bench_json(args.out, doc)
        return 0 if doc["ok"] else 1

    if args.smoke:
        # scale the corpus with the shard count so per-shard scale (and
        # per-shard graph navigability) matches the single-device smoke
        doc = run(n_base=256 * args.shards, n_ops=96, batch=16, dim=16,
                  seed=args.seed, n_expand=4, mode="smoke",
                  shards=args.shards, wal=args.wal, tier=args.tier,
                  fused=args.fused_beam, ckpt_every=args.ckpt_every,
                  work_dir=work_dir)
    else:
        doc = run(n_base=4096, n_ops=4096, batch=64, dim=64, seed=args.seed,
                  n_expand=4, mode="full", shards=args.shards, wal=args.wal,
                  tier=args.tier, fused=args.fused_beam,
                  ckpt_every=args.ckpt_every, work_dir=work_dir)

    validate_schema(doc)
    print(json.dumps(doc, indent=1))
    if args.smoke:
        if args.out:
            # an explicit --out in smoke mode gets the smoke doc (CI
            # uploads the measurement it produced); the committed full-
            # run JSON is only written by full runs
            write_bench_json(args.out, doc)
        gates = ()
        if args.gate_async:
            gates += ("fanout_dispatch_leq_0p7x", "overlap_p99_leq_1p3x")
        if args.fused_beam:
            gates += ("fused_parity_p50_leq_while",)
        if gates:
            for name in gates:
                print(f"  {'PASS' if doc['criteria'][name] else 'FAIL'} "
                      f"{name}")
            if not all(doc["criteria"][g] for g in gates):
                return 1
        print("smoke: schema OK (perf criteria not enforced)")
        return 0

    write_bench_json(out, doc)
    for name, ok in doc["criteria"].items():
        print(f"  {'PASS' if ok else 'FAIL'} {name}")
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    raise SystemExit(main())
