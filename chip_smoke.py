#!/usr/bin/env python3
"""Smoke run of the LSM-VEC serving path on a TPU — a correctness check,
not a benchmark.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # 4 shards on a four-chip host

Deployment: the ann-benchmarks ``sift-128-euclidean`` shape (SIFT1M) —
base vectors of 128 float32, squared L2, k = 10 — synthesised from
``--seed`` by `make_clustered_vectors`, and queried with held-out vectors
drawn from other seeds.  Everything runs in this one process, through the
entry points a user calls:

  1. stop unless ``jax.devices()[0].platform == "tpu"``;
  2. ``LSMVecIndex.build`` (``--four-chips``: a 4-shard `ShardedBackend`
     holding 4x as many vectors, one shard per chip);
  3. a `ServeEngine` over it, with a write-ahead log under ``smoke_out/``;
  4. query batches, one insert batch and one delete batch, the queries
     again;
  5. one ``dispatch_search(...).collect()`` against a blocking ``search``;
  6. one ``maintain("consolidate")``, and the queries once more.

Checks (each failure exits non-zero): recall@10 against brute force over
the live set is >= 0.90 before and after the updates; every acked insert
is its own nearest neighbour (1@1 >= 0.99); no deleted id is ever
returned; the two-phase search returns the blocking search's ids.

The last line of stdout is the device record
``{"ok": true, "device": {"platform", "kind", "count"}}``; the phase
times printed before it are smoke timings, not benchmark results.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import hnsw  # noqa: E402
from repro.core.backend import SearchParams  # noqa: E402
from repro.core.distributed import ShardedBackend  # noqa: E402
from repro.core.index import LSMVecIndex, brute_force_knn, recall_at_k  # noqa: E402
from repro.data.synth import make_clustered_vectors  # noqa: E402
from repro.kernels.gather_l2.ops import gather_l2, gather_l2_q8  # noqa: E402
from repro.kernels.gather_l2.ref import gather_l2_q8_ref, gather_l2_ref  # noqa: E402
from repro.kernels.l2_distance.ops import l2_distance  # noqa: E402
from repro.kernels.l2_distance.ref import l2_distance_ref  # noqa: E402
from repro.serve import ServeConfig, ServeEngine  # noqa: E402
from repro.serve.wal import WalConfig  # noqa: E402

DIM = 128                 # SIFT1M vector width
K = 10                    # recall@10, as ann-benchmarks reports SIFT1M
CAP = 1 << 20             # id space per index (shard): SIFT1M fits
N_DEFAULT = 1 << 18       # base vectors per index (shard); see CHANGES.md
# Both beams are raised from the default 48 (PERF.md, Findings):
# on this data the greedy upper-layer descent ends in the wrong cluster
# for most inserts and queries, and only a wide beam leaves it.  At
# ef_construction 48 an insert batch's candidate search stayed there
# for 94 of 256 inserts, which then hung off their batch siblings alone.
EF_SEARCH = 1024
EF_CONSTRUCTION = 384
N_QUERIES = 512
QUERY_BATCH = 128
N_UPDATE = 256            # vectors inserted, ids deleted
RECALL_FLOOR = 0.90
SELF_HIT_FLOOR = 0.99
N_SHARDS = 4
OUT_DIR = os.path.join(REPO, "smoke_out")   # git-ignored
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Phases:
    """Wall time and programs compiled per phase (host clock around work
    that ends in a device sync: smoke timings, not benchmark results)."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.last: tuple[str, float, int, float] = ("", 0.0, 0, 0.0)
        # compiles are reported from whichever thread compiles (the
        # sharded build runs one per shard)
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, secs: float, **_):
        if event == COMPILE_EVENT:
            with self._lock:
                self.compiles += 1
                self.compile_s += secs

    @contextlib.contextmanager
    def __call__(self, name: str):
        n0, c0, t0 = self.compiles, self.compile_s, time.perf_counter()
        yield
        row = (name, time.perf_counter() - t0, self.compiles - n0,
               self.compile_s - c0)
        self.last = row
        print(f"smoke timing (wall, not a benchmark): {row[0]}: {row[1]} s; "
              f"{row[2]} programs compiled in {row[3]} s")


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")
    print(f"check passed: {what}")


def require_chips(count: int):
    """The TPU devices this run needs, or exit non-zero naming what is
    missing.  Never falls back to another platform."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU: jax.devices()[0].platform is "
            f"{devs[0].platform!r}; this smoke runs only on the chip")
    if len(devs) < count:
        raise SystemExit(f"chip_smoke: needs {count} TPU chips, found "
                         f"{len(devs)}")
    return devs[:count]


def kernels_match_reference(seed: int) -> None:
    """The serving path's Pallas kernels, compiled for the chip, against
    their pure-JAX references on a small input."""
    rng = np.random.default_rng(seed)
    qs = jnp.asarray(rng.normal(size=(8, DIM)), jnp.float32)
    table = jnp.asarray(rng.normal(size=(1024, DIM)), jnp.float32)
    qtable = jnp.asarray(rng.integers(-127, 128, (1024, DIM)), jnp.int8)
    scales = jnp.asarray(rng.uniform(0.01, 0.1, 1024), jnp.float32)
    ids = jnp.asarray(rng.integers(-1, 1024, (8, 48)), jnp.int32)
    pairs = {
        "gather_l2": (gather_l2(qs, table, ids, use_pallas=True),
                      gather_l2_ref(qs, table, ids)),
        "gather_l2_q8": (gather_l2_q8(qs, qtable, scales, ids,
                                      use_pallas=True),
                         gather_l2_q8_ref(qs, qtable, scales, ids)),
        "l2_distance": (l2_distance(qs, table, use_pallas=True),
                        l2_distance_ref(qs, table)),
    }
    for name, (got, want) in pairs.items():
        got, want = np.asarray(got), np.asarray(want)
        check(np.array_equal(np.isinf(got), np.isinf(want))
              and np.allclose(got, want, rtol=1e-5, atol=1e-3),
              f"{name} Pallas kernel matches its reference (max abs diff "
              f"{np.nanmax(np.abs(np.where(np.isinf(want), 0, got - want)))})")


def serve_queries(engine: ServeEngine, qs: np.ndarray) -> np.ndarray:
    """Submit one query per row through the engine; ext ids [len(qs), K]."""
    tickets = [engine.submit_query(q) for q in qs]
    engine.drain()
    return np.stack([t.result().ids for t in tickets])


def truth(ref: np.ndarray, live: np.ndarray, qs: np.ndarray) -> np.ndarray:
    """Brute-force kNN over the live set — the plain reference, indexed
    by external id."""
    return brute_force_knn(jnp.asarray(ref), qs, K, live=jnp.asarray(live),
                           block=QUERY_BATCH)


def exercise(backend, base: np.ndarray, seed: int, phase: Phases,
             four_chips: bool) -> dict:
    """Serve the built backend through `ServeEngine` and run every check;
    returns the recall values."""
    n = len(base)
    rng = np.random.default_rng(seed + 3)
    queries = make_clustered_vectors(N_QUERIES, DIM, seed=seed + 1)
    fresh = make_clustered_vectors(N_UPDATE, DIM, seed=seed + 2)
    victims = rng.choice(n, N_UPDATE, replace=False)
    ref = np.concatenate([base, fresh])        # row = external id
    live = np.zeros(len(ref), bool)
    live[:n] = True
    shutil.rmtree(OUT_DIR, ignore_errors=True)
    engine = ServeEngine(backend, ServeConfig(
        query_batch=QUERY_BATCH, insert_batch=N_UPDATE,
        delete_batch=N_UPDATE,
        wal=WalConfig(dir=os.path.join(OUT_DIR, "wal"))))
    recalls = {}

    with phase("first query batch (compiles the search)"):
        found = serve_queries(engine, queries[:QUERY_BATCH])
    with phase(f"{N_QUERIES - QUERY_BATCH} more queries"):
        found = np.concatenate(
            [found, serve_queries(engine, queries[QUERY_BATCH:])])
    recalls["before_updates"] = recall_at_k(found, truth(ref, live, queries))
    check(recalls["before_updates"] >= RECALL_FLOOR,
          f"recall@{K} before updates {recalls['before_updates']} >= "
          f"{RECALL_FLOOR}")

    with phase(f"insert batch of {N_UPDATE} (acked through the WAL)"):
        tickets = [engine.submit_insert(x) for x in fresh]
        engine.drain()
        acked = np.asarray([t.result() for t in tickets], np.int64)
    check(np.array_equal(acked, np.arange(n, n + N_UPDATE)),
          "inserts acked with the next external ids in submission order")
    live[acked] = True
    with phase(f"delete batch of {N_UPDATE}"):
        tickets = [engine.submit_delete(int(v)) for v in victims]
        engine.drain()
        deleted = [t.result() for t in tickets]
    check(all(d is True for d in deleted), "every delete acked as applied")
    live[victims] = False

    def no_deleted(label: str, *ids: np.ndarray) -> None:
        back = np.isin(np.concatenate([i.ravel() for i in ids]), victims)
        check(not back.any(), f"no deleted id returned {label} "
              f"({int(back.sum())} found)")

    with phase("queries, self-hit and deleted-vector probes after updates"):
        found = serve_queries(engine, queries)
        self_hit = serve_queries(engine, fresh)
        ghosts = serve_queries(engine, base[victims])
    recalls["after_updates"] = recall_at_k(found, truth(ref, live, queries))
    check(recalls["after_updates"] >= RECALL_FLOOR,
          f"recall@{K} after updates {recalls['after_updates']} >= "
          f"{RECALL_FLOOR}")
    recalls["self_hit_1at1"] = float(np.mean(self_hit[:, 0] == acked))
    check(recalls["self_hit_1at1"] >= SELF_HIT_FLOOR,
          f"acked inserts found as their own nearest neighbour: 1@1 "
          f"{recalls['self_hit_1at1']} >= {SELF_HIT_FLOOR}")
    no_deleted("after the deletes", found, self_hit, ghosts)

    params = SearchParams(use_snapshot=True, pad_to=QUERY_BATCH,
                          record_heat=False)
    with phase("dispatch_search().collect() and blocking search"):
        two_phase = backend.dispatch_search(
            queries[:QUERY_BATCH], K, params=params).collect()
        blocking = backend.search(queries[:QUERY_BATCH], K, params=params)
    check(np.array_equal(two_phase.ids, blocking.ids),
          "dispatch_search().collect() ids equal the blocking search ids")

    if four_chips:
        spare_check(backend)
        with phase("overlapped consolidation on the spare devices"):
            backend.begin_maintain("consolidate")
            pending = [sh.maintenance_pending for sh in backend.shards]
            rep = backend.maintain("consolidate")
            backend.sync()
        for s, started in enumerate(pending):
            check(started, f"shard {s} started an overlapped repair")
    else:
        with phase('maintain("consolidate")'):
            rep = backend.maintain("consolidate")
            backend.sync()
    check(rep.reclaimed == N_UPDATE,
          f"consolidation reclaimed {rep.reclaimed} == {N_UPDATE} slots")

    with phase("queries and deleted-vector probes after consolidation"):
        found = serve_queries(engine, queries)
        ghosts = serve_queries(engine, base[victims])
    recalls["after_consolidate"] = recall_at_k(found,
                                               truth(ref, live, queries))
    check(recalls["after_consolidate"] >= RECALL_FLOOR,
          f"recall@{K} after consolidation {recalls['after_consolidate']} "
          f">= {RECALL_FLOOR}")
    no_deleted("after consolidation", found, ghosts)
    engine.close()
    return recalls


def spare_check(backend: ShardedBackend) -> None:
    """Where each shard's state lives, and where `_spare_device` would
    run its overlapped repair."""
    homes = [sh._home_device() for sh in backend.shards]
    check(len(set(homes)) == len(homes), "every shard has its own device")
    for s, sh in enumerate(backend.shards):
        on_home = all(leaf.devices() == {homes[s]}
                      for leaf in jax.tree.leaves(sh.state))
        check(on_home, f"shard {s} state is all on device {homes[s].id}")
        spare = sh._spare_device()
        owner = homes.index(spare) if spare in homes else None
        print(f"shard {s}: home device {homes[s].id}, spare device "
              f"{None if spare is None else spare.id} (home of shard "
              f"{owner})")


def peak_bytes(devs) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n", type=int, default=N_DEFAULT,
                    help="base vectors per index (per shard)")
    ap.add_argument("--four-chips", action="store_true",
                    help=f"serve {N_SHARDS} shards, one per chip, and run "
                         "nothing else")
    args = ap.parse_args(argv)
    sys.stdout.reconfigure(line_buffering=True)   # progress survives a kill

    devs = require_chips(N_SHARDS if args.four_chips else 1)
    print(f"device_kind={devs[0].device_kind} device_count={len(devs)}")
    print(f"compile cache: {enable_compile_cache()}")
    cfg = hnsw.HNSWConfig(cap=CAP, dim=DIM, ef_search=EF_SEARCH,
                          ef_construction=EF_CONSTRUCTION)
    print(f"config: {cfg._asdict()}")
    shards = N_SHARDS if args.four_chips else 1
    n_total = args.n * shards
    cut = "" if args.n >= 1_000_000 else \
        " (cut from SIFT1M's 1,000,000 per index for build time)"
    print(f"n={n_total} ({shards} x {args.n}{cut}) cap={CAP} per index")

    phase = Phases()
    with phase("kernels against their references"):
        kernels_match_reference(args.seed)
    with phase("synthesise base vectors"):
        base = make_clustered_vectors(n_total, DIM, seed=args.seed)
    with phase(f"load: build {shards} index(es)"):
        if args.four_chips:
            backend = ShardedBackend(cfg, N_SHARDS, devices=devs,
                                     seed=args.seed).build(base,
                                                           seed=args.seed)
        else:
            backend = LSMVecIndex.build(cfg, base, seed=args.seed)
        backend.sync()
    _, load_s, n_prog, compile_s = phase.last
    print(f"load: {load_s} s, of which compiling {compile_s} s; "
          f"{n_prog} programs compiled while loading")

    recalls = exercise(backend, base, args.seed, phase, args.four_chips)
    print(f"recall: {json.dumps(recalls)}")
    print(f"peak_bytes_in_use per device: {peak_bytes(devs)}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
