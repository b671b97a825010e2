"""Sharded-vs-single-device parity suite (DESIGN.md §10).

The serve engine programs against the `VectorBackend` protocol; these
tests pin the contract that makes that safe:

- strict-mode serving over `ShardedBackend(n_shards=1)` is bit-parity
  with serving over a bare `LSMVecIndex` on the same stream;
- at 4 shards the same stream holds a recall floor vs single-device;
- churn under sharding: tombstone counts and consolidation are per
  shard, external ids stay stable through reorder + consolidate;
- adaptive batch shaping derives coalescing windows from the arrival
  EMA and exposes them in `ServeMetrics`.

The CI `serve-shard-smoke` job runs this file standalone under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` so every shard
gets its own device; the suite itself never touches XLA_FLAGS (a
module-level mutation would silently change the device topology for
every other test collected in the same pytest run) — the routing,
merge, and id-map logic under test is device-count-independent, so it
also passes on a single device in the tier-1 run.
"""

import numpy as np
import pytest

from repro.core import (
    HNSWConfig,
    LSMVecIndex,
    SearchResult,
    UpdateResult,
    VectorBackend,
    brute_force_knn,
    recall_at_k,
)
from repro.core.backend import shard_of_seq
from repro.core.distributed import ShardedBackend
from repro.data.synth import make_clustered_vectors
from repro.serve import MaintenancePolicy, Op, ServeConfig, ServeEngine

CFG = HNSWConfig(cap=1024, dim=32, M=12, M_up=6, num_upper=2,
                 ef_search=48, ef_construction=48, k=10,
                 rho=1.0, use_filter=False, lsm_mem_cap=128,
                 lsm_levels=2, lsm_fanout=8)

NO_MAINT = MaintenancePolicy(tombstone_ratio=None, consolidate_ratio=None,
                             heat_budget=None)


def make_data(n, seed=0):
    return make_clustered_vectors(n, dim=32, seed=seed, clusters=16)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _stream(rng, base, fresh, n_ops, ins_ids):
    """(op, payload) mixed stream; deletes target live external ids."""
    stream = []
    live = list(range(len(base)))
    fi = 0
    for _ in range(n_ops):
        r = rng.random()
        if r < 0.7 or (r >= 0.85 and len(live) < 32):
            stream.append(("q", base[rng.integers(0, len(base))]))
        elif r < 0.85 and fi < len(fresh):
            stream.append(("i", fresh[fi]))
            fi += 1
        else:
            stream.append(("d", live.pop(rng.integers(0, len(live)))))
    return stream


def _drive(backend, stream, *, strict, caps=16):
    eng = ServeEngine(
        backend,
        ServeConfig(query_batch=caps, insert_batch=caps, delete_batch=caps,
                    strict_order=strict, query_window=0.0, insert_window=0.0,
                    delete_window=0.0, maintenance=NO_MAINT),
        clock=FakeClock())
    tickets = [(op, eng.submit_query(p) if op == "q" else
                eng.submit_insert(p) if op == "i" else
                eng.submit_delete(p)) for op, p in stream]
    eng.drain()
    return eng, tickets


# ---------------------------------------------------------------------------
# protocol + typed results
# ---------------------------------------------------------------------------

def test_both_backends_satisfy_the_protocol():
    base = make_data(96, seed=0)
    single = LSMVecIndex.build(CFG, base)
    sharded = ShardedBackend(CFG, 4).build(base)
    assert isinstance(single, VectorBackend)
    assert isinstance(sharded, VectorBackend)
    for b in (single, sharded):
        res = b.search(base[:3], k=5)
        assert isinstance(res, SearchResult)
        assert res.ids.shape == res.dists.shape == (3, 5)
        assert 0 < res.beam.hops <= res.beam.slots
        with pytest.raises(TypeError):
            ids, dists = res             # sequence compat is gone
        up = b.insert_batch(make_data(4, seed=1))
        assert isinstance(up, UpdateResult) and up.n_applied == 4
        assert b.delete_batch([int(up.ids[0])]).n_applied == 1
        st = b.stats()
        assert st.n_tombstones == 1 and len(st.shards) >= 1
        assert st.n_tombstones == sum(s.n_tombstones for s in st.shards)


def test_routing_is_deterministic_and_balanced():
    asg = np.asarray(shard_of_seq(np.arange(4096), 4))
    counts = np.bincount(asg, minlength=4)
    assert (counts > 4096 // 4 - 200).all(), counts   # no starved shard
    np.testing.assert_array_equal(
        asg, np.asarray(shard_of_seq(np.arange(4096), 4)))
    assert (np.asarray(shard_of_seq(np.arange(64), 1)) == 0).all()


# ---------------------------------------------------------------------------
# strict-mode parity: sharded(P=1) == single-device, bit for bit
# ---------------------------------------------------------------------------

def test_sharded1_strict_serving_bit_parity_with_single_device():
    base = make_data(512, seed=2)
    fresh = make_data(64, seed=3)
    rng = np.random.default_rng(11)
    stream = _stream(rng, base, fresh, 300, [])

    eng_s, tk_s = _drive(LSMVecIndex.build(CFG, base), stream, strict=True)
    eng_p, tk_p = _drive(ShardedBackend(CFG, 1).build(base), stream,
                         strict=True)

    assert eng_s.batch_log == eng_p.batch_log
    for (op_a, a), (op_b, b) in zip(tk_s, tk_p):
        ra, rb = a.result(), b.result()
        if op_a == "q":
            np.testing.assert_array_equal(ra.ids, rb.ids)
            np.testing.assert_array_equal(ra.dists, rb.dists)
        else:
            assert ra == rb                 # ext ids / delete outcomes


def test_sharded4_same_stream_recall_floor():
    # 4 shards over 1024 rows = 256 nodes/shard: the per-shard scale the
    # serve_load sharded smoke also uses.  (Far smaller shards lose
    # navigability in the bulk-built graph itself — a bulk_build
    # property, not a sharding one.)
    base = make_data(1024, seed=4)
    fresh = make_data(64, seed=5)
    rng = np.random.default_rng(12)
    stream = _stream(rng, base, fresh, 300, [])
    queries = make_data(32, seed=6)

    results = {}
    for name, backend in (("single",
                           LSMVecIndex.build(CFG._replace(cap=2048), base)),
                          ("sharded", ShardedBackend(CFG, 4).build(base))):
        eng, tickets = _drive(backend, stream, strict=True)
        n_ins = sum(1 for op, _ in stream if op == "i")
        dels = [p for op, p in stream if op == "d"]
        tq = [eng.submit_query(q) for q in queries]
        eng.drain()
        found = np.stack([t.result().ids for t in tq])
        allv = np.concatenate([base, fresh[:n_ins]])
        live = np.ones(len(allv), bool)
        live[dels] = False
        truth = brute_force_knn(allv, queries, 10, live=live)
        results[name] = recall_at_k(found, truth)
    assert results["sharded"] >= 0.95 * results["single"], results
    assert results["sharded"] >= 0.7


# ---------------------------------------------------------------------------
# churn under sharding: per-shard tombstones + consolidation
# ---------------------------------------------------------------------------

def test_churn_under_sharding_tombstones_and_consolidation_per_shard():
    base = make_data(512, seed=7)
    backend = ShardedBackend(CFG, 4).build(base)
    pol = MaintenancePolicy(tombstone_ratio=None, consolidate_ratio=0.25,
                            heat_budget=None, check_every=4)
    eng = ServeEngine(backend, ServeConfig(delete_batch=16, maintenance=pol),
                      clock=FakeClock())
    rng = np.random.default_rng(13)
    victims = rng.choice(512, 220, replace=False)
    for v in victims:
        eng.submit_delete(int(v))
    eng.drain()
    st = backend.stats()
    # every tombstone is accounted per shard; consolidated slots +
    # still-pending tombstones cover the whole victim set
    assert st.n_tombstones == sum(s.n_tombstones for s in st.shards)
    assert eng.maintenance.slots_reclaimed + st.n_tombstones == len(victims)
    assert eng.maintenance.consolidations >= 1
    assert sum(backend.consolidations) >= 1     # per-shard log
    # per-shard trigger: no shard may sit far over the ratio post-drain
    # (deletes arriving after the last check stay tombstoned until the
    # next one — bounded by check_every * delete_batch per shard)
    for s in st.shards:
        assert s.n_tombstones <= pol.check_every * 16
    # deleted ext ids never return
    tq = [eng.submit_query(base[int(v)]) for v in victims[:16]]
    eng.drain()
    returned = set(int(i) for t in tq for i in t.result().ids)
    assert not (returned & set(int(v) for v in victims))


def test_sharded_reorder_keeps_external_ids_stable():
    base = make_data(400, seed=8)
    backend = ShardedBackend(CFG, 2).build(base)
    pol = MaintenancePolicy(tombstone_ratio=None, consolidate_ratio=None,
                            heat_budget=1, check_every=1)
    eng = ServeEngine(backend, ServeConfig(query_batch=16, insert_batch=16,
                                           delete_batch=16, maintenance=pol),
                      clock=FakeClock())
    probe = base[37]
    t0 = eng.submit_query(probe)
    eng.drain()
    assert int(t0.result().ids[0]) == 37
    x = make_data(1, seed=9)[0] + 50.0
    t_ins = eng.submit_insert(x)
    eng.drain()
    assert eng.maintenance.reorders >= 1
    perm = eng.maintenance.last_perm
    assert perm is not None and len(perm) == backend.cap
    assert not np.array_equal(perm, np.arange(len(perm)))
    t1 = eng.submit_query(probe)
    t2 = eng.submit_query(x)
    eng.drain()
    assert int(t1.result().ids[0]) == 37
    assert int(t2.result().ids[0]) == int(t_ins.result())
    eng.submit_delete(37)
    t3 = eng.submit_query(probe)
    eng.drain()
    assert int(t3.result().ids[0]) != 37


# ---------------------------------------------------------------------------
# adaptive batch shaping (Quake-style windows from the arrival EMA)
# ---------------------------------------------------------------------------

def test_adaptive_windows_track_arrival_rate():
    base = make_data(256, seed=10)
    idx = LSMVecIndex.build(CFG, base)
    clock = FakeClock()
    cfg = ServeConfig(query_batch=8, query_window=0.01,
                      adaptive_windows=True, window_min=0.0,
                      window_max=0.02, window_fill=0.5, window_alpha=0.2,
                      maintenance=NO_MAINT)
    eng = ServeEngine(idx, cfg, clock=clock)
    # steady 1 ms inter-arrival gap: EMA converges to the gap itself
    for i in range(12):
        eng.submit_query(base[i])
        clock.t += 0.001
    eng.drain()
    w_slow = eng.metrics.windows[Op.QUERY]
    # expected: fill * cap * gap = 0.5 * 8 * 0.001 = 4 ms (clamped at 20)
    assert w_slow == pytest.approx(0.004, rel=0.2)
    # 20x faster arrivals shrink the window toward zero
    for i in range(40):
        eng.submit_query(base[i % 200])
        clock.t += 0.00005
    eng.drain()
    w_fast = eng.metrics.windows[Op.QUERY]
    assert w_fast < w_slow / 4
    # chosen windows surface in the metrics snapshot
    snap = eng.metrics.snapshot()
    assert snap["query"]["window_ms"] == pytest.approx(w_fast * 1e3,
                                                       abs=1e-3)


def test_adaptive_window_actually_gates_release():
    base = make_data(128, seed=11)
    idx = LSMVecIndex.build(CFG, base)
    clock = FakeClock()
    eng = ServeEngine(idx,
                      ServeConfig(query_batch=8, query_window=0.5,
                                  adaptive_windows=True, window_min=0.002,
                                  window_max=0.02, maintenance=NO_MAINT),
                      clock=clock)
    # establish a 1 ms arrival EMA -> window 0.5*8*0.001 = 4 ms
    for i in range(10):
        eng.submit_query(base[i])
        clock.t += 0.001
    eng.drain()
    # one lone query: held while the adaptive window is open ...
    eng.submit_query(base[0])
    assert eng.pump() is None
    # ... and released once its age crosses the chosen window
    clock.t += eng.metrics.windows[Op.QUERY] + 1e-4
    assert eng.pump() is Op.QUERY


# ---------------------------------------------------------------------------
# delete_noops: one stats surface, no drift
# ---------------------------------------------------------------------------

def test_delete_noops_single_surface():
    base = make_data(256, seed=12)
    idx = LSMVecIndex.build(CFG, base)
    eng = ServeEngine(idx, ServeConfig(delete_batch=8, maintenance=NO_MAINT),
                      clock=FakeClock())
    # device-side no-op: tombstone id 5 behind the engine's back, then
    # delete it through the engine (engine map says allocated+fresh)
    idx.delete(5)
    eng.submit_delete(5)
    # host-side no-ops: a repeat and an unallocated ext id
    eng.submit_delete(5)
    eng.submit_delete(900)
    eng.drain()
    st = idx.stats()
    assert st.delete_noops == 1          # the device count
    assert eng.metrics.delete_noops == 2  # the host count
    assert eng.delete_noops == 3          # the one combined accessor
