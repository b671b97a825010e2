"""Async serving spine tests (DESIGN.md §13): two-phase dispatch /
collect parity, merge tie-break stability, and non-blocking
(double-buffered) consolidation with atomic cutover.

Runs on however many devices the session exposes — the dispatch
contract is about *ordering* (enqueue everything, then block), which
holds on one device too; the wall-clock win needs one device per shard
and is measured by ``benchmarks/serve_load.py``'s fanout probe.
"""

import numpy as np
import pytest

from repro.core import hnsw
from repro.core.backend import (
    MaintenanceReport,
    SearchHandle,
    SearchParams,
    SearchResult,
    merge_topk,
)
from repro.core.distributed import ShardedBackend
from repro.core.index import LSMVecIndex, brute_force_knn, recall_at_k
from repro.data.synth import make_clustered_vectors


def make_data(n, dim=32, seed=0):
    return make_clustered_vectors(n, dim=dim, seed=seed, clusters=16)


CFG = hnsw.HNSWConfig(cap=1024, dim=32, M=12, M_up=6, num_upper=2,
                      ef_search=48, ef_construction=48, k=10,
                      rho=1.0, use_filter=False, lsm_mem_cap=128,
                      lsm_levels=2, lsm_fanout=8)
LAZY = CFG._replace(lazy_delete=True)


# ---------------------------------------------------------------------------
# merge_topk tie-break stability
# ---------------------------------------------------------------------------

def test_merge_topk_ties_resolve_to_lower_shard_index():
    """Equal distances across shards must resolve deterministically to
    the earlier shard's candidate — the stable P-way merge contract."""
    d = np.array([[1.0, 2.0, 3.0]], np.float32)
    s0 = (np.array([[10, 11, 12]], np.int64), d)
    s1 = (np.array([[20, 21, 22]], np.int64), d.copy())
    res = merge_topk([s0[0], s1[0]], [s0[1], s1[1]], k=4)
    # tie at 1.0: shard 0's id 10 precedes shard 1's id 20, and so on
    np.testing.assert_array_equal(res.ids, [[10, 20, 11, 21]])
    np.testing.assert_array_equal(res.dists, [[1.0, 1.0, 2.0, 2.0]])


def test_merge_topk_is_a_permutation_stable_merge():
    """Shuffling which shard holds which candidates changes only the
    tie order (by design), never the returned candidate *set* per row,
    and identical shard contents in a different shard order merge ties
    toward the new lower index."""
    rng = np.random.default_rng(0)
    d0 = np.sort(rng.random((4, 6)).astype(np.float32), axis=1)
    d1 = np.sort(rng.random((4, 6)).astype(np.float32), axis=1)
    i0 = rng.integers(0, 500, (4, 6)).astype(np.int64)
    i1 = rng.integers(500, 1000, (4, 6)).astype(np.int64)
    a = merge_topk([i0, i1], [d0, d1], k=8)
    b = merge_topk([i1, i0], [d1, d0], k=8)
    # distances agree exactly; candidate sets per row agree
    np.testing.assert_array_equal(a.dists, b.dists)
    for ra, rb in zip(a.ids, b.ids):
        assert set(ra.tolist()) == set(rb.tolist())


def test_merge_topk_single_shard_is_identity():
    ids = np.array([[3, 1, 9]], np.int64)
    dists = np.array([[0.1, 0.5, 0.9]], np.float32)
    res = merge_topk([ids], [dists], k=3)
    np.testing.assert_array_equal(res.ids, ids)
    np.testing.assert_array_equal(res.dists, dists)


def test_merge_topk_pads_stay_last():
    ids = np.array([[5, -1, -1]], np.int64)
    dists = np.array([[0.4, np.inf, np.inf]], np.float32)
    other = (np.array([[7, -1, -1]], np.int64),
             np.array([[0.2, np.inf, np.inf]], np.float32))
    res = merge_topk([ids, other[0]], [dists, other[1]], k=4)
    np.testing.assert_array_equal(res.ids[0][:2], [7, 5])
    assert (res.ids[0][2:] == -1).all()
    assert np.isinf(res.dists[0][2:]).all()


# ---------------------------------------------------------------------------
# two-phase dispatch / collect parity
# ---------------------------------------------------------------------------

def _churn(backend, seed):
    """Interleave deletes of served ids and fresh inserts (tombstone
    churn) so parity is checked against a live, damaged graph."""
    rng = np.random.default_rng(seed)
    born = np.asarray(backend.initial_ids(), np.int64)
    victims = rng.choice(born, 40, replace=False)
    backend.delete_batch(victims)
    backend.insert_batch(make_data(24, seed=seed + 1) + 50.0)
    return victims


@pytest.mark.parametrize("shards", [1, 4])
def test_dispatch_collect_matches_blocking_search(shards):
    """search() is defined as dispatch+collect; an explicit two-phase
    round trip must be bit-identical to the one-call path, before and
    after tombstone churn, for 1 and 4 shards."""
    base = make_data(512, seed=1)
    if shards == 1:
        be = LSMVecIndex.build(LAZY, base)
    else:
        be = ShardedBackend(LAZY._replace(cap=256), shards).build(base)
    queries = make_data(16, seed=2)
    for phase in range(2):
        h = be.dispatch_search(queries, k=10)
        assert isinstance(h, SearchHandle)
        sync = be.search(queries, k=10)
        res = h.collect()
        assert isinstance(res, SearchResult)
        np.testing.assert_array_equal(res.ids, sync.ids)
        np.testing.assert_array_equal(res.dists, sync.dists)
        assert res.beam == sync.beam and res.beam.hops > 0
        if shards > 1:
            # shards run side by side: the slowest shard's trips, the
            # summed hops and slots
            parts = [sh.search(queries, k=10).beam for sh in be.shards]
            assert res.beam.trips == max(b.trips for b in parts)
            assert res.beam.hops == sum(b.hops for b in parts)
            assert res.beam.slots == sum(b.slots for b in parts)
        if phase == 0:
            _churn(be, seed=3)


def test_shards1_matches_bare_index_bitwise():
    """The sharded fan-out at P=1 is the single-device search exactly:
    same ids, same distances, the §13 bit-parity anchor."""
    base = make_data(384, seed=4)
    single = LSMVecIndex.build(LAZY, base)
    sharded = ShardedBackend(LAZY, 1).build(base)
    queries = make_data(12, seed=5)
    for be in (single, sharded):
        _churn(be, seed=6)
    a = single.search(queries, k=10)
    b = sharded.search(queries, k=10)
    np.testing.assert_array_equal(a.ids, b.ids)
    np.testing.assert_array_equal(a.dists, b.dists)
    assert a.beam == b.beam


def test_dispatch_interleaving_does_not_change_results():
    """Handles dispatched before other queries' device work still
    collect their own results (no cross-talk between in-flight
    dispatches)."""
    base = make_data(256, seed=7)
    be = LSMVecIndex.build(CFG, base)
    q1 = make_data(8, seed=8)
    q2 = make_data(8, seed=9)
    want1 = be.search(q1, k=5)
    want2 = be.search(q2, k=5)
    h1 = be.dispatch_search(q1, k=5)
    h2 = be.dispatch_search(q2, k=5)
    r2, r1 = h2.collect(), h1.collect()      # collect out of order
    np.testing.assert_array_equal(r1.ids, want1.ids)
    np.testing.assert_array_equal(r2.ids, want2.ids)


def test_search_params_resolution_single_site():
    """None fields resolve from the backend config exactly once, at the
    dispatch boundary; explicit fields win."""
    p = SearchParams().resolve(CFG)
    assert (p.rho, p.ef, p.use_filter, p.n_expand) == (
        CFG.rho, CFG.ef_search, CFG.use_filter, CFG.n_expand)
    assert p.record_heat is True             # index-level default
    q = SearchParams(rho=0.5, record_heat=False).resolve(CFG)
    assert q.rho == 0.5 and q.record_heat is False
    # params route: narrower ef returns at most the same recall work
    base = make_data(256, seed=10)
    idx = LSMVecIndex.build(CFG, base)
    r1 = idx.search(base[:4], k=5, params=SearchParams(ef=16))
    r2 = idx.search(base[:4], k=5)
    assert r1.ids.shape == r2.ids.shape


# ---------------------------------------------------------------------------
# non-blocking consolidation: begin / poll / write-barrier cutover
# ---------------------------------------------------------------------------

def _tombstoned_index(seed=11, n=512, n_del=120):
    data = make_data(n, seed=seed)
    idx = LSMVecIndex.build(LAZY, data)
    rng = np.random.default_rng(seed)
    victims = rng.choice(n, n_del, replace=False).astype(np.int64)
    idx.delete_batch(victims)
    return idx, data, victims


def test_overlapped_consolidate_matches_sync_consolidate():
    """begin+poll lands bit-identically where the stop-the-world
    consolidate lands: same reclaimed count, same final state arrays."""
    idx_a, _, _ = _tombstoned_index()
    idx_b = idx_a.clone()
    rep_sync = idx_a.maintain("consolidate")
    assert isinstance(rep_sync, MaintenanceReport) and rep_sync.applied

    assert idx_b.begin_maintain("consolidate")
    assert idx_b.maintenance_pending
    rep = idx_b.poll_maintain(block=True)
    assert rep is not None and rep.applied
    assert rep.detail.get("overlapped") is True
    assert rep.reclaimed == rep_sync.reclaimed
    assert not idx_b.maintenance_pending
    for name, a, b in zip(hnsw.HNSWState._fields,
                          idx_a.state, idx_b.state):
        if name == "store":
            continue           # LSM flush timing may differ, content not
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def test_queries_during_inflight_repair_serve_live_state():
    """Between begin and cutover, searches still run against the
    pre-repair live state and never return tombstoned ids."""
    idx, data, victims = _tombstoned_index(seed=12)
    queries = data[victims[:8]]
    assert idx.begin_maintain("consolidate")
    res = idx.search(queries, k=10)          # repair still in flight
    assert not (set(res.ids.flatten().tolist()) & set(victims.tolist()))
    rep = idx.poll_maintain(block=True)
    assert rep is not None and rep.applied
    res2 = idx.search(queries, k=10)
    assert not (set(res2.ids.flatten().tolist()) & set(victims.tolist()))


def test_write_barrier_claims_inflight_repair():
    """A mutation arriving mid-repair forces the cutover first (the
    write barrier), and the finished report is still claimable —
    exactly once — afterwards."""
    idx, _, _ = _tombstoned_index(seed=13)
    pre_tomb = idx.n_tombstones
    assert pre_tomb > 0
    assert idx.begin_maintain("consolidate")
    idx.insert_batch(make_data(8, seed=14) + 80.0)   # barrier -> cutover
    assert idx.n_tombstones == 0             # repair landed before insert
    rep = idx.poll_maintain()
    assert rep is not None and rep.applied and rep.reclaimed == pre_tomb
    assert idx.poll_maintain(block=True) is None     # claimed exactly once


def test_begin_maintain_noop_without_pressure():
    data = make_data(128, seed=15)
    idx = LSMVecIndex.build(LAZY, data)
    assert not idx.begin_maintain("consolidate")
    assert not idx.maintenance_pending
    assert idx.poll_maintain(block=True) is None


def test_sharded_overlapped_consolidate_aggregates_shards():
    base = make_data(512, seed=16)
    be = ShardedBackend(LAZY._replace(cap=352), 2).build(base)
    rng = np.random.default_rng(17)
    born = np.asarray(be.initial_ids(), np.int64)
    victims = rng.choice(born, 160, replace=False)
    be.delete_batch(victims)
    assert be.begin_maintain("consolidate", ratio=0.1)
    rep = be.poll_maintain(block=True)
    assert rep is not None and rep.applied
    assert rep.reclaimed == 160
    assert rep.detail["shards"] == [0, 1]
    assert sum(be.consolidations) >= 2
    # post-cutover recall over the survivors holds
    inv = np.full(be.cap, -1, np.int64)
    inv[born] = np.arange(len(born))
    live = np.ones(512, bool)
    live[inv[victims]] = False
    queries = make_data(16, seed=18)
    res = be.search(queries, k=10)
    ids = np.where(res.ids >= 0, inv[np.maximum(res.ids, 0)], -1)
    import jax.numpy as jnp
    truth = brute_force_knn(jnp.asarray(base), jnp.asarray(queries), 10,
                            live=jnp.asarray(live))
    assert recall_at_k(ids, truth) >= 0.7


def test_maintain_uniform_reports():
    """compact / reorder / consolidate all answer through one
    MaintenanceReport shape."""
    idx, _, _ = _tombstoned_index(seed=19, n=256, n_del=60)
    rep_c = idx.maintain("consolidate")
    assert rep_c.op == "consolidate" and rep_c.applied
    rep_k = idx.maintain("compact")
    assert rep_k.op == "compact" and rep_k.applied
    idx.search(make_data(8, seed=20), k=5)   # heat for the reorder
    rep_r = idx.maintain("reorder", window=8, lam=1.0)
    assert rep_r.op == "reorder" and rep_r.perm is not None
    assert sorted(rep_r.perm.tolist()) == list(range(len(rep_r.perm)))
    with pytest.raises(ValueError):
        idx.maintain("no-such-op")
