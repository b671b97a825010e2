"""Tests for the batched update pipeline (hnsw.insert_batch /
delete_batch) and multi-expansion beam search (DESIGN.md §3-§4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hnsw, lsm
from repro.core.backend import SearchParams
from repro.core.index import LSMVecIndex, brute_force_knn, recall_at_k
from repro.data.synth import make_clustered_vectors


def make_data(n, dim=32, seed=0):
    return make_clustered_vectors(n, dim=dim, seed=seed, clusters=16)


CFG = hnsw.HNSWConfig(cap=2048, dim=32, M=12, M_up=6, num_upper=2,
                      ef_search=48, ef_construction=48, k=10,
                      rho=1.0, use_filter=False, lsm_mem_cap=128,
                      lsm_levels=2, lsm_fanout=8, batch_expand=4)


@pytest.fixture(scope="module")
def built_index():
    data = make_data(768)
    return LSMVecIndex.build(CFG, data), data


def test_insert_batch_ids_size_and_count_mirror():
    data = make_data(256, seed=1)
    idx = LSMVecIndex.build(CFG, data)
    xs = make_data(96, seed=2)
    ids = idx.insert_batch(xs).ids.tolist()
    assert ids == list(range(256, 256 + 96))
    assert idx.size == 352
    assert idx._count == int(idx.state.count) == 352


def test_insert_batch_find_self(built_index):
    idx, data = built_index
    new = make_data(32, seed=42) + 100.0     # far-away cluster
    ids = idx.insert_batch(new).ids.tolist()
    found = idx.search(new, k=1).ids
    assert set(found[:, 0].tolist()) == set(ids)


def test_insert_batch_recall():
    base = make_data(512, seed=3)
    extra = make_data(128, seed=4)
    idx = LSMVecIndex.build(CFG, base)
    idx.insert_batch(extra)
    allv = np.concatenate([base, extra])
    queries = make_data(24, seed=8)
    ids = idx.search(queries, k=10).ids
    truth = brute_force_knn(jnp.asarray(allv), jnp.asarray(queries), 10)
    r = recall_at_k(ids, truth)
    assert r >= 0.75, f"post-batch-insert recall {r:.3f}"


def test_insert_batch_rows_written_to_lsm():
    base = make_data(256, seed=5)
    idx = LSMVecIndex.build(CFG, base)
    ids = idx.insert_batch(make_data(64, seed=6)).ids.tolist()
    live, rows = lsm.resolve_all(CFG.lsm_cfg, idx.state.store, idx._count)
    live = np.asarray(live)
    rows = np.asarray(rows)
    for i in ids:
        assert live[i] == 1, f"node {i} has no bottom row"
        assert (rows[i] >= 0).any(), f"node {i} row is empty"


def test_insert_batch_cold_start_seeds_per_item():
    cfg = CFG._replace(cap=512)
    idx = LSMVecIndex(cfg, seed=0)
    xs = make_data(96, seed=7)
    ids = idx.insert_batch(xs).ids.tolist()
    assert ids == list(range(96))
    assert idx.size == 96
    found = idx.search(xs[:8], k=1).ids
    assert (found[:, 0] == np.arange(8)).mean() >= 0.9


def test_delete_batch_matches_sequential_deletes():
    """delete_batch stages Algorithm 2 through an overlay + one bulk
    `lsm.puts`: every non-store field is bit-identical to the per-item
    loop over the same ids in the same order, and the LSM tree resolves
    to identical content (flush timing may differ, never what a lookup
    returns)."""
    data = make_data(256, seed=9)
    idx_a = LSMVecIndex.build(CFG, data)
    idx_b = LSMVecIndex.build(CFG, data)
    victims = [3, 77, 150, 9, 201, 42]
    for v in victims:
        idx_a.delete(v)
    idx_b.delete_batch(victims)
    for name, a, b in zip(hnsw.HNSWState._fields, idx_a.state, idx_b.state):
        if name == "store":
            la, ra = lsm.resolve_all(CFG.lsm_cfg, a, CFG.cap)
            lb, rb = lsm.resolve_all(CFG.lsm_cfg, b, CFG.cap)
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
            np.testing.assert_array_equal(np.asarray(ra), np.asarray(rb))
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)
    np.testing.assert_array_equal(
        np.asarray(jax.tree.map(np.asarray, idx_a.io_stats)),
        np.asarray(jax.tree.map(np.asarray, idx_b.io_stats)))


def test_delete_batch_removes_from_results(built_index):
    idx, _ = built_index
    queries = make_data(8, seed=10)
    ids = idx.search(queries, k=1).ids
    victims = sorted(set(ids[:, 0].tolist()))
    idx.delete_batch(victims)
    ids2 = idx.search(queries, k=10).ids
    for row in ids2:
        assert not (set(row.tolist()) & set(victims)), "deleted id returned"


def test_multi_expansion_recall_parity(built_index):
    """n_expand=4 must stay within 0.01 recall of the exact B=1 path and
    return sorted distances."""
    idx, data = built_index
    queries = make_data(32, seed=11)
    live = np.asarray(idx.state.levels[:len(data)]) >= 0
    truth = brute_force_knn(jnp.asarray(data), jnp.asarray(queries), 10,
                            live=jnp.asarray(live))
    ids1 = idx.search(queries, k=10, params=SearchParams(n_expand=1)).ids
    res4 = idx.search(queries, k=10, params=SearchParams(n_expand=4))
    ids4, d4 = res4.ids, res4.dists
    r1 = recall_at_k(ids1, truth)
    r4 = recall_at_k(ids4, truth)
    assert abs(r4 - r1) <= 0.01, (r1, r4)
    for row in d4:
        assert np.all(np.diff(row) >= -1e-5)


def test_multi_expansion_parity_on_damaged_graph():
    """The trip cap must not starve B>1 searches where the frontier stays
    thin — a heavily deleted graph is the worst case (searches there
    terminate by frontier exhaustion, which the cap must not preempt)."""
    data = make_data(512, seed=20)
    idx = LSMVecIndex.build(CFG, data)
    rng = np.random.default_rng(0)
    victims = rng.choice(512, 200, replace=False)
    idx.delete_batch(victims)
    live = np.ones(512, bool)
    live[victims] = False
    queries = make_data(24, seed=21)
    truth = brute_force_knn(jnp.asarray(data), jnp.asarray(queries), 10,
                            live=jnp.asarray(live))
    r1 = recall_at_k(
        idx.search(queries, k=10, params=SearchParams(n_expand=1)).ids, truth)
    r4 = recall_at_k(
        idx.search(queries, k=10, params=SearchParams(n_expand=4)).ids, truth)
    assert r4 >= r1 - 0.01, (r1, r4)


def test_multi_expansion_visits_no_fewer_nodes(built_index):
    """B=4 expands at least as many nodes as B=1 on the same queries
    (speculative expansions are a superset-ish frontier)."""
    idx, _ = built_index
    queries = make_data(16, seed=12)
    idx.reset_stats()
    idx.search(queries, k=10,
               params=SearchParams(n_expand=1, record_heat=False))
    hops1 = int(idx.io_stats.n_hops)
    idx.reset_stats()
    idx.search(queries, k=10,
               params=SearchParams(n_expand=4, record_heat=False))
    hops4 = int(idx.io_stats.n_hops)
    idx.reset_stats()
    assert hops4 >= hops1


def test_insert_batch_padded_matches_exact_shape():
    """pad-and-mask dispatch: a padded batch produces the same ids and
    graph as the exact-shape call never could prove alone — padding must
    not perturb which neighbors valid items link to."""
    data = make_data(256, seed=30)
    idx = LSMVecIndex.build(CFG, data)
    xs = make_data(20, seed=31)
    ids = idx.insert_batch(xs, pad_to=32).ids.tolist()
    assert ids == list(range(256, 276))
    assert idx.size == 276
    assert idx._count == int(idx.state.count) == 276
    found = idx.search(xs, k=1).ids
    assert (found[:, 0] == np.array(ids)).mean() >= 0.9
    # padding ids were never allocated: nothing lives past the last valid
    live, rows = lsm.resolve_all(CFG.lsm_cfg, idx.state.store, CFG.cap)
    assert not np.asarray(live)[276:].any()
    assert np.asarray(idx.state.levels)[276:].max() == -1


def test_insert_batch_padded_no_retrace_across_occupancy():
    """Different occupancies of the same pad width reuse one traced
    shape; so does the all-consumed-by-seeding edge (empty rest skips
    dispatch entirely)."""
    cfg = CFG._replace(cap=1024)
    idx = LSMVecIndex(cfg, seed=0)
    seed_gap = LSMVecIndex.BATCH_MIN_GRAPH - idx.size
    ids = idx.insert_batch(make_data(seed_gap, seed=32), pad_to=32)
    assert ids.ids.tolist() == list(range(seed_gap))
    assert idx.trace_counts()["insert_batch"] == 0   # all seeded per-item
    before = None
    for occupancy, seed in ((32, 33), (7, 34), (1, 35), (32, 36)):
        ids = idx.insert_batch(make_data(occupancy, seed=seed), pad_to=32)
        assert ids.n_applied == occupancy
        counts = idx.trace_counts()["insert_batch"]
        if before is not None:
            assert counts == before, "padded insert retraced"
        before = counts
    assert before == 1
    # ragged chunking: 70 items through width 32 = 3 calls, same trace
    ids = idx.insert_batch(make_data(70, seed=37), pad_to=32)
    assert ids.n_applied == 70 and idx.trace_counts()["insert_batch"] == 1


def test_delete_batch_padded_and_masked_ids():
    """-1 ids are exact no-ops; pad_to chunks and pads transparently."""
    data = make_data(256, seed=40)
    idx_a = LSMVecIndex.build(CFG, data)
    idx_b = LSMVecIndex.build(CFG, data)
    victims = [5, 99, 180]
    idx_a.delete_batch(victims, pad_to=8)
    idx_b.delete_batch(victims)
    assert idx_a.size == idx_b.size == 253
    for name, a, b in zip(hnsw.HNSWState._fields, idx_a.state, idx_b.state):
        if name == "store":
            la, ra = lsm.resolve_all(CFG.lsm_cfg, a, CFG.cap)
            lb, rb = lsm.resolve_all(CFG.lsm_cfg, b, CFG.cap)
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
            np.testing.assert_array_equal(np.asarray(ra), np.asarray(rb))
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)
    # same traced shape across occupancies
    n0 = idx_a.trace_counts()["delete_batch"]
    idx_a.delete_batch([7], pad_to=8)
    assert idx_a.trace_counts()["delete_batch"] == n0


def test_search_snapshot_bit_parity(built_index):
    """Snapshot-gather adjacency + pad-and-mask lanes return exactly what
    the per-hop LSM path returns, and padded lanes record no heat/stats."""
    idx, _ = built_index
    queries = make_data(24, seed=50)
    res_a = idx.search(queries, k=10, params=SearchParams(record_heat=False))
    res_b = idx.search(queries, k=10, params=SearchParams(
        record_heat=False, use_snapshot=True, pad_to=32))
    ids_a, d_a = res_a.ids, res_a.dists
    ids_b, d_b = res_b.ids, res_b.dists
    np.testing.assert_array_equal(ids_a, ids_b)
    np.testing.assert_array_equal(d_a, d_b)
    # stats parity between the two paths on identical queries
    idx.reset_stats()
    idx.search(queries, k=10, params=SearchParams(record_heat=False))
    direct = jax.tree.map(int, idx.io_stats)
    idx.reset_stats()
    idx.search(queries, k=10, params=SearchParams(
        record_heat=False, use_snapshot=True, pad_to=32))
    snap = jax.tree.map(int, idx.io_stats)
    idx.reset_stats()
    assert direct == snap


def test_snapshot_invalidated_on_writes(built_index):
    """The cached dense view re-resolves after any write: a fresh insert
    must be findable through the snapshot path immediately."""
    idx, _ = built_index
    new = make_data(4, seed=51) + 250.0
    ids = idx.insert_batch(new, pad_to=8).ids.tolist()
    found = idx.search(
        new, k=1, params=SearchParams(use_snapshot=True, pad_to=8)).ids
    assert set(found[:, 0].tolist()) == set(ids)
    victim = ids[0]
    idx.delete_batch([victim], pad_to=8)
    found2 = idx.search(
        new[:1], k=5, params=SearchParams(use_snapshot=True, pad_to=8)).ids
    assert victim not in found2[0].tolist()


def test_mixed_batch_and_single_updates():
    """Batched and per-item updates interleave cleanly."""
    base = make_data(300, seed=13)
    idx = LSMVecIndex.build(CFG, base)
    ids = idx.insert_batch(make_data(40, seed=14)).ids.tolist()
    one = idx.insert(make_data(1, seed=15)[0])
    assert one == ids[-1] + 1
    idx.delete_batch(ids[:10])
    idx.delete(ids[10])
    assert idx.size == 300 + 40 + 1 - 11
    q = make_data(4, seed=16)
    d = idx.search(q, k=5).dists
    assert np.isfinite(d).all()


def test_insert_batch_every_insert_reachable_from_pre_batch_graph():
    """Each acked insert of a batch is reachable from the entry over the
    bottom layer and has an in-link from a node that existed before the
    batch — it does not hang off its batch siblings alone."""
    base = make_data(768, seed=11)
    idx = LSMVecIndex.build(CFG, base)
    ids = idx.insert_batch(make_data(128, seed=12)).ids
    live, rows = lsm.resolve_all(CFG.lsm_cfg, idx.state.store, CFG.cap)
    rows = np.asarray(jnp.where(live[:, None] > 0, rows, -1))
    n = idx._count
    old = rows[:len(base)]
    assert np.isin(ids, old[old >= 0]).all()
    seen = np.zeros(n, bool)
    frontier = np.asarray([int(idx.state.entry)])
    seen[frontier] = True
    while len(frontier):
        nxt = rows[frontier].ravel()
        nxt = np.unique(nxt[(nxt >= 0) & (nxt < n)])
        frontier = nxt[~seen[nxt]]
        seen[frontier] = True
    assert seen[ids].all()
