"""Bulk-build invariants: the device-side construction links the same
graph as a plain host-side construction, bucketing the candidate widths
changes no graph, and loading compiles O(log n) programs.

The oracle below is the construction written out on the host: every
batch scores the placed set at its exact width, and nodes are
diversity-selected and back-linked one at a time.  On integer-valued
vectors every distance is exact in f32 on both sides, and ties break by
arrival position on both, so the two graphs must be equal.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hnsw, lsm
from repro.data.synth import make_clustered_vectors
from repro.kernels.l2_distance.ops import l2_distance


def _oracle_diversity(cand, cand_d, vecs_np, deg):
    order = np.argsort(cand_d, kind="stable")
    cand, cand_d = cand[order], cand_d[order]
    cv = vecs_np[cand]
    diff = cv[:, None, :] - cv[None, :, :]
    pair = np.einsum("ijk,ijk->ij", diff, diff)
    kept, kept_idx = [], []
    for ci in range(len(cand)):
        if len(kept) >= deg:
            break
        if all(pair[ci, kj] >= cand_d[ci] for kj in kept_idx):
            kept.append(int(cand[ci]))
            kept_idx.append(ci)
    for ci in range(len(cand)):
        if len(kept) >= deg:
            break
        if int(cand[ci]) not in kept:
            kept.append(int(cand[ci]))
    return kept


def _oracle_incremental_graph(vecs_np, member_ids, deg, seed, batch=64):
    rows = np.full((vecs_np.shape[0], deg), -1, np.int32)
    ids = np.asarray(member_ids)
    order = ids[np.random.default_rng(seed).permutation(ids.size)]
    placed = [int(order[0])]
    bounds, step = [1], 1
    while bounds[-1] < order.size:
        bounds.append(min(bounds[-1] + step, order.size))
        step = min(batch, step * 2)
    for s, e in zip(bounds[:-1], bounds[1:]):
        chunk = order[s:e]
        d_blk = np.asarray(l2_distance(jnp.asarray(vecs_np[chunk]),
                                       jnp.asarray(vecs_np[placed])))
        kk = len(placed) if ids.size <= max(128, 4 * deg) \
            else min(2 * deg, len(placed))
        top = np.argsort(d_blk, axis=1, kind="stable")[:, :kk]
        placed_arr = np.asarray(placed)
        for bi, i in enumerate(chunk):
            nb = _oracle_diversity(placed_arr[top[bi]], d_blk[bi, top[bi]],
                                   vecs_np, deg)
            rows[i, :len(nb)] = nb
            for p_ in nb:
                free = np.flatnonzero(rows[p_] < 0)
                if free.size:
                    j = int(free[0])
                else:
                    d_new = ((vecs_np[rows[p_]] - vecs_np[i]) ** 2).sum(1)
                    j = int(np.argmin(d_new))
                rows[p_, j] = i
            placed.append(int(i))
    return rows


@pytest.fixture(scope="module")
def int_data():
    """Integer-valued vectors: every squared distance (and the matmul
    decomposition of it) is an exact f32 integer."""
    return np.random.default_rng(7).integers(-40, 41, (2048, 16)).astype(
        np.float32)


@pytest.mark.parametrize("n_members,deg", [(2048, 16), (700, 8), (100, 8)])
def test_incremental_graph_matches_host_oracle(int_data, n_members, deg):
    """All members (the bottom layer), a sparse subset (an upper layer)
    and a subset small enough for the complete-placed-set path."""
    members = np.random.default_rng(1).choice(len(int_data), n_members,
                                              replace=False)
    got = hnsw._incremental_graph(jnp.asarray(int_data), members, deg,
                                  seed=3)
    want = _oracle_incremental_graph(int_data, members, deg, seed=3)
    np.testing.assert_array_equal(got, want)


def test_bulk_build_graph_unchanged_by_width_bucketing(monkeypatch):
    """Padding the candidate widths to powers of two (masked columns)
    builds the graph that exact per-batch widths build (above the
    128-column floor every block has either way)."""
    cfg = hnsw.HNSWConfig(cap=2048, dim=128)
    data = jnp.asarray(make_clustered_vectors(2048, 128, seed=0))

    def graph():
        st = hnsw.bulk_build(cfg, data, jax.random.key(0))
        _, rows = lsm.resolve_all(cfg.lsm_cfg, st.store, cfg.cap)
        return np.asarray(rows), np.asarray(st.upper_adj)

    bucketed = graph()
    monkeypatch.setattr(hnsw, "_bucket", lambda n: max(n, 128))
    exact = graph()
    np.testing.assert_array_equal(bucketed[0], exact[0])   # bottom layer
    np.testing.assert_array_equal(bucketed[1], exact[1])   # upper layers


@pytest.mark.parametrize("n", [2048, 8192])
def test_build_compiles_log_n_candidate_programs(n):
    """One candidate-selection program per (bucketed width, cut) pair:
    O(log n), where one per batch would be O(n / 64)."""
    cfg = hnsw.HNSWConfig(cap=n, dim=128)
    x = make_clustered_vectors(n, 128, seed=n)
    jax.clear_caches()       # count this build's programs, not earlier ones
    programs = (hnsw._nearest_placed, hnsw._link_chunk)
    before = [p._cache_size() for p in programs]
    hnsw.bulk_build(cfg, jnp.asarray(x), jax.random.key(0))
    selects, links = (p._cache_size() - b for p, b in zip(programs, before))
    widths = int(math.log2(hnsw._bucket(n))) - 6      # 128 .. bucket(n)
    # candidate selection: two cuts (2*M, 2*M_up) per width plus the
    # small-layer full sort; linking: one per (degree, cut) — against
    # more than n / 64 batches in the bottom layer alone
    assert 1 <= selects <= 2 * widths + 1
    assert 1 <= links <= 3
    assert selects + links < n // 64
