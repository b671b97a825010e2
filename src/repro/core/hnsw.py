"""Hybrid memory/disk hierarchical proximity graph (paper §3.2).

Upper HNSW layers (layers 2.. in the paper's numbering; <1% of nodes) are
memory-resident dense adjacency arrays.  The bottom layer — the bulk of the
graph — lives in the LSM tree, so every structural update is an
out-of-place LSM write.  Vectors are stored in one contiguous ID-sorted
array ("disk", i.e. HBM on the TPU mapping) fetched by offset; SimHash
codes are memory-resident.

Implements Algorithm 1 (insert) and Algorithm 2 (delete with local
neighbor relinking) plus a bulk construction path used for initial index
builds (an exact-kNN bottom graph, the offline analogue).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import lsm, simhash
from repro.core.backend import MemoryBreakdown
from repro.core.iostats import IOStats
from repro.core.traversal import BeamResult, beam_search, greedy_descent
from repro.kernels.beam.ops import beam_iter_cap, fused_beam_search
from repro.kernels.gather_l2.ops import gather_l2, gather_l2_q8
from repro.kernels.l2_distance.ops import l2_distance

INF = jnp.inf


class HNSWConfig(NamedTuple):
    cap: int                 # id-space size (max nodes ever allocated)
    dim: int
    M: int = 16              # bottom-layer degree (LSM row width)
    M_up: int = 8            # upper-layer degree
    num_upper: int = 3       # number of memory-resident upper layers
    ef_search: int = 48
    ef_construction: int = 48
    k: int = 10
    m_bits: int = 64         # SimHash code width
    rho: float = 1.0         # sampling ratio (Eq. 8); 1.0 = no sampling
    eps: float = 0.1         # Hoeffding miss probability (Eq. 6)
    use_filter: bool = True  # hash-threshold filtering on top of rho
    lsm_mem_cap: int = 256
    lsm_levels: int = 3
    lsm_fanout: int = 8
    n_expand: int = 1        # query-path multi-expansion width (B); 1 = classic
    batch_expand: int = 4    # multi-expansion width for insert_batch searches
    #: two-phase lazy deletion (DESIGN.md §9): delete/delete_batch only set
    #: a tombstone bit (routable-not-returnable) and `consolidate` splices
    #: tombstones out of the graph later.  False = the eager Algorithm-2
    #: relink-on-delete path (the paper baseline).
    lazy_delete: bool = True
    #: two-lane tiered store (DESIGN.md §12): cold nodes answer beam
    #: expansions from the int8 quantized lane and the final candidate
    #: window is reranked against full-precision rows from the cold store.
    tier: bool = False
    #: width of the exact-rerank window over the beam result (clamped to
    #: ef_search).  Recall loss from cold-lane quantization is bounded by
    #: this window: any true neighbor the approximate beam ranks within
    #: the top `rerank` gets its exact distance back before the final cut.
    rerank: int = 32
    #: fused beam-search megakernel (DESIGN.md §15): run the whole
    #: bottom-layer beam loop for a query block in one launch
    #: (`repro.kernels.beam`) instead of the XLA `while_loop`.  Only the
    #: snapshot serving path routes through it (plain LSM-probe searches
    #: keep the `while_loop`); results are bit-parity either way, so
    #: flipping this never changes answers — only the launch shape.
    fused_beam: bool = False
    #: scale on the Exp(1) level draw: P(level >= 1) = exp(-1/level_scale).
    #: 1.0 keeps the historical draw (~37% of nodes upper); the paper's
    #: "<1% of nodes in upper layers" regime is level_scale ~= 0.25
    #: (e^-4 ~= 1.8%), which the memory benchmarks use so the resident
    #: upper-layer vector cache doesn't dwarf the lane accounting.
    level_scale: float = 1.0

    @property
    def lsm_cfg(self) -> lsm.LSMConfig:
        # last level must hold every node's adjacency row
        need = self.cap
        base = max(self.lsm_mem_cap, 64)
        fan = self.lsm_fanout
        # grow fanout chain until the last level covers `need`
        lv = self.lsm_levels
        while base * fan ** lv < need:
            fan += 1
        return lsm.LSMConfig(mem_cap=base, num_levels=lv, fanout=fan,
                             row_width=self.M)


    @property
    def max_iters(self) -> int:
        return 2 * self.ef_search

    @property
    def words(self) -> int:
        return self.m_bits // 32


class HNSWState(NamedTuple):
    vectors: jax.Array      # f32[cap, dim] — "disk" array, ID-sorted
    norms: jax.Array        # f32[cap]
    codes: jax.Array        # uint32[cap, W] — memory-resident
    levels: jax.Array       # int32[cap]: -1 absent/deleted, else 0..num_upper
    upper_adj: jax.Array    # int32[num_upper, cap, M_up]
    store: lsm.LSMState     # bottom-layer adjacency
    proj: jax.Array         # f32[m_bits, dim] — SimHash projections
    count: jax.Array        # int32[] — ids allocated so far
    n_live: jax.Array       # int32[]
    entry: jax.Array        # int32[]
    max_level: jax.Array    # int32[]
    mean_norm: jax.Array    # f32[]
    heat: jax.Array         # int32[cap, M] — sampled edge heat (§3.4)
    # lazy-deletion lane (DESIGN.md §9): tombstoned nodes keep levels >= 0
    # (routable) but are masked out of result heaps (not returnable) until
    # `consolidate` splices them out and reclaims the slots
    tombstone: jax.Array    # bool[cap]
    n_tombstones: jax.Array  # int32[] — live tombstone count
    n_delete_noops: jax.Array  # int32[] — deletes of absent/dead ids
    # tiered hot/cold lanes (DESIGN.md §12): `hot` marks nodes whose dense
    # f32 row is RAM-resident; cold nodes are served from (qvecs, qscale)
    # — per-row absmax int8 — and only touch the full-precision row at
    # rerank.  `tier_heat` is the demotion policy's EWMA of per-node heat.
    hot: jax.Array          # bool[cap] — True = dense lane resident
    qvecs: jax.Array        # int8[cap, dim] — cold-lane codes
    qscale: jax.Array       # f32[cap] — cold-lane per-row scales
    tier_heat: jax.Array    # f32[cap] — heat EWMA (policy state)


def init(cfg: HNSWConfig, key: jax.Array) -> HNSWState:
    return HNSWState(
        vectors=jnp.zeros((cfg.cap, cfg.dim), jnp.float32),
        norms=jnp.zeros((cfg.cap,), jnp.float32),
        codes=jnp.zeros((cfg.cap, cfg.words), jnp.uint32),
        levels=jnp.full((cfg.cap,), -1, jnp.int32),
        upper_adj=jnp.full((cfg.num_upper, cfg.cap, cfg.M_up), -1, jnp.int32),
        store=lsm.init(cfg.lsm_cfg),
        proj=jax.random.normal(key, (cfg.m_bits, cfg.dim), jnp.float32),
        count=jnp.zeros((), jnp.int32),
        n_live=jnp.zeros((), jnp.int32),
        entry=jnp.full((), -1, jnp.int32),
        max_level=jnp.zeros((), jnp.int32),
        mean_norm=jnp.ones((), jnp.float32),
        heat=jnp.zeros((cfg.cap, cfg.M), jnp.int32),
        tombstone=jnp.zeros((cfg.cap,), jnp.bool_),
        n_tombstones=jnp.zeros((), jnp.int32),
        n_delete_noops=jnp.zeros((), jnp.int32),
        hot=jnp.ones((cfg.cap,), jnp.bool_),
        qvecs=jnp.zeros((cfg.cap, cfg.dim), jnp.int8),
        qscale=jnp.zeros((cfg.cap,), jnp.float32),
        tier_heat=jnp.zeros((cfg.cap,), jnp.float32),
    )


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

def _dist_fn(state: HNSWState, q: jax.Array):
    """ids int32[n] -> squared L2 f32[n]; -1 ids cost nothing (+inf).

    On TPU this is the fused gather+distance Pallas kernel (the "disk
    fetch"); on CPU containers the jnp oracle with identical semantics.
    """
    def fn(ids):
        return gather_l2(q[None, :], state.vectors, ids[None, :])[0]
    return fn


def _exact_resident(state: HNSWState) -> jax.Array:
    """bool[cap]: nodes whose f32 row is RAM-resident (DESIGN.md §12).

    Hot-lane nodes by definition; upper-layer nodes too, because their
    rows are already in the resident upper routing cache regardless of
    lane — demoting one only drops its bottom-lane dense copy.
    """
    return state.hot | (state.levels > 0)


def _tier_dist_fn(state: HNSWState, q: jax.Array):
    """Mixed-lane distance: exact for resident rows, dequant+L2 for cold.

    Each id hits exactly one lane (the other contributes +inf), so the
    lanes merge with an elementwise min.  Cold distances are approximate;
    `_tier_rerank` restores exactness for the final candidate window.
    """
    resident = _exact_resident(state)

    def fn(ids):
        res = resident[jnp.maximum(ids, 0)]
        hot_ids = jnp.where((ids >= 0) & res, ids, -1)
        cold_ids = jnp.where((ids >= 0) & ~res, ids, -1)
        d_hot = gather_l2(q[None, :], state.vectors, hot_ids[None, :])[0]
        d_cold = gather_l2_q8(q[None, :], state.qvecs, state.qscale,
                              cold_ids[None, :])[0]
        return jnp.minimum(d_hot, d_cold)
    return fn


def _tier_rerank(cfg: HNSWConfig, state: HNSWState, q: jax.Array,
                 res: BeamResult) -> BeamResult:
    """Exact rerank of the top-`cfg.rerank` beam window (the tier
    contract): cold candidates get their full-precision row fetched from
    the cold store (one modeled disk read each, counted in n_vec), the
    window re-sorts on exact distances, and everything past the window
    keeps its approximate ordering — recall loss is bounded by the
    window, not the quantizer.
    """
    r = max(1, min(cfg.rerank, int(res.ids.shape[0])))
    ids_r = res.ids[:r]
    cold = (ids_r >= 0) & ~_exact_resident(state)[jnp.maximum(ids_r, 0)]
    fetch = jnp.where(cold, ids_r, -1)
    d_exact = gather_l2(q[None, :], state.vectors, fetch[None, :])[0]
    d_new = jnp.where(cold, d_exact, res.dists[:r])
    neg, order = jax.lax.top_k(-d_new, r)
    stats = res.stats._replace(
        n_vec=res.stats.n_vec + jnp.sum(cold).astype(jnp.int32))
    return res._replace(ids=res.ids.at[:r].set(ids_r[order]),
                        dists=res.dists.at[:r].set(-neg),
                        stats=stats)


def _bottom_adj_fn(cfg: HNSWConfig, state: HNSWState):
    """Batched bottom-layer adjacency: B node ids -> one LSM batch lookup."""
    def fn(nodes):
        found, rows, probes = lsm.get_batch(cfg.lsm_cfg, state.store, nodes)
        return jnp.where(found[:, None], rows, -1), probes
    return fn


def _snapshot_adj_fn(snapshot: jax.Array):
    """Adjacency served from a resolved dense view (`lsm.snapshot_rows`).

    Row-for-row identical to `_bottom_adj_fn` against the frozen tree —
    absent/tombstoned rows are already -1 in the view — but each read is a
    single gather instead of a full LSM probe.  `n_probes` keeps the
    1-read-per-row cost model of `lsm.get`.
    """
    def fn(nodes):
        rows = snapshot[jnp.maximum(nodes, 0)]
        return jnp.where((nodes >= 0)[:, None], rows, -1), \
            jnp.ones_like(nodes)
    return fn


def _upper_adj_fn(state: HNSWState, u: int):
    """Batched upper-layer adjacency (memory-resident dense rows)."""
    def fn(nodes):
        rows = state.upper_adj[u, jnp.maximum(nodes, 0)]
        return jnp.where((nodes >= 0)[:, None], rows, -1), \
            jnp.zeros_like(nodes)
    return fn


def _point_dist(state: HNSWState, q: jax.Array, node: jax.Array) -> jax.Array:
    v = state.vectors[jnp.maximum(node, 0)]
    return jnp.sum((q - v) ** 2)


def _descend_upper(cfg: HNSWConfig, state: HNSWState, q: jax.Array,
                   down_to: jax.Array):
    """Greedy-route through upper layers u = num_upper-1 .. down_to."""
    ep = jnp.maximum(state.entry, 0)
    d_ep = _point_dist(state, q, ep)
    for u in reversed(range(cfg.num_upper)):
        live_u = state.levels > u
        new_ep, new_d = greedy_descent(q, ep, d_ep, state.upper_adj[u],
                                       state.vectors, live_u)
        use = jnp.asarray(u, jnp.int32) >= down_to
        ep = jnp.where(use, new_ep, ep)
        d_ep = jnp.where(use, new_d, d_ep)
    return ep, d_ep


def _topm(ids: jax.Array, dists: jax.Array, m: int):
    """Best-m prefix of a distance-sorted candidate list (pad -1).

    `lax.top_k` instead of a stable argsort: ties resolve to the lower
    index either way, but top_k is a selection, not a full sort — XLA
    CPU's stable sorts were the dominant cost of the delete relink scan.
    """
    neg_d, order = jax.lax.top_k(-dists, m)
    out_ids = ids[order]
    out_d = -neg_d
    return jnp.where(jnp.isfinite(out_d), out_ids, -1), out_d


def _diversity_topm(ids: jax.Array, dists: jax.Array, vectors: jax.Array,
                    m: int, alpha: float = 1.0):
    """HNSW neighbor-selection heuristic (keepPruned variant).

    Greedily keeps candidate c only if it is closer to the base point than
    to every already-kept neighbor (`alpha` relaxes the test, Vamana
    style), then fills leftover slots with the nearest pruned candidates.
    Plain closest-M edges all point into the local cluster and strand the
    graph on clustered data; diverse edges are what keeps it navigable.
    """
    order = jnp.argsort(dists, stable=True)
    ids, dists = ids[order], dists[order]
    c = ids.shape[0]
    cv = vectors[jnp.maximum(ids, 0)]
    pair = jnp.sum((cv[:, None, :] - cv[None, :, :]) ** 2, axis=-1)
    valid = jnp.isfinite(dists) & (ids >= 0)

    def body(i, kept):
        dominated = jnp.any(kept & (alpha * pair[i] < dists[i]))
        space = jnp.sum(kept) < m
        return kept.at[i].set(valid[i] & (~dominated) & space)

    kept = jax.lax.fori_loop(0, c, body, jnp.zeros((c,), jnp.bool_))
    rank = jnp.argsort(~kept, stable=True)   # kept first, distance order
    ids2, valid2 = ids[rank], valid[rank]
    return jnp.where(valid2[:m], ids2[:m], -1), dists[rank][:m]


def _evict_slot(row: jax.Array, row_vecs_d_new: jax.Array) -> jax.Array:
    """Backlink slot choice: empty slot first, else evict the existing
    neighbor *closest to the incoming node* (most redundant direction) —
    never the farthest, which would strip the long-range portals."""
    score = jnp.where(row < 0, INF, -row_vecs_d_new)
    return jnp.argmax(score)


def _dedup_to_inf(ids: jax.Array, dists: jax.Array):
    """Mask duplicate ids (keep the first occurrence) with +inf.

    O(C^2) comparison triangle instead of sort+scatter: identical result
    (the stable id-sort kept the lowest original index of each id group),
    and at relink pool sizes the triangle is far cheaper than an XLA CPU
    stable sort.
    """
    eq = ids[None, :] == ids[:, None]
    dup = jnp.any(jnp.tril(eq, k=-1), axis=1)
    return jnp.where(dup, INF, dists)


def _relink_upper_rows(cfg: HNSWConfig, state_vectors, state_levels,
                       state_tomb, upper_adj, u: int, i, nbr, active):
    """Vectorized Algorithm-2 relink of node i's layer-u neighbors.

    All M_up relink rows derive from the same up-front 2-hop candidate
    pool (`cand` is read once, before any write), so the per-neighbor
    loop vectorizes into one [M_up, C] distance block + one scatter —
    bit-identical to writing the rows one at a time, since no row's
    computation reads another's write.
    """
    nbr_safe = jnp.maximum(nbr, 0)
    cand = jnp.concatenate(
        [upper_adj[u, nbr_safe].reshape(-1), nbr])              # 2-hop pool C
    d = jnp.sum((state_vectors[jnp.maximum(cand, 0)][None, :, :]
                 - state_vectors[nbr_safe][:, None, :]) ** 2, axis=-1)
    bad = (cand[None, :] < 0) | (cand[None, :] == i) \
        | (cand[None, :] == nbr[:, None]) \
        | (state_levels[jnp.maximum(cand, 0)][None, :] <= u) \
        | state_tomb[jnp.maximum(cand, 0)][None, :]
    d = jnp.where(bad, INF, d)
    masked = jnp.where(bad, -1, jnp.broadcast_to(cand, bad.shape))
    d = jax.vmap(_dedup_to_inf)(masked, d)
    new_rows, _ = jax.vmap(lambda dd: _topm(cand, dd, cfg.M_up))(d)
    ok = active & (nbr >= 0)
    idx_w = jnp.where(ok, nbr_safe, cfg.cap)   # masked rows drop
    upper_adj = upper_adj.at[u, idx_w].set(new_rows, mode="drop")
    return upper_adj.at[u, jnp.where(active, jnp.maximum(i, 0),
                                     cfg.cap)].set(-1, mode="drop")


# ---------------------------------------------------------------------------
# search (paper §3.2 "Search in LSM-VEC")
# ---------------------------------------------------------------------------

def search(cfg: HNSWConfig, state: HNSWState, q: jax.Array,
           *, rho: float | None = None, ef: int | None = None,
           use_filter: bool | None = None,
           n_expand: int | None = None,
           snapshot: jax.Array | None = None,
           active: jax.Array | None = None) -> BeamResult:
    """Single-query search: upper greedy descent -> sampled bottom beam.

    `n_expand` > 1 turns on multi-expansion (DESIGN.md §3): that many
    frontier nodes are expanded per beam iteration through one batched
    adjacency read and one fused distance block.  The default (1) is the
    paper's classic one-node-per-hop traversal.

    `snapshot` (optional, from `lsm.snapshot_rows`) serves bottom-layer
    adjacency by row gather from a resolved dense view instead of per-hop
    LSM probes — bit-identical results against an unchanged tree; the
    caller owns invalidation (re-resolve after any write).  `active`
    supports pad-and-mask dispatch: a False lane returns all -1/inf,
    records nothing, and costs no IOStats (DESIGN.md §8).

    Under `cfg.lazy_delete` the traversal distinguishes *routable* from
    *returnable* (DESIGN.md §9): tombstoned nodes are expanded through at
    full cost — their edges keep delete-damaged regions reachable — but
    never appear in the returned top-k.
    """
    if cfg.fused_beam and snapshot is not None:
        res = _search_batch_fused(
            cfg, state, q[None, :], snapshot=snapshot,
            active=(None if active is None
                    else jnp.asarray(active).reshape(1)),
            rho=rho, ef=ef, use_filter=use_filter, n_expand=n_expand)
        return jax.tree.map(lambda a: a[0], res)
    ef = ef or cfg.ef_search
    rho = cfg.rho if rho is None else rho
    use_filter = cfg.use_filter if use_filter is None else use_filter
    n_expand = cfg.n_expand if n_expand is None else n_expand
    # clamp like beam_search does, so the max_iters budget below stays
    # B-invariant even for n_expand > ef
    n_expand = max(1, min(n_expand, ef))
    routable = state.levels >= 0
    # static dispatch: the eager config never pays the returnable re-pack
    returnable = (routable & ~state.tombstone) if cfg.lazy_delete else None
    ep, d_ep = _descend_upper(cfg, state, q, jnp.zeros((), jnp.int32))
    code_q = simhash.encode(simhash.SimHashParams(state.proj), q[None, :])[0]
    adj_fn = _bottom_adj_fn(cfg, state) if snapshot is None \
        else _snapshot_adj_fn(snapshot)
    dist_fn = _tier_dist_fn(state, q) if cfg.tier else _dist_fn(state, q)
    res = beam_search(
        q, ep, d_ep,
        adj_fn, dist_fn,
        state.codes, code_q, routable,
        cap=cfg.cap, ef=ef, k=cfg.k, m_bits=cfg.m_bits, eps=cfg.eps,
        rho=rho, max_iters=2 * ef, use_filter=use_filter,
        q_norm=jnp.sqrt(jnp.sum(q * q)), mean_norm=state.mean_norm,
        n_expand=n_expand, active=active, returnable=returnable)
    if cfg.tier:
        res = _tier_rerank(cfg, state, q, res)
    return res


def _search_batch_fused(cfg: HNSWConfig, state: HNSWState, qs: jax.Array,
                        *, snapshot: jax.Array,
                        active: jax.Array | None = None,
                        rho: float | None = None, ef: int | None = None,
                        use_filter: bool | None = None,
                        n_expand: int | None = None,
                        record_heat: bool = True) -> BeamResult:
    """Fused-megakernel route for the snapshot serving path: one
    `fused_beam_search` launch for the whole query block instead of a
    vmapped `while_loop` (DESIGN.md §15).

    The per-query prelude (upper greedy descent, SimHash query encode,
    norms) is vmapped exactly like `search`, and the dense operands
    (snapshot adjacency, routable/returnable lanes, tier split) carry
    the identical semantics — results are bit-parity with the
    `while_loop` path; `tests/test_beam_kernel.py` pins it.

    `record_heat=False` is a capability the `while_loop` path doesn't
    have: it statically drops the per-trip heat carries from the fused
    loop (result arrays come back as -1/False padding).
    """
    ef = ef or cfg.ef_search
    rho = cfg.rho if rho is None else rho
    use_filter = cfg.use_filter if use_filter is None else use_filter
    n_expand = cfg.n_expand if n_expand is None else n_expand
    n_expand = max(1, min(n_expand, ef))
    routable = state.levels >= 0
    returnable = (routable & ~state.tombstone) if cfg.lazy_delete else None
    params = simhash.SimHashParams(state.proj)
    ent, ent_d = jax.vmap(
        lambda q: _descend_upper(cfg, state, q,
                                 jnp.zeros((), jnp.int32)))(qs)
    code_qs = jax.vmap(lambda q: simhash.encode(params, q[None, :])[0])(qs)
    q_norms = jax.vmap(lambda q: jnp.sqrt(jnp.sum(q * q)))(qs)
    ids, dists, stats, heat_nodes, heat_mask = fused_beam_search(
        qs, ent, ent_d, snapshot, state.vectors, state.codes, code_qs,
        routable, q_norms, state.mean_norm, returnable=returnable,
        resident=_exact_resident(state) if cfg.tier else None,
        qvecs=state.qvecs if cfg.tier else None,
        qscale=state.qscale if cfg.tier else None, active=active,
        ef=ef, k=cfg.k, m_bits=cfg.m_bits, eps=cfg.eps, rho=rho,
        max_iters=2 * ef, use_filter=use_filter, n_expand=n_expand,
        record_heat=record_heat)
    # the kernel's fori_loop runs every lane for the whole trip cap
    trips = jnp.full(qs.shape[:1], beam_iter_cap(2 * ef, n_expand, ef),
                     jnp.int32)
    res = BeamResult(
        ids, dists,
        IOStats(n_adj=stats[:, 0], n_vec=stats[:, 1],
                n_filtered=stats[:, 2], n_hops=stats[:, 3]),
        heat_nodes, heat_mask, trips)
    if cfg.tier:
        res = jax.vmap(lambda q, r: _tier_rerank(cfg, state, q, r))(qs, res)
    return res


def search_batch(cfg: HNSWConfig, state: HNSWState, qs: jax.Array,
                 *, active: jax.Array | None = None,
                 record_heat: bool = True, **kw) -> BeamResult:
    """Batched search; `active` (bool[B]) masks padded query lanes.

    With `cfg.fused_beam` and a snapshot, the whole block routes
    through the one-launch megakernel path; otherwise the vmapped
    `while_loop` (which always records heat — `record_heat` is the
    fused path's static skip and is ignored here).
    """
    if cfg.fused_beam and kw.get("snapshot") is not None:
        return _search_batch_fused(cfg, state, qs, active=active,
                                   record_heat=record_heat, **kw)
    if active is None:
        return jax.vmap(lambda q: search(cfg, state, q, **kw))(qs)
    return jax.vmap(lambda q, a: search(cfg, state, q, active=a, **kw))(
        qs, active)


def beam_counters(res: BeamResult, *, ef: int, n_expand: int) -> jax.Array:
    """A search batch's beam-loop counters, reduced on the device:
    int32[3] = (trips, hops, slots).

    `trips` is how many trips the batch's loop ran: the max over lanes
    (the vmapped `while_loop` runs until its slowest lane stops; the
    fused kernel runs its trip cap).  `hops` sums the lanes' expansions.
    `slots` is the expansions the loop had room for: trips x lanes x
    nodes popped per trip, padded lanes included, since the device runs
    them — so hops / slots is the share of the loop's lanes that did
    work, at most 1.
    """
    trips = jnp.max(res.trips).astype(jnp.int32)
    width = res.trips.shape[0] * max(1, min(n_expand, ef))
    return jnp.stack([trips, jnp.sum(res.stats.n_hops).astype(jnp.int32),
                      trips * width])


# ---------------------------------------------------------------------------
# insert (Algorithm 1)
# ---------------------------------------------------------------------------

def _backlink_rows(cfg: HNSWConfig, store: lsm.LSMState, vectors: jax.Array,
                   nbrs: jax.Array, x: jax.Array, i) -> lsm.LSMState:
    """Bulk bottom-layer backlink pass: read the M neighbor rows in one
    batched lookup, evict each row's most redundant slot, write everything
    back with a single `lsm.puts`.  Masked (-1) neighbors land on the
    reserved dead key, exactly like the per-edge `_put_masked` path did."""
    ok = nbrs >= 0
    nbrs_safe = jnp.maximum(nbrs, 0)
    found, rows, _ = lsm.get_batch(cfg.lsm_cfg, store, nbrs_safe)  # [M, M]
    rows = jnp.where(found[:, None], rows, -1)
    d_new = jnp.sum((vectors[jnp.maximum(rows, 0)]
                     - x[None, None, :]) ** 2, axis=-1)            # [M, M]
    slots = jax.vmap(_evict_slot)(rows, d_new)
    new_rows = rows.at[jnp.arange(nbrs.shape[0]), slots].set(i)
    dead = jnp.asarray(cfg.cap, jnp.int32)
    return lsm.puts(cfg.lsm_cfg, store,
                    jnp.where(ok, nbrs_safe, dead), new_rows)


def _put_masked(cfg: HNSWConfig, store: lsm.LSMState, key, row, active):
    """LSM put that lands on a reserved dead key when inactive.

    Avoids lax.cond duplication of the flush machinery: id `cap` is outside
    the live id space and never looked up.
    """
    dead = jnp.asarray(cfg.cap, jnp.int32)
    return lsm.put(cfg.lsm_cfg, store,
                   jnp.where(active, key, dead), row)


def insert(cfg: HNSWConfig, state: HNSWState, x: jax.Array,
           key: jax.Array) -> Tuple[HNSWState, IOStats]:
    """Insert one vector (Algorithm 1).  Returns (state, construction IO)."""
    i = state.count
    # paper: Pr(L) ∝ e^{-L/s}  -> L = floor(s * Exp(1)), capped at num_upper
    # (s = cfg.level_scale; 1.0 is the classic draw)
    u01 = jax.random.uniform(key, (), jnp.float32, 1e-7, 1.0)
    lvl = jnp.minimum(
        jnp.floor(-cfg.level_scale * jnp.log(u01)).astype(jnp.int32),
        cfg.num_upper)

    xnorm = jnp.sqrt(jnp.sum(x * x))
    code = simhash.encode(simhash.SimHashParams(state.proj), x[None, :])[0]
    state = state._replace(
        vectors=state.vectors.at[i].set(x),
        norms=state.norms.at[i].set(xnorm),
        codes=state.codes.at[i].set(code),
        levels=state.levels.at[i].set(lvl),
        mean_norm=(state.mean_norm * state.n_live + xnorm)
        / jnp.maximum(state.n_live + 1, 1),
    )

    first = state.n_live == 0

    # ---- phase 1+2: upper layers ------------------------------------------
    # This block intentionally stays the paper-exact, unconditional form of
    # Algorithm 1 (beam + where-selects on every layer, sequential
    # backlinks): it is the parity reference the tests pin.  The batched
    # pipeline's `_connect_upper` is the cond-gated, vectorized variant of
    # the same logic — a change to the linking rule must land in both.
    ep = jnp.maximum(state.entry, 0)
    d_ep = _point_dist(state, x, ep)
    upper_adj = state.upper_adj
    for u in reversed(range(cfg.num_upper)):
        live_u = (state.levels > u) & (jnp.arange(cfg.cap) != i)
        above = jnp.asarray(u, jnp.int32) >= lvl   # greedy-only zone
        # greedy step (used when u >= lvl)
        g_ep, g_d = greedy_descent(x, ep, d_ep, upper_adj[u],
                                   state.vectors, live_u)
        # connect zone (u < lvl): ef-search this layer, link bidirectionally
        res = beam_search(
            x, ep, d_ep, _upper_adj_fn(state._replace(upper_adj=upper_adj), u),
            _dist_fn(state, x), state.codes, code, live_u,
            cap=cfg.cap, ef=cfg.ef_construction, k=cfg.k, m_bits=cfg.m_bits,
            eps=cfg.eps, rho=1.0, max_iters=2 * cfg.ef_construction,
            use_filter=False, q_norm=xnorm, mean_norm=state.mean_norm)
        nbrs, _ = _diversity_topm(res.ids, res.dists, state.vectors,
                                  cfg.M_up)
        connect = (~above) & (~first)
        upper_adj = upper_adj.at[u, i].set(
            jnp.where(connect, nbrs, upper_adj[u, i]))
        # backlinks: always formed; evict the most redundant edge when full
        for j in range(cfg.M_up):
            n = nbrs[j]
            ok = connect & (n >= 0)
            n_safe = jnp.maximum(n, 0)
            row = upper_adj[u, n_safe]
            d_new = jnp.sum((state.vectors[jnp.maximum(row, 0)]
                             - x[None, :]) ** 2, axis=-1)
            slot = _evict_slot(row, d_new)
            new_row = row.at[slot].set(i)
            upper_adj = upper_adj.at[u, n_safe].set(
                jnp.where(ok, new_row, row))
        ep = jnp.where(above, g_ep, jnp.where(res.dists[0] < INF,
                                              res.ids[0], ep))
        d_ep = jnp.where(above, g_d, jnp.minimum(res.dists[0], d_ep))
    state = state._replace(upper_adj=upper_adj)

    # ---- phase 3: bottom layer (disk / LSM) ---------------------------------
    res = beam_search(
        x, ep, d_ep, _bottom_adj_fn(cfg, state), _dist_fn(state, x),
        state.codes, code, (state.levels >= 0) & (jnp.arange(cfg.cap) != i),
        cap=cfg.cap, ef=cfg.ef_construction, k=cfg.k, m_bits=cfg.m_bits,
        eps=cfg.eps, rho=cfg.rho, max_iters=2 * cfg.ef_construction,
        use_filter=cfg.use_filter, q_norm=xnorm, mean_norm=state.mean_norm)
    nbrs, _ = _diversity_topm(res.ids, res.dists, state.vectors, cfg.M)
    nbrs = jnp.where(first, -1, nbrs)

    store = _put_masked(cfg, state.store, i, nbrs, jnp.bool_(True))
    # bidirectional links (Fig. 3: links are always formed; when the row is
    # full the most redundant existing edge is evicted, keeping the new
    # node reachable without stripping long-range portals).  The whole
    # backlink pass is amortized: one batched row read over the M
    # neighbors and one bulk `puts` instead of M get+put round-trips —
    # exact because beam candidates (hence `nbrs`) are distinct ids, so
    # no backlink row feeds another's lookup.
    store = _backlink_rows(cfg, store, state.vectors, nbrs, x, i)

    new_entry = jnp.where(first | (lvl > state.max_level), i, state.entry)
    state = state._replace(
        store=store,
        count=state.count + 1,
        n_live=state.n_live + 1,
        entry=new_entry,
        max_level=jnp.maximum(state.max_level, lvl))
    stats = res.stats._replace(
        n_vec=res.stats.n_vec + cfg.M)  # backlink row re-rankings
    return state, stats


# ---------------------------------------------------------------------------
# batched updates (DESIGN.md §4) — the FreshDiskANN-style bulk pipeline
# ---------------------------------------------------------------------------

def _connect_upper(cfg: HNSWConfig, state: HNSWState, upper_adj: jax.Array,
                   u: int, x, code, xnorm, i, ep, d_ep, n_expand: int):
    """Connect node i on upper layer u: ef-search, diversity-select, and a
    vectorized backlink-eviction pass (exact because the selected neighbors
    are distinct beam candidates, so their row updates are independent).
    Returns the updated (upper_adj, ep, d_ep)."""
    n_expand = max(1, min(n_expand, cfg.ef_construction))
    live_u = (state.levels > u) & (jnp.arange(cfg.cap) != i)
    adj = _upper_adj_fn(state._replace(upper_adj=upper_adj), u)
    res = beam_search(
        x, ep, d_ep, adj, _dist_fn(state, x), state.codes, code, live_u,
        cap=cfg.cap, ef=cfg.ef_construction, k=cfg.k, m_bits=cfg.m_bits,
        eps=cfg.eps, rho=1.0, max_iters=2 * cfg.ef_construction,
        use_filter=False, q_norm=xnorm, mean_norm=state.mean_norm,
        n_expand=n_expand)
    nbrs, _ = _diversity_topm(res.ids[:max(2 * cfg.M_up, cfg.M_up + 4)],
                              res.dists[:max(2 * cfg.M_up, cfg.M_up + 4)],
                              state.vectors, cfg.M_up)
    upper_adj = upper_adj.at[u, i].set(nbrs)
    ok = nbrs >= 0
    ns = jnp.maximum(nbrs, 0)
    rows = upper_adj[u, ns]                                  # [M_up, M_up]
    d_new = jnp.sum((state.vectors[jnp.maximum(rows, 0)]
                     - x[None, None, :]) ** 2, axis=-1)
    slots = jax.vmap(_evict_slot)(rows, d_new)
    new_rows = rows.at[jnp.arange(cfg.M_up), slots].set(i)
    # masked entries scatter out of bounds and are dropped
    idx = jnp.where(ok, ns, cfg.cap)
    upper_adj = upper_adj.at[u, idx].set(new_rows, mode="drop")
    ep = jnp.where(res.dists[0] < INF, res.ids[0], ep)
    d_ep = jnp.minimum(res.dists[0], d_ep)
    return upper_adj, ep, d_ep


def insert_batch(cfg: HNSWConfig, state: HNSWState, xs: jax.Array,
                 keys: jax.Array, *,
                 valid: jax.Array | None = None,
                 n_expand: int | None = None,
                 return_overlay: bool = False):
    """Insert a batch of vectors in one jit — zero per-item host syncs.

    Two phases (DESIGN.md §4):
      A (vmapped): every vector's bottom-layer candidate search runs
        against the *pre-batch* graph snapshot with multi-expansion beams,
        so the whole batch is one embarrassingly parallel sweep — the
        FreshDiskANN streaming-update recipe.
      B (`lax.scan`): graph writes are sequential and ids are computed
        inside the scan from the carried `count`.  Upper-layer connects
        run under `lax.cond` (only ~e^-1 of inserts reach layer >= 1, so
        the expensive construction beams are skipped for the rest), and
        the bottom backlink pass is the bulk read + `lsm.puts` path.

    Items in the same batch do not see each other as bottom-layer
    neighbor *candidates* (they still become mutually reachable through
    base-graph backlinks, like sequential inserts).  Callers should seed
    a small graph per-item first; `LSMVecIndex.insert_batch` does.

    `valid` (bool[n], default all-True) is the pad-and-mask hook
    (DESIGN.md §8): masked items allocate no id and write nothing, so a
    serving layer can dispatch ragged micro-batches through one traced
    shape.  Valid items must form a *prefix* (padding at the tail) so the
    ids computed from the scanned `count` stay consecutive.

    `return_overlay=True` additionally returns the staged bottom-layer
    write set `(overlay_rows int32[cap+1, M], overlay_valid bool[cap+1])`
    — every key the batch touched with its *final* row.  A caller
    holding a pre-batch dense snapshot can patch it with one
    `jnp.where(overlay_valid, overlay_rows, snap)` instead of paying a
    full `lsm.resolve_all` re-resolve (DESIGN.md §13).
    """
    if n_expand is None:
        n_expand = cfg.batch_expand
    n_expand = max(1, min(n_expand, cfg.ef_construction))
    n = xs.shape[0]
    if valid is None:
        valid = jnp.ones((n,), jnp.bool_)
    base_id = state.count
    codes = simhash.encode(simhash.SimHashParams(state.proj), xs)
    xnorms = jnp.sqrt(jnp.sum(xs * xs, axis=1))
    u01 = jax.vmap(
        lambda kk: jax.random.uniform(kk, (), jnp.float32, 1e-7, 1.0))(keys)
    lvls = jnp.minimum(
        jnp.floor(-cfg.level_scale * jnp.log(u01)).astype(jnp.int32),
        cfg.num_upper)

    # Intra-batch neighbor candidates: the snapshot cannot see batch
    # siblings, and an out-of-distribution batch (say, a brand-new
    # cluster) would otherwise compete for the same few base-node
    # backlink slots and come out mostly unreachable.  One triangular
    # [n, n] distance block (RAM-resident, no t_v cost — the batch is in
    # memory) lets item i also link to its nearest *earlier* items j < i,
    # whose ids (base_id + j) are deterministic and whose rows are
    # already staged in the overlay when i's backlink pass reads them —
    # the same "link to already-placed nodes" rule sequential insert has.
    bb = (xnorms[:, None] ** 2 + xnorms[None, :] ** 2
          - 2.0 * jnp.matmul(xs, xs.T, precision=jax.lax.Precision.HIGHEST))
    # masked (padding) items are never candidates; valid items form a
    # prefix, so the j < i triangle only ever pairs valid with valid
    bb = jnp.where(jnp.tril(jnp.ones((n, n), jnp.bool_), k=-1)
                   & valid[None, :], bb, INF)
    m_in = max(1, min(cfg.M, n - 1))
    nb_negd, nb_j = jax.lax.top_k(-bb, m_in)
    in_d = -nb_negd                                            # [n, m_in]
    in_ids = jnp.where(jnp.isfinite(in_d), base_id + nb_j, -1)

    # phase-A view with the batch vectors materialized, so diversity
    # selection can measure candidate pairs that include batch siblings
    vectors_view = state.vectors.at[base_id + jnp.arange(n)].set(xs)

    # ---- phase A: batch-parallel candidate search on the snapshot ---------
    # The pre-batch bottom graph is frozen for the whole sweep, so resolve
    # the LSM tree into a dense newest-wins view once (FreshDiskANN
    # searches its frozen disk index the same way) and serve adjacency by
    # row gather instead of per-hop LSM probes.  Rows are identical to
    # what `get_batch` would return; `n_probes` keeps the 1-read-per-row
    # cost model of `lsm.get`.
    snap_live, snap_rows = lsm.resolve_all(cfg.lsm_cfg, state.store, cfg.cap)
    snapshot = jnp.where(snap_live[:, None] > 0, snap_rows, -1)
    snap_adj = _snapshot_adj_fn(snapshot)

    def cand_search(x, code, xnorm, ids_in, d_in, v):
        ep, d_ep = _descend_upper(cfg, state, x, jnp.zeros((), jnp.int32))
        res = beam_search(
            x, ep, d_ep, snap_adj, _dist_fn(state, x),
            state.codes, code, state.levels >= 0,
            cap=cfg.cap, ef=cfg.ef_construction, k=cfg.k, m_bits=cfg.m_bits,
            eps=cfg.eps, rho=cfg.rho,
            max_iters=2 * cfg.ef_construction,
            use_filter=cfg.use_filter, q_norm=xnorm,
            mean_norm=state.mean_norm, n_expand=n_expand, active=v)
        # diversity-select the bottom neighbors here: it only reads the
        # frozen snapshot + batch view, and vmapping it runs the
        # sequential dominance loop once for the whole batch instead of
        # once per scanned item.  The beam is distance-sorted and
        # keepPruned almost never reaches past ~2M candidates, so
        # truncate before merging the intra-batch pool (disjoint ids:
        # beam ids are pre-batch, ids_in are >= base_id).
        pool = min(2 * cfg.M, res.ids.shape[0])
        cand_ids = jnp.concatenate([res.ids[:pool], ids_in])
        cand_d = jnp.concatenate([res.dists[:pool], d_in])
        nbrs, _ = _diversity_topm(cand_ids, cand_d, vectors_view, cfg.M)
        return nbrs, res.stats

    cand_nbrs, stats_a = jax.vmap(cand_search)(xs, codes, xnorms,
                                               in_ids, in_d, valid)

    # ---- phase B: sequential graph writes ---------------------------------
    # Bottom-layer rows are staged in a dense overlay carried through the
    # scan instead of being put into the LSM per item: a flush `lax.cond`
    # inside a scan makes XLA copy the level arrays on every step
    # (measured ~20x the cost of the appends themselves).  Reads resolve
    # overlay-first, then the phase-A snapshot — exactly the newest-wins
    # view the in-scan puts would have produced — and the LSM absorbs all
    # staged rows in one bulk `puts` after the scan.
    overlay_rows = jnp.full((cfg.cap + 1, cfg.M), -1, jnp.int32)
    overlay_valid = jnp.zeros((cfg.cap + 1,), jnp.bool_)
    dead = jnp.asarray(cfg.cap, jnp.int32)

    def step(carry, inp):
        st, orows, ovalid = carry
        x, code, xnorm, lvl, nbrs, v = inp
        i = st.count
        # masked (padding) items scatter to the out-of-bounds id `cap`,
        # which mode="drop" discards — the step is then a pure no-op
        i_w = jnp.where(v, i, cfg.cap)
        st = st._replace(
            vectors=st.vectors.at[i_w].set(x, mode="drop"),
            norms=st.norms.at[i_w].set(xnorm, mode="drop"),
            codes=st.codes.at[i_w].set(code, mode="drop"),
            levels=st.levels.at[i_w].set(lvl, mode="drop"),
            mean_norm=jnp.where(
                v,
                (st.mean_norm * st.n_live + xnorm)
                / jnp.maximum(st.n_live + 1, 1),
                st.mean_norm))
        first = st.n_live == 0

        # Upper-layer work only matters for items that reach layer >= 1
        # (~1 - e^-1 of them): bottom-layer candidates are precomputed, so
        # for lvl == 0 the greedy descents and connects would all be dead
        # code.  One cond skips the whole loop for the common case.
        def upper_work(ua):
            ep = jnp.maximum(st.entry, 0)
            d_ep = _point_dist(st, x, ep)
            for u in reversed(range(cfg.num_upper)):
                live_u = (st.levels > u) & (jnp.arange(cfg.cap) != i)
                g_ep, g_d = greedy_descent(x, ep, d_ep, ua[u],
                                           st.vectors, live_u)
                above = jnp.asarray(u, jnp.int32) >= lvl

                def connect(op, u=u):
                    a, e, de = op
                    return _connect_upper(cfg, st, a, u, x, code, xnorm, i,
                                          e, de, n_expand)

                def skip(op, g_ep=g_ep, g_d=g_d):
                    a, e, de = op
                    return a, g_ep, g_d

                ua, ep, d_ep = jax.lax.cond(
                    ~above, connect, skip, (ua, ep, d_ep))
            return ua

        upper_adj = jax.lax.cond((lvl > 0) & (~first) & v, upper_work,
                                 lambda ua: ua, st.upper_adj)
        st = st._replace(upper_adj=upper_adj)

        nbrs = jnp.where(first | (~v), -1, nbrs)
        # backlink pass against overlay-else-snapshot rows (pure gathers)
        ok = nbrs >= 0
        nbrs_safe = jnp.maximum(nbrs, 0)
        rows = jnp.where(ovalid[nbrs_safe][:, None],
                         orows[nbrs_safe], snapshot[nbrs_safe])
        d_new = jnp.sum((st.vectors[jnp.maximum(rows, 0)]
                         - x[None, None, :]) ** 2, axis=-1)
        slots = jax.vmap(_evict_slot)(rows, d_new)
        new_rows = rows.at[jnp.arange(cfg.M), slots].set(i)
        w_keys = jnp.concatenate([jnp.where(v, i, dead)[None],
                                  jnp.where(ok, nbrs_safe, dead)])
        w_vals = jnp.concatenate([nbrs[None, :], new_rows])
        orows = orows.at[w_keys].set(w_vals)
        ovalid = ovalid.at[w_keys].set(True)

        vi = v.astype(jnp.int32)
        new_entry = jnp.where(v & (first | (lvl > st.max_level)),
                              i, st.entry)
        st = st._replace(
            count=st.count + vi, n_live=st.n_live + vi,
            entry=new_entry,
            max_level=jnp.where(v, jnp.maximum(st.max_level, lvl),
                                st.max_level))
        return (st, orows, ovalid), w_keys

    (state, overlay_rows, overlay_valid), w_keys = jax.lax.scan(
        step, (state, overlay_rows, overlay_valid),
        (xs, codes, xnorms, lvls, cand_nbrs, valid))
    # one bulk LSM apply: every staged key carries its *final* overlay row,
    # so duplicate keys across items all write the same (last) value and
    # newest-wins is preserved.  (Deduping here would not save memtable
    # slots — static shapes mean duplicates could only be renamed to the
    # dead key, which occupies a slot all the same.)  Dead-key rows pad
    # exactly like the per-item `_put_masked` path.
    w_keys = w_keys.reshape(-1)
    w_vals = overlay_rows[jnp.minimum(w_keys, cfg.cap)]
    state = state._replace(
        store=lsm.puts(cfg.lsm_cfg, state.store, w_keys, w_vals))
    # masked lanes already report zero beam stats (active-gated)
    stats = IOStats(*(jnp.sum(a).astype(jnp.int32) for a in stats_a))
    # backlink row re-rankings, as in the per-item path
    stats = stats._replace(
        n_vec=stats.n_vec
        + jnp.sum(valid).astype(jnp.int32) * cfg.M)
    if return_overlay:
        return state, stats, (overlay_rows, overlay_valid)
    return state, stats


def delete_batch(cfg: HNSWConfig, state: HNSWState,
                 ids: jax.Array) -> Tuple[HNSWState, IOStats]:
    """Delete a batch of node ids in one jit.

    Dispatches statically on `cfg.lazy_delete`: the lazy path
    (`tombstone_batch`) marks the ids routable-but-not-returnable with no
    graph writes; the eager path is the Algorithm-2 relink pipeline
    below.  Negative ids are masked no-ops either way (the pad-and-mask
    serving contract); non-negative ids that are absent or already
    deleted are *counted* no-ops (`state.n_delete_noops`), never silent
    graph writes.
    """
    if cfg.lazy_delete:
        return tombstone_batch(cfg, state, ids)
    return _delete_batch_eager(cfg, state, ids)


def _delete_batch_eager(cfg: HNSWConfig, state: HNSWState,
                        ids: jax.Array) -> Tuple[HNSWState, IOStats]:
    """Eager batched delete — Algorithm 2 through an overlay.

    Like `insert_batch`'s phase B, the scanned per-item relinks read and
    stage bottom-layer rows in a dense newest-wins overlay (seeded from
    one `lsm.resolve_all` of the pre-batch tree) instead of issuing LSM
    puts inside the scan — in-scan puts drag the flush `lax.cond` into
    the loop and XLA copies the level arrays every step (the cond-copy
    tax, DESIGN.md §4).  One bulk `lsm.puts` after the scan applies every
    staged key's final row and liveness, so the resulting tree *content*
    is identical to the sequential per-item loop (flush/compaction timing
    may differ, which only changes how entries are distributed across
    runs, never what a lookup resolves).

    Negative ids are masked no-ops (the pad-and-mask serving contract,
    DESIGN.md §8): they allocate no writes and leave every state field
    untouched.
    """
    M = cfg.M
    ids = jnp.asarray(ids, jnp.int32)
    snap_live, snap_rows = lsm.resolve_all(cfg.lsm_cfg, state.store, cfg.cap)
    # spare slot cfg.cap absorbs masked writes, exactly like insert_batch
    dlive = jnp.concatenate([snap_live, jnp.zeros((1,), jnp.int8)])
    drows = jnp.concatenate(
        [snap_rows, jnp.full((1, M), lsm.EMPTY, jnp.int32)])
    dead = jnp.asarray(cfg.cap, jnp.int32)
    tomb = jnp.full((M,), lsm.EMPTY, jnp.int32)

    def step(carry, node):
        st, dlive, drows = carry
        i = jnp.asarray(node, jnp.int32)
        v = i >= 0
        i_safe = jnp.maximum(i, 0)
        # absent / already-deleted ids are counted no-ops: every write
        # below is gated on `was_live`, so a double delete stages nothing
        # (previously it re-tombstoned the key — a silent graph write)
        was_live = v & (st.levels[i_safe] >= 0)

        # ---- upper layers (same relink rule as `delete`, v-gated) --------
        upper_adj = st.upper_adj
        for u in range(cfg.num_upper):
            active = was_live & (st.levels[i_safe] > u)
            nbr = upper_adj[u, i_safe]                           # [M_up]
            upper_adj = _relink_upper_rows(
                cfg, st.vectors, st.levels, st.tombstone, upper_adj, u, i,
                nbr, active)
        st = st._replace(upper_adj=upper_adj)

        # ---- bottom layer (Algorithm 2 lines 13-22) ----------------------
        # reads resolve from the carried dense view: identical content to
        # what per-item `lsm.get`/`get_batch` would return mid-sequence
        n1 = jnp.where(was_live & (dlive[i_safe] > 0),
                       drows[i_safe], -1)                       # [M]
        n1_safe = jnp.maximum(n1, 0)
        rows = drows[n1_safe]                                   # [M, M]
        cand = jnp.concatenate([rows.reshape(-1), n1])          # C = M*M + M
        d = jnp.sum((st.vectors[jnp.maximum(cand, 0)][None, :, :]
                     - st.vectors[n1_safe][:, None, :]) ** 2, axis=-1)
        bad = (cand[None, :] < 0) | (cand[None, :] == i) \
            | (cand[None, :] == n1[:, None]) \
            | (st.levels[jnp.maximum(cand, 0)][None, :] < 0) \
            | st.tombstone[jnp.maximum(cand, 0)][None, :]
        d = jnp.where(bad, INF, d)
        masked_ids = jnp.where(bad, -1, jnp.broadcast_to(cand, bad.shape))
        d = jax.vmap(_dedup_to_inf)(masked_ids, d)
        new_rows, _ = jax.vmap(lambda dd: _topm(cand, dd, cfg.M))(d)

        # stage: relinked neighbor rows (live), then i's tombstone —
        # same write order as the sequential puts + lsm.delete
        tgt = jnp.where(n1 >= 0, n1_safe, dead)
        drows = drows.at[tgt].set(new_rows)
        dlive = dlive.at[tgt].set(1)
        ti = jnp.where(was_live, i_safe, dead)
        drows = drows.at[ti].set(tomb)
        dlive = dlive.at[ti].set(0)
        w_keys = jnp.concatenate([tgt, ti[None]])               # [M + 1]

        levels = st.levels.at[i_safe].set(
            jnp.where(was_live, -1, st.levels[i_safe]))
        need_new_entry = was_live & (st.entry == i)
        # entry repair is a full-cap argmax, needed only when the entry
        # node itself dies — cond it out of the common per-item path
        entry = jax.lax.cond(
            need_new_entry,
            lambda lv: jnp.argmax(
                jnp.where(jnp.arange(cfg.cap) == i, -1, lv)
            ).astype(jnp.int32),
            lambda lv: st.entry, levels)
        st = st._replace(
            levels=levels, entry=entry,
            max_level=jnp.where(
                was_live, jnp.maximum(levels[jnp.maximum(entry, 0)], 0),
                st.max_level),
            n_live=st.n_live - was_live.astype(jnp.int32),
            n_delete_noops=st.n_delete_noops
            + (v & ~was_live).astype(jnp.int32))
        stats = IOStats(
            n_adj=jnp.where(was_live, 1 + cfg.M, 0).astype(jnp.int32),
            n_vec=jnp.where(
                was_live, jnp.sum(jnp.isfinite(d)), 0).astype(jnp.int32),
            n_filtered=jnp.zeros((), jnp.int32),
            n_hops=jnp.zeros((), jnp.int32))
        return (st, dlive, drows), (w_keys, stats)

    (state, dlive, drows), (w_keys, stats) = jax.lax.scan(
        step, (state, dlive, drows), ids)
    # one bulk LSM apply: duplicate keys all carry their *final* overlay
    # row + liveness, so newest-wins resolution matches the sequential loop
    w_keys = w_keys.reshape(-1)
    state = state._replace(
        store=lsm.puts(cfg.lsm_cfg, state.store, w_keys,
                       drows[w_keys], dlive[w_keys]))
    return state, IOStats(*(jnp.sum(a).astype(jnp.int32) for a in stats))


# ---------------------------------------------------------------------------
# delete (Algorithm 2)
# ---------------------------------------------------------------------------

def delete(cfg: HNSWConfig, state: HNSWState, node) -> Tuple[HNSWState, IOStats]:
    """Delete one node; dispatches statically on `cfg.lazy_delete`.

    Lazy (default): set the tombstone bit only — the node stays routable
    but is never returned; `consolidate` reclaims it later.  Eager: the
    paper's Algorithm-2 local relink.  Deleting an absent or
    already-deleted id is a counted no-op either way.
    """
    if cfg.lazy_delete:
        return tombstone_batch(cfg, state,
                               jnp.asarray(node, jnp.int32)[None])
    return _delete_eager(cfg, state, node)


def _delete_eager(cfg: HNSWConfig, state: HNSWState,
                  node) -> Tuple[HNSWState, IOStats]:
    """Delete a vector with local neighbor relinking (Algorithm 2)."""
    i = jnp.asarray(node, jnp.int32)
    was_live = state.levels[i] >= 0
    upper_adj = state.upper_adj

    # ---- upper layers (vectorized relink, see _relink_upper_rows) -----------
    for u in range(cfg.num_upper):
        active = state.levels[i] > u
        nbr = upper_adj[u, i]                                   # [M_up]
        upper_adj = _relink_upper_rows(
            cfg, state.vectors, state.levels, state.tombstone, upper_adj,
            u, i, nbr, active)
    state = state._replace(upper_adj=upper_adj)

    # ---- bottom layer (Algorithm 2 lines 13-22) -----------------------------
    # The per-neighbor relink rows all derive from the same up-front
    # 2-hop candidate pool (no read-after-write dependency), so the whole
    # pass vectorizes: one [M, C] distance block, vmapped dedup/top-M, and
    # one bulk `puts` for the M rewritten rows.
    found, n1, _ = lsm.get(cfg.lsm_cfg, state.store, i)
    n1 = jnp.where(found & was_live, n1, -1)                    # [M]
    n1_safe = jnp.maximum(n1, 0)
    _, rows, _ = lsm.get_batch(cfg.lsm_cfg, state.store, n1_safe)  # [M, M]
    cand = jnp.concatenate([rows.reshape(-1), n1])              # C = M*M + M
    d = jnp.sum((state.vectors[jnp.maximum(cand, 0)][None, :, :]
                 - state.vectors[n1_safe][:, None, :]) ** 2, axis=-1)
    bad = (cand[None, :] < 0) | (cand[None, :] == i) \
        | (cand[None, :] == n1[:, None]) \
        | (state.levels[jnp.maximum(cand, 0)][None, :] < 0) \
        | state.tombstone[jnp.maximum(cand, 0)][None, :]
    d = jnp.where(bad, INF, d)
    masked_ids = jnp.where(bad, -1, jnp.broadcast_to(cand, bad.shape))
    d = jax.vmap(_dedup_to_inf)(masked_ids, d)
    new_rows, _ = jax.vmap(lambda dd: _topm(cand, dd, cfg.M))(d)
    dead = jnp.asarray(cfg.cap, jnp.int32)
    store = lsm.puts(cfg.lsm_cfg, state.store,
                     jnp.where(n1 >= 0, n1_safe, dead), new_rows)
    n_vec = jnp.sum(jnp.isfinite(d)).astype(jnp.int32)
    # deleting an absent/dead id stages no tombstone (counted no-op)
    store = lsm.delete(cfg.lsm_cfg, store, jnp.where(was_live, i, dead))

    levels = state.levels.at[i].set(jnp.where(was_live, -1,
                                              state.levels[i]))
    # entry repair: highest remaining level (argmax breaks ties by lowest id)
    need_new_entry = was_live & (state.entry == i)
    alt = jnp.argmax(jnp.where(jnp.arange(cfg.cap) == i, -1, levels))
    entry = jnp.where(need_new_entry, alt.astype(jnp.int32), state.entry)
    state = state._replace(
        store=store, levels=levels, entry=entry,
        max_level=jnp.where(
            was_live, jnp.maximum(levels[jnp.maximum(entry, 0)], 0),
            state.max_level),
        n_live=state.n_live - was_live.astype(jnp.int32),
        n_delete_noops=state.n_delete_noops
        + (~was_live).astype(jnp.int32))
    stats = IOStats(
        n_adj=jnp.where(was_live, 1 + cfg.M, 0).astype(jnp.int32),
        n_vec=jnp.where(was_live, n_vec, 0),
        n_filtered=jnp.zeros((), jnp.int32),
        n_hops=jnp.zeros((), jnp.int32))
    return state, stats


# ---------------------------------------------------------------------------
# lazy deletion + background consolidation (DESIGN.md §9)
# ---------------------------------------------------------------------------

def tombstone_batch(cfg: HNSWConfig, state: HNSWState,
                    ids: jax.Array) -> Tuple[HNSWState, IOStats]:
    """Phase-1 lazy delete: mark `ids` tombstoned in one scatter.

    No graph or LSM writes at all — the nodes keep their adjacency rows
    and stay *routable* (traversal expands through them, so routes
    crossing deleted regions survive), but the returnable mask hides
    them from every result heap.  Slots are reclaimed later by
    `consolidate` (FreshDiskANN's delete-list recipe).

    Negative ids are masked no-ops (the pad-and-mask serving contract).
    Non-negative ids that are absent, already tombstoned, or duplicated
    within the batch are counted in `n_delete_noops` and change nothing.
    """
    ids = jnp.asarray(ids, jnp.int32)
    valid = (ids >= 0) & (ids < cfg.cap)
    safe = jnp.clip(ids, 0, cfg.cap - 1)
    # within-batch duplicates: only the first occurrence applies (the
    # tombstone lane is read once, before any write of this batch)
    eq = (safe[None, :] == safe[:, None]) & valid[None, :]
    first = ~jnp.any(jnp.tril(eq, k=-1), axis=1)
    applies = valid & first & (state.levels[safe] >= 0) \
        & ~state.tombstone[safe]
    n_new = jnp.sum(applies).astype(jnp.int32)
    # masked lanes scatter to the out-of-bounds id `cap` and are dropped,
    # the same idiom as insert_batch's masked writes
    idx_w = jnp.where(applies, safe, cfg.cap)
    tomb = state.tombstone.at[idx_w].set(True, mode="drop")
    state = state._replace(
        tombstone=tomb,
        n_tombstones=state.n_tombstones + n_new,
        n_live=state.n_live - n_new,
        n_delete_noops=state.n_delete_noops
        + jnp.sum((ids >= 0) & ~applies).astype(jnp.int32))
    return state, IOStats.zero()


def _diversity_block(vectors: jax.Array, cand: jax.Array, d: jax.Array,
                     m: int, alpha: float = 1.0) -> jax.Array:
    """Blocked keepPruned diversity selection: `_diversity_topm` over a
    [b, C] candidate block, with the pairwise matrix built by matmul
    (norms + cv@cv^T) instead of the [b, C, C, dim] difference broadcast,
    which would not fit at consolidation block sizes.  `d` must already
    be +inf for duplicate/invalid candidates."""
    b, C = cand.shape
    order = jnp.argsort(d, axis=1, stable=True)
    ids_s = jnp.take_along_axis(cand, order, axis=1)
    d_s = jnp.take_along_axis(d, order, axis=1)
    cv = vectors[jnp.maximum(ids_s, 0)]                   # [b, C, dim]
    n2 = jnp.sum(cv * cv, axis=-1)
    pair = n2[:, :, None] + n2[:, None, :] \
        - 2.0 * jnp.einsum("bcd,bed->bce", cv, cv,
                           precision=jax.lax.Precision.HIGHEST)
    valid = jnp.isfinite(d_s) & (ids_s >= 0)

    def body(i, kept):
        dominated = jnp.any(
            kept & (alpha * pair[:, i, :] < d_s[:, i][:, None]), axis=1)
        space = jnp.sum(kept, axis=1) < m
        return kept.at[:, i].set(valid[:, i] & (~dominated) & space)

    kept = jax.lax.fori_loop(0, C, body, jnp.zeros((b, C), jnp.bool_))
    rank = jnp.argsort(~kept, axis=1, stable=True)  # kept first, dist order
    ids_r = jnp.take_along_axis(ids_s, rank, axis=1)[:, :m]
    valid_r = jnp.take_along_axis(valid, rank, axis=1)[:, :m]
    return jnp.where(valid_r, ids_r, -1)


def _consolidate_rows(vectors: jax.Array, adj: jax.Array, tomb: jax.Array,
                      owner: jax.Array, member: jax.Array, W: int,
                      block: int):
    """Graph-wide batched splice: for every `owner` node whose row holds
    tombstoned neighbors, rebuild the row from the row itself plus the
    tombstoned neighbors' out-neighbors (their 2-hop bridge), selecting
    `member` targets under the diversity rule — FreshDiskANN's
    RobustPrune step.  Plain closest-W splicing measurably halves
    post-consolidation QPS: it fills repaired rows with cluster-local
    edges and strips the long-range portals the beam navigates by.

    `adj` is a dense view int32[cap, W]; `owner` masks which rows may be
    rewritten, `member` which ids are valid targets.  Only the rows that
    change are repaired: their ids are packed to the front of one
    cap-long list and taken `block` at a time, so the [block, W + W*W,
    dim] distance gather never materializes at full cap and the work
    scales with the rows that touch a tombstone, not with cap.  Returns
    (new_adj, changed, n_dist) where rows with no tombstoned neighbor
    come back untouched.
    """
    cap = adj.shape[0]
    changed = owner & jnp.any((adj >= 0) & tomb[jnp.maximum(adj, 0)],
                              axis=1)
    n_changed = jnp.sum(changed).astype(jnp.int32)
    order = jnp.nonzero(changed, size=cap + block, fill_value=cap)[0]

    def repair(i, carry):
        new_adj, n_dist = carry
        blk = jax.lax.dynamic_slice(order, (i * block,), (block,))
        in_range = blk < cap
        safe_blk = jnp.minimum(blk, cap - 1)
        r = adj[safe_blk]                                    # [b, W]
        rs = jnp.maximum(r, 0)
        parent_tomb = (r >= 0) & tomb[rs]                    # [b, W]
        # out-neighbors of tombstoned neighbors only: live neighbors'
        # rows are not part of the FreshDiskANN splice pool
        exp = adj[rs].reshape(block, W * W)
        exp_ok = jnp.repeat(parent_tomb, W, axis=1)
        cand = jnp.concatenate([r, jnp.where(exp_ok, exp, -1)], axis=1)
        cs = jnp.maximum(cand, 0)
        bad = (cand < 0) | (cand == blk[:, None]) | ~member[cs]
        d = jnp.sum((vectors[cs]
                     - vectors[safe_blk][:, None, :]) ** 2, axis=-1)
        d = jnp.where(bad, INF, d)
        masked = jnp.where(bad, -1, cand)
        d = jax.vmap(_dedup_to_inf)(masked, d)
        new_r = _diversity_block(vectors, cand, d, W)
        n_dist = n_dist + jnp.sum(
            jnp.where(in_range[:, None], jnp.isfinite(d), False))
        # padding entries (id cap) scatter out of bounds and are dropped
        return new_adj.at[blk].set(new_r, mode="drop"), n_dist

    new_adj, n_dist = jax.lax.fori_loop(
        0, (n_changed + block - 1) // block, repair,
        (adj, jnp.zeros((), jnp.int32)))
    return new_adj, changed, n_dist


def consolidate(cfg: HNSWConfig, state: HNSWState, *,
                block: int = 256) -> Tuple[HNSWState, IOStats]:
    """Phase-2 lazy delete: splice every tombstone out and reclaim slots.

    The StreamingMerge-style batched repair (FreshDiskANN §4): resolve
    the bottom layer into a dense view once, rewrite every live row that
    touches a tombstone (splicing in the tombstones' out-neighbors under
    the relink rule), do the same for the memory-resident upper layers,
    then emit the surviving rows as one fresh sorted LSM run
    (`lsm.rebuild_from_dense`) — tombstoned ids simply do not appear in
    the rebuilt store, which is the slot reclamation.  Internal ids are
    never reused (allocation stays monotonic), so a serving layer's
    external↔internal map needs no rewrite: entries of reclaimed ids
    become permanently inert (see `serve`, DESIGN.md §9).

    Safe to call with zero tombstones (no row changes, store rewrite
    only).  Entry repair runs when the entry node itself is reclaimed.
    """
    live8, rows = lsm.resolve_all(cfg.lsm_cfg, state.store, cfg.cap)
    tomb = state.tombstone
    routable = state.levels >= 0
    keep = routable & ~tomb
    rows = jnp.where((routable & (live8 > 0))[:, None], rows, -1)

    new_rows, changed, n_dist = _consolidate_rows(
        state.vectors, rows, tomb, keep, keep, cfg.M, block)
    store = lsm.rebuild_from_dense(cfg.lsm_cfg, state.store, keep, new_rows)

    uppers = []
    for u in range(cfg.num_upper):
        member_u = keep & (state.levels > u)
        new_u, _, n_dist_u = _consolidate_rows(
            state.vectors, state.upper_adj[u], tomb, member_u, member_u,
            cfg.M_up, block)
        # reclaimed nodes lose their upper rows outright
        uppers.append(jnp.where(tomb[:, None], -1, new_u))
        n_dist = n_dist + n_dist_u
    upper_adj = jnp.stack(uppers)

    n_reclaimed = state.n_tombstones
    levels = jnp.where(tomb, -1, state.levels)
    entry_dead = (state.entry >= 0) & tomb[jnp.maximum(state.entry, 0)]
    alt = jnp.argmax(levels).astype(jnp.int32)
    entry = jnp.where(entry_dead, alt, state.entry)
    state = state._replace(
        store=store,
        upper_adj=upper_adj,
        levels=levels,
        entry=entry,
        max_level=jnp.maximum(levels[jnp.maximum(entry, 0)], 0),
        # repaired rows changed slot alignment; their heat restarts
        heat=jnp.where((tomb | changed)[:, None], 0, state.heat),
        tombstone=jnp.zeros_like(tomb),
        n_tombstones=jnp.zeros((), jnp.int32),
        # reclaimed slots leave the tier: back to the (empty) hot lane so
        # per-lane byte accounting never counts dead ids as cold rows
        hot=jnp.where(tomb, True, state.hot),
        qscale=jnp.where(tomb, 0.0, state.qscale),
        tier_heat=jnp.where(tomb, 0.0, state.tier_heat))
    stats = IOStats(
        n_adj=((1 + cfg.M) * n_reclaimed
               + jnp.sum(changed).astype(jnp.int32)),
        n_vec=n_dist,
        n_filtered=jnp.zeros((), jnp.int32),
        n_hops=jnp.zeros((), jnp.int32))
    return state, stats


# ---------------------------------------------------------------------------
# bulk construction (initial index build)
# ---------------------------------------------------------------------------

def _bucket(n: int) -> int:
    """Pad width of the build's candidate blocks: the next power of two,
    at least 128.  Each distinct width is one compiled program, so
    loading compiles O(log n) programs instead of one per batch."""
    return max(128, 1 << max(n - 1, 0).bit_length())


@functools.partial(jax.jit, static_argnames=("batch", "width", "kk"))
def _nearest_placed(arrived, start, n_placed, *, batch, width, kk):
    """The `kk` nearest placed nodes of each row of one arrival chunk.

    `arrived` holds the layer's vectors in arrival order (zero rows past
    the end), so the placed set is always the prefix `[:n_placed]`: the
    chunk `arrived[start:start + batch]` is scored against the first
    `width` rows, columns past `n_placed` are masked to +inf, and the
    top `kk` come back as (distance, arrival position) pairs — ascending
    by distance, lowest position first on ties."""
    chunk = jax.lax.dynamic_slice_in_dim(arrived, start, batch)
    d = l2_distance(chunk, arrived[:width])
    d = jnp.where(jnp.arange(width) < n_placed, d, INF)
    neg, pos = jax.lax.top_k(-d, kk)
    return -neg, pos


@functools.partial(jax.jit, donate_argnums=0)
def _link_chunk(rows, vecs, order, start, n_chunk, n_cand, cand_d, cand_p):
    """Link one arrival chunk into a layer's adjacency `rows` (node ids,
    int32[n_total, deg], -1 = empty).

    Row b of the chunk is node `order[start + b]` (rows b >= n_chunk are
    padding).  Its neighbors are diversity-selected (HNSW's keepPruned
    heuristic) among its first `n_cand` candidates `cand_p` (arrival
    positions, nearest first): a candidate is kept unless an already
    kept one is closer to it than it is to the node, and the pruned ones
    fill what is left of the row.  Then, node by node in arrival order,
    each neighbor takes a back-edge: its first free slot, or the slot of
    its edge most redundant w.r.t. the newcomer.
    """
    n_total, deg = rows.shape
    b, c = cand_p.shape
    nodes = order[start + jnp.arange(b)]
    live = jnp.arange(b) < n_chunk
    valid = jnp.broadcast_to(jnp.arange(c) < n_cand, (b, c))
    cand = order[cand_p]                                    # [b, c]
    cv = vecs[cand]                                         # [b, c, dim]
    n2 = jnp.sum(cv * cv, axis=-1)
    pair = n2[:, :, None] + n2[:, None, :] - 2.0 * jnp.einsum(
        "bcd,bed->bce", cv, cv, precision=jax.lax.Precision.HIGHEST)
    spares = pair >= cand_d[:, :, None]     # spares[r, i, j]: j spares i

    def keep(ci, carry):
        kept, n_kept = carry
        take = (~jnp.any(kept & ~spares[:, ci, :], axis=1)
                & (n_kept < deg) & valid[:, ci])
        return kept.at[:, ci].set(take), n_kept + take

    kept, _ = jax.lax.fori_loop(
        0, c, keep, (jnp.zeros((b, c), jnp.bool_), jnp.zeros((b,), jnp.int32)))
    # kept first, then pruned, each nearest first; invalid never
    rank = jnp.argsort(jnp.where(valid, (~kept).astype(jnp.int32), 2),
                       axis=1, stable=True)[:, :deg]
    nbrs = jnp.where(jnp.take_along_axis(valid, rank, axis=1)
                     & live[:, None],
                     jnp.take_along_axis(cand, rank, axis=1), -1)

    def link(r, rows):
        i, nb = nodes[r], nbrs[r]
        rows = rows.at[jnp.where(live[r], i, n_total)].set(nb, mode="drop")
        back = rows[jnp.maximum(nb, 0)]                     # [deg, deg]
        d_new = jnp.sum((vecs[jnp.maximum(back, 0)] - vecs[i]) ** 2, axis=-1)
        free = back < 0
        slot = jnp.where(jnp.any(free, axis=1), jnp.argmax(free, axis=1),
                         jnp.argmin(d_new, axis=1))
        return rows.at[jnp.where(nb >= 0, nb, n_total), slot].set(
            i, mode="drop")

    return jax.lax.fori_loop(0, b, link, rows)


def _incremental_graph(vecs, member_ids, deg: int, seed: int,
                       batch: int = 64):
    """Batched random-order incremental construction of one layer.

    Nodes arrive in random order and connect to a *diversity-selected* set
    among the already-placed nodes (HNSW's neighbor heuristic); back-edges
    evict the placed node's most redundant edge.  Early arrivals keep
    long-range links, which is exactly how incremental HNSW/NSW layers
    become navigable — an exact kNN graph would fall apart into per-cluster
    islands.  Runs on the device: the layer's vectors are gathered into
    arrival order once, each batch's exact nearest candidates come from
    `_nearest_placed` at a bucketed width, and `_link_chunk` links the
    batch into the adjacency, which stays on the device until the layer
    is done — the host only dispatches.  `vecs` is f32[n_total, dim].
    """
    import numpy as np
    n_total = vecs.shape[0]
    ids = np.asarray(member_ids)
    if ids.size == 0:
        return np.full((n_total, deg), -1, np.int32)
    rng = np.random.default_rng(seed)
    order = ids[rng.permutation(ids.size)]
    # geometric batch ramp: early nodes (the long-range hubs) must connect
    # densely to each other, not just to the seed
    bounds = [1]
    step = 1
    while bounds[-1] < order.size:
        bounds.append(min(bounds[-1] + step, order.size))
        step = min(batch, step * 2)
    # candidate pool for diversity.  Very small builds (tiny shards,
    # sparse upper layers) see the *complete* placed set: with only a
    # few dozen nodes the 2*deg nearest candidates all sit inside one
    # tight cluster and diversity selection can strand other clusters
    # entirely (the small-shard navigability loss).
    small = ids.size <= max(128, 4 * deg)
    # every layer pads to the full build's row count, so layers share
    # their programs: one per (bucketed width, cut)
    n_rows = _bucket(n_total) + batch
    pos = np.zeros(n_rows, np.int32)
    pos[:order.size] = order
    pos = jnp.asarray(pos)
    arrived = jnp.where((jnp.arange(n_rows) < order.size)[:, None],
                        vecs[pos], 0.0)
    rows = jnp.full((n_total, deg), -1, jnp.int32)
    for s, e in zip(bounds[:-1], bounds[1:]):
        width = _bucket(s)
        cand_d, cand_p = _nearest_placed(
            arrived, s, s, batch=batch, width=width,
            kk=width if small else min(2 * deg, width))
        rows = _link_chunk(rows, vecs, pos, s, e - s,
                           s if small else min(2 * deg, s), cand_d, cand_p)
    return np.array(rows)     # writable: reachability repair edits it


def _repair_reachability(rows, vecs_np, member_ids, entry: int, deg: int):
    """Guarantee every member is reachable from `entry` over `rows`.

    Diversity selection on clustered data can leave whole clusters as
    graph islands (no inbound path from the entry chain), which beam
    search then never finds no matter the ef.  Repair: while any member
    is unreachable, bridge the globally closest (reachable, unreachable)
    pair with a bidirectional edge — each bridge absorbs everything
    reachable from the bridged node.  Bridge edges are *protected*: a
    full row evicts its unprotected slot most redundant w.r.t. the new
    neighbor (the `_backlink_rows` rule), never an earlier bridge — two
    islands sharing one anchor would otherwise evict each other's bridge
    forever.  Anchors with no evictable slot are skipped.

    Each unreachable node's nearest anchor is kept and only updated
    against the anchors a bridge adds (and recomputed when its anchor
    runs out of evictable slots), so a layer with many islands costs one
    blocked nearest-anchor pass plus small updates, not a full pass per
    bridge.  An eviction can cut a path found earlier, so a full BFS
    from the entry re-checks the layer after every round; the rounds
    are bounded by the member count, so repair always terminates.
    """
    import numpy as np
    members = np.asarray(member_ids)
    if members.size <= 1:
        return rows
    in_layer = np.zeros(rows.shape[0], bool)
    in_layer[members] = True
    protected = np.zeros(rows.shape, bool)
    sq = np.zeros(rows.shape[0], np.float32)
    sq[members] = (vecs_np[members] ** 2).sum(1)

    def absorb(start: int, seen):
        """Mark everything reachable from `start`; returns the new nodes."""
        seen[start] = True
        frontier = np.asarray([start])
        found = [frontier]
        while frontier.size:
            nxt = rows[frontier].ravel()
            nxt = np.unique(nxt[nxt >= 0])
            nxt = nxt[in_layer[nxt] & ~seen[nxt]]
            seen[nxt] = True
            found.append(nxt)
            frontier = nxt
        return np.concatenate(found)

    def evictable(nodes):
        return ((rows[nodes] < 0) | ~protected[nodes]).any(axis=1)

    def nearest(us, anchors):
        """(squared distance, id) of each node's nearest anchor."""
        best_d = np.full(us.size, np.inf, np.float32)
        best_a = np.full(us.size, -1, np.int64)
        for s in range(0, anchors.size, 8192):
            blk = anchors[s:s + 8192]
            d = (sq[us][:, None] + sq[blk][None, :]
                 - 2.0 * (vecs_np[us] @ vecs_np[blk].T))
            j = np.argmin(d, axis=1)
            dj = d[np.arange(us.size), j]
            better = dj < best_d
            best_d[better] = dj[better]
            best_a[better] = blk[j[better]]
        return best_d, best_a

    def add_edge(src: int, dst: int):
        if dst in rows[src]:
            j = int(np.flatnonzero(rows[src] == dst)[0])
            protected[src, j] = True
            return
        free = np.flatnonzero(rows[src] < 0)
        if free.size:
            j = int(free[0])
        else:
            cand = np.flatnonzero(~protected[src])
            if cand.size == 0:
                return      # row is all bridges; caller skips such anchors
            nbr = vecs_np[rows[src, cand]]
            j = int(cand[np.argmin(((nbr - vecs_np[dst]) ** 2).sum(1))])
        rows[src, j] = dst
        protected[src, j] = True

    for _ in range(members.size):
        seen = np.zeros(rows.shape[0], bool)
        absorb(entry, seen)
        un = members[~seen[members]]
        if un.size == 0:
            break
        reach = members[seen[members]]
        best_d, best_a = nearest(un, reach[evictable(reach)])
        while un.size and np.isfinite(best_d).any():
            j = int(np.argmin(best_d))
            u_node, r_node = int(un[j]), int(best_a[j])
            add_edge(r_node, u_node)
            add_edge(u_node, r_node)
            new = absorb(u_node, seen)
            keep = ~seen[un]
            un, best_d, best_a = un[keep], best_d[keep], best_a[keep]
            if not un.size:
                break
            fresh = new[evictable(new)]
            if fresh.size:
                d2, a2 = nearest(un, fresh)
                better = d2 < best_d
                best_d[better] = d2[better]
                best_a[better] = a2[better]
            stale = (best_a >= 0) & ~evictable(np.maximum(best_a, 0))
            if stale.any():
                reach = members[seen[members]]
                best_d[stale], best_a[stale] = nearest(
                    un[stale], reach[evictable(reach)])
        if un.size and not np.isfinite(best_d).any():
            break           # no anchor can take a bridge
    return rows


def bulk_build(cfg: HNSWConfig, vectors: jax.Array, key: jax.Array,
               *, batch: int = 64) -> HNSWState:
    """Initial index build: batched incremental construction per layer.

    Semantically this is Algorithm 1 run over a random insertion order with
    exact (brute-force) neighbor search instead of beam search — the graph
    the paper's insert procedure converges to, built at matmul speed.  The
    bottom layer is written into the LSM tree as one sorted run (the
    offline "build one big level" path); dynamic updates afterwards always
    go through insert()/delete().
    """
    import numpy as np
    n, dim = vectors.shape
    assert n <= cfg.cap and dim == cfg.dim
    k_init, k_lvl = jax.random.split(key)
    state = init(cfg, k_init)

    vecs = jnp.asarray(vectors, jnp.float32)
    vecs_np = np.asarray(vecs)
    norms = jnp.linalg.norm(vecs, axis=1)
    codes = simhash.encode(simhash.SimHashParams(state.proj), vecs)
    lvls_np = np.minimum(
        np.floor(-cfg.level_scale * np.log(np.asarray(jax.random.uniform(
            k_lvl, (n,), jnp.float32, 1e-7, 1.0)))).astype(np.int32),
        cfg.num_upper)
    lvls_np[0] = cfg.num_upper   # stable entry chain
    ids = jnp.arange(n, dtype=jnp.int32)

    # entry = node 0 (forced to the top level above); every layer repairs
    # reachability from it so no cluster is stranded as a graph island
    bottom = _incremental_graph(vecs, np.arange(n), cfg.M, seed=0,
                                batch=batch)
    bottom = _repair_reachability(bottom, vecs_np, np.arange(n), 0, cfg.M)
    store = lsm.bulk_load(cfg.lsm_cfg, ids, jnp.asarray(bottom))

    upper = jnp.full((cfg.num_upper, cfg.cap, cfg.M_up), -1, jnp.int32)
    for u in range(cfg.num_upper):
        members = np.flatnonzero(lvls_np > u)
        rows_u = _incremental_graph(vecs, members, cfg.M_up, seed=u + 1,
                                    batch=batch)
        rows_u = _repair_reachability(rows_u, vecs_np, members, 0, cfg.M_up)
        upper = upper.at[u, :n].set(jnp.asarray(rows_u))

    lvls = jnp.asarray(lvls_np)
    entry = jnp.argmax(lvls).astype(jnp.int32)
    return state._replace(
        vectors=state.vectors.at[:n].set(vecs),
        norms=state.norms.at[:n].set(norms),
        codes=state.codes.at[:n].set(codes),
        levels=state.levels.at[:n].set(lvls),
        upper_adj=upper,
        store=store,
        count=jnp.asarray(n, jnp.int32),
        n_live=jnp.asarray(n, jnp.int32),
        entry=entry,
        max_level=lvls[entry],
        mean_norm=jnp.mean(norms))


# ---------------------------------------------------------------------------
# memory accounting (paper Fig. 6 — what must stay RAM-resident)
# ---------------------------------------------------------------------------

def memory_counts(state: HNSWState) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Device-side (n_routable, n_hot, n_upper) for the byte model."""
    routable = state.levels >= 0
    n_routable = jnp.sum(routable)
    n_hot = jnp.sum(routable & state.hot)
    n_upper = jnp.sum(state.levels > 0)
    return n_routable, n_hot, n_upper


def memory_breakdown(cfg: HNSWConfig, state: HNSWState,
                     counts=None) -> MemoryBreakdown:
    """Per-component resident bytes (DESIGN.md §12).

    The serving-vector lanes: with tiering off every routable node keeps
    its dense f32 row resident (the dense baseline the paper's Fig. 6
    argues against); with tiering on only hot-lane nodes do, and cold
    nodes cost ``dim + 4`` bytes (int8 row + f32 scale).  The bottom
    adjacency graph stays on "disk" (the LSM tree) in both modes —
    DiskANN-style systems keeping the *graph* in RAM during updates is
    the other half of the paper's 66.2% claim.

    Components the pre-tier accounting omitted are now counted: the
    tombstone bitmap, the insert-overlay staging buffers, and the
    ext↔int id maps a serving layer holds 1:1 with backend capacity.
    `counts` lets a caller pass pre-fetched host values of
    `memory_counts` to avoid a device sync.
    """
    if counts is None:
        counts = memory_counts(state)
    # one fused fetch instead of three scalar unboxings; stats() passes
    # pre-fetched host counts so the serve path never reaches the device
    n_routable, n_hot, n_upper = map(
        int, jax.device_get(counts))  # sync-ok: fused accounting fetch
    n_cold = n_routable - n_hot
    if not cfg.tier:
        n_hot, n_cold = n_routable, 0
    return MemoryBreakdown(
        hot_vectors=n_hot * cfg.dim * 4,
        cold_codes=n_cold * (cfg.dim + 4),
        upper_graph=n_upper * cfg.M_up * 4 * cfg.num_upper,
        upper_vec_cache=n_upper * cfg.dim * 4,
        simhash_codes=n_routable * cfg.words * 4,
        memtable=cfg.lsm_cfg.mem_cap * (4 + 4 * cfg.M + 1),
        tombstones=cfg.cap,
        insert_overlay=(cfg.cap + 1) * (4 * cfg.M + 1),
        id_maps=2 * cfg.cap * 8,
        misc=4096,
        n_hot=n_hot,
        n_cold=n_cold)


def memory_resident_bytes(cfg: HNSWConfig, state: HNSWState) -> int:
    """Total resident bytes: `memory_breakdown(...).total` (host int)."""
    return memory_breakdown(cfg, state).total
