"""Sampling-guided beam search over the hybrid memory/disk graph (§3.3).

The bottom-layer traversal is the paper's hot loop: repeatedly pop the
closest unexpanded candidate, read its adjacency row (from the LSM tree —
pays `t_n`), *prefilter* its neighbors with in-memory SimHash collision
counts (Eq. 5-6), and fetch full vectors only for survivors (pays `t_v`
each — Eq. 8's `rho * d` term).

Implementation notes (TPU adaptation — DESIGN.md §2):
 - The frontier is a fixed-size sorted beam (candidate set C and result set
   W of classic HNSW merged into one ef-wide array with `expanded` flags),
   so the whole search is a `jax.lax.while_loop` over static shapes and
   vmaps over a query batch.
 - `visited` is a bool[cap+1] array; masked scatter-writes land in the
   spare slot.
 - Edge-heat is recorded per hop as (node, fetched-mask) pairs so the
   caller can build the reordering heatmap (§3.4) without carrying a
   [cap, M] array through the loop.
 - Multi-expansion (DESIGN.md §3): `n_expand` (B) frontier nodes are
   popped per iteration, their adjacency rows are read through one
   batched LSM lookup, and the SimHash prefilter plus the fused
   gather+distance kernel run over the whole B*M candidate block before a
   single merge.  This cuts the `while_loop` trip count ~B× and makes
   each distance call wide enough to feed the MXU.  B=1 reproduces the
   classic one-node-per-hop search exactly.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import simhash
from repro.core.iostats import IOStats

INF = jnp.inf


class BeamResult(NamedTuple):
    ids: jax.Array       # int32[ef] — best ids found, ascending distance
    dists: jax.Array     # f32[ef]
    stats: IOStats
    # heat arrays have length iter_cap * n_expand, where iter_cap =
    # min(max_iters, ceil(max_iters / n_expand) + 3); for n_expand=1 that
    # is max_iters.  Callers reshape with (-1, ...), never a fixed size.
    heat_nodes: jax.Array   # int32[iter_cap * n_expand] — expanded nodes (-1 pad)
    heat_mask: jax.Array    # bool[iter_cap * n_expand, M] — fetched slots per hop


def _rank_desc(score: jax.Array) -> jax.Array:
    """rank[i] = position of i when sorting score descending (stable)."""
    order = jnp.argsort(-score, stable=True)
    return jnp.argsort(order, stable=True)


def beam_search(
    q: jax.Array,                    # f32[dim]
    entry: jax.Array,                # int32[] — entry node id
    entry_dist: jax.Array,           # f32[] — distance(q, entry)
    adj_fn: Callable,                # ids int32[B] -> (rows int32[B, M], probes int32[B])
    dist_fn: Callable,               # ids int32[n] -> f32[n] (inf for id<0)
    codes: jax.Array,                # uint32[cap, W] in-memory hash codes
    code_q: jax.Array,               # uint32[W]
    live: jax.Array,                 # bool[cap] — node liveness
    *,
    cap: int,
    ef: int,
    k: int,
    m_bits: int,
    eps: float,
    rho: float,                      # sampling ratio: fetch ceil(rho * |eligible|)
    max_iters: int,
    use_filter: bool,
    q_norm: jax.Array,               # f32[]
    mean_norm: jax.Array,            # f32[]
    n_expand: int = 1,               # B: frontier nodes expanded per iteration
    active: jax.Array | None = None,  # bool[] — False: inert (padded) lane
    returnable: jax.Array | None = None,  # bool[cap] — None: all of `live`
) -> BeamResult:
    """Single-query sampling-guided beam search.  vmap over queries.

    `adj_fn` is the *batched* adjacency reader: it takes the B popped node
    ids at once (-1 for inactive expansion slots, which must yield all -1
    rows) so the storage layer can serve the whole frontier block in one
    lookup (`lsm.get_batch`) instead of B point reads.

    `live` is the *routable* mask: nodes the traversal may fetch and
    expand through.  `returnable` (optional) is the stricter mask of
    nodes allowed in the final result list — the lazy-deletion contract
    (DESIGN.md §9): tombstoned nodes stay routable (their edges keep the
    graph connected and the beam expands through them at full cost) but
    are masked out of the returned heap after the loop.  None means
    every routable node is returnable (the classic eager behavior).

    `max_iters` budgets *expansions*, not loop trips: with B > 1 an
    iteration can pop fewer than B nodes when the frontier is thin (the
    first hops always are), so trip-count budgeting would starve wide
    beams.  The loop runs until the expansion budget or the frontier is
    exhausted; for B=1 expansions == iterations, the seed semantics.

    `active` supports pad-and-mask batch dispatch: a False lane never
    enters the loop (its entry distance is masked to +inf), returns all
    -1/inf results, records no heat, and contributes zero IOStats — under
    vmap it costs nothing beyond the trips its live siblings need.
    """
    B = max(1, min(n_expand, ef))
    M = adj_fn(jnp.zeros((B,), jnp.int32))[0].shape[1]
    # trip cap: budget/B trips suffice once the frontier is B wide, plus
    # slack for the thin ramp-up hops (the frontier grows ~M-fold per
    # trip, so 3 trips reach any B <= M^3).  Without the cap a single
    # thin-but-alive straggler would drag a vmapped batch through up to
    # `max_iters` trips.  B=1 keeps the exact seed cap.  Heat storage is
    # sized to the cap, so every trip records.
    iter_cap = min(max_iters, -(-max_iters // B) + 3)
    heat_len = iter_cap

    if active is None:
        entry_n_vec = jnp.ones((), jnp.int32)
    else:
        entry_dist = jnp.where(active, entry_dist, INF)
        entry = jnp.where(active, entry, -1)
        entry_n_vec = jnp.asarray(active, jnp.int32)
    beam_ids = jnp.full((ef,), -1, jnp.int32).at[0].set(entry)
    beam_d = jnp.full((ef,), INF, jnp.float32).at[0].set(entry_dist)
    expanded = jnp.zeros((ef,), jnp.bool_)
    visited = jnp.zeros((cap + 1,), jnp.bool_).at[jnp.maximum(entry, 0)].set(
        entry >= 0)
    heat_nodes = jnp.full((heat_len, B), -1, jnp.int32)
    heat_mask = jnp.zeros((heat_len, B, M), jnp.bool_)
    stats = IOStats.zero()
    # entry vector was fetched to compute entry_dist (not on masked lanes)
    stats = stats._replace(n_vec=stats.n_vec + entry_n_vec)

    # frontier threshold (classic HNSW): stop expanding once every
    # candidate within the ef-th best has been visited, so `ef` is the
    # recall knob.  A tighter cut (the k-th or 3k-th best) ignores every
    # ef past it and caps recall on dense clusters.
    fidx = ef - 1

    def cond(carry):
        it, beam_ids, beam_d, expanded, _, stats, *_ = carry
        thresh = beam_d[fidx]
        frontier = (~expanded) & jnp.isfinite(beam_d) & (beam_d <= thresh)
        return (it < iter_cap) & (stats.n_hops < max_iters) \
            & jnp.any(frontier)

    def body(carry):
        (it, beam_ids, beam_d, expanded, visited, stats,
         heat_nodes, heat_mask) = carry

        # -- pop the B closest unexpanded candidates -----------------------
        frontier_d = jnp.where(expanded, INF, beam_d)
        thresh = beam_d[fidx]
        if B == 1:
            slots = jnp.argmin(frontier_d)[None]
        else:
            # top_k, not a full sort: ties resolve to the lower slot, same
            # as the stable argmin pop
            _, slots = jax.lax.top_k(-frontier_d, B)
        sel_d = frontier_d[slots]
        # extras past the frontier threshold would never be expanded by the
        # B=1 loop (the threshold only tightens) — keep them inert
        active = jnp.isfinite(sel_d) & (sel_d <= thresh)
        expanded = expanded.at[slots].set(expanded[slots] | active)
        nodes = jnp.where(active, beam_ids[slots], -1)

        # -- batched adjacency read (t_n) ----------------------------------
        rows, n_probes = adj_fn(nodes)                  # [B, M], [B]
        row = rows.reshape(B * M)
        valid = (row >= 0) & (row <= cap - 1)
        safe = jnp.where(valid, row, cap)
        seen = visited[safe]
        alive = jnp.where(valid, live[jnp.minimum(safe, cap - 1)], False)
        eligible = valid & (~seen) & alive
        if B > 1:
            # duplicates across the B rows would enter the beam twice
            # (visited is only updated after the block): keep the first
            # occurrence of each id within the block.  An O((BM)^2)
            # comparison triangle beats sort+scatter at these widths.
            eq = safe[None, :] == safe[:, None]
            earlier = jnp.tril(eq, k=-1)
            eligible = eligible & ~jnp.any(earlier, axis=1)

        # -- SimHash prefilter (Eq. 5-6), in-memory, whole block -----------
        cand_codes = codes[jnp.minimum(safe, cap - 1)]
        cols = simhash.collisions(code_q[None, :], cand_codes, m_bits)
        delta_sq = beam_d[k - 1]
        if use_filter:
            cos = simhash.cos_from_l2(delta_sq, q_norm, mean_norm)
            thr = simhash.hoeffding_threshold(m_bits, eps, cos)
            pass_thr = (cols.astype(jnp.float32) >= thr) | ~jnp.isfinite(delta_sq)
        else:
            pass_thr = jnp.ones_like(eligible)
        pre_mask = eligible & pass_thr

        # -- sampling cap (Eq. 8): evaluate only rho of the survivors,
        #    keeping the most-colliding ones ------------------------------
        if isinstance(rho, (int, float)) and rho >= 1.0:
            # static fast path: everything eligible is fetched, so the two
            # ranking argsorts vanish from the loop body
            fetch_mask = pre_mask
        else:
            score = jnp.where(pre_mask, cols, -1)
            rank = _rank_desc(score)
            n_elig = jnp.sum(pre_mask)
            cap_dyn = jnp.ceil(rho * n_elig).astype(jnp.int32)
            fetch_mask = pre_mask & (rank < cap_dyn)
        fetch_ids = jnp.where(fetch_mask, row, -1)

        # -- one fused gather+distance call over the B*M block (t_v each) --
        dists = dist_fn(fetch_ids)

        # -- bookkeeping ----------------------------------------------------
        visited = visited.at[jnp.where(fetch_mask, safe, cap)].set(True)
        n_fetch = jnp.sum(fetch_mask).astype(jnp.int32)
        stats = IOStats(
            n_adj=stats.n_adj + jnp.sum(jnp.where(active, n_probes, 0)),
            n_vec=stats.n_vec + n_fetch,
            n_filtered=stats.n_filtered
            + jnp.sum(eligible).astype(jnp.int32) - n_fetch,
            n_hops=stats.n_hops + jnp.sum(active).astype(jnp.int32),
        )
        heat_nodes = heat_nodes.at[it].set(nodes)
        heat_mask = heat_mask.at[it].set(fetch_mask.reshape(B, M))

        # -- single merge of the whole block into the beam ------------------
        all_ids = jnp.concatenate([beam_ids, fetch_ids])
        all_d = jnp.concatenate([beam_d, dists])
        all_exp = jnp.concatenate([expanded, jnp.ones((B * M,), jnp.bool_)])
        # new candidates are unexpanded; mark masked ones expanded (inert)
        all_exp = all_exp.at[ef:].set(~fetch_mask)
        # top_k == stable argsort prefix here: ties prefer the lower index
        _, order = jax.lax.top_k(-all_d, ef)
        return (it + 1, all_ids[order], all_d[order], all_exp[order],
                visited, stats, heat_nodes, heat_mask)

    init = (jnp.int32(0), beam_ids, beam_d, expanded, visited, stats,
            heat_nodes, heat_mask)
    (_, beam_ids, beam_d, _, _, stats, heat_nodes, heat_mask) = \
        jax.lax.while_loop(cond, body, init)
    if returnable is not None:
        # routable-but-not-returnable entries (tombstones) are demoted to
        # +inf/-1 and the survivors re-packed to the front — one selection
        # outside the loop, so routing cost is identical with or without
        # tombstones in the beam
        ok = (beam_ids >= 0) & returnable[jnp.clip(beam_ids, 0, cap - 1)]
        beam_d = jnp.where(ok, beam_d, INF)
        neg_d, order = jax.lax.top_k(-beam_d, ef)
        beam_d = -neg_d
        beam_ids = jnp.where(jnp.isfinite(beam_d), beam_ids[order], -1)
    return BeamResult(beam_ids, beam_d, stats,
                      heat_nodes.reshape(heat_len * B),
                      heat_mask.reshape(heat_len * B, M))


def greedy_descent(
    q: jax.Array,
    entry: jax.Array,
    entry_dist: jax.Array,
    adj: jax.Array,                # int32[cap, M_up] — one upper layer
    vectors: jax.Array,            # f32[cap, dim]
    live: jax.Array,               # bool[cap]
    *,
    max_steps: int = 64,
) -> Tuple[jax.Array, jax.Array]:
    """Greedy routing in one memory-resident upper layer (Alg. 1 lines 6-8).

    Upper-layer nodes are <1% of the data and their vectors are cached in
    RAM (paper §3.2), so these reads cost no slow-tier I/O.
    """
    cap = adj.shape[0]

    def cond(c):
        step, _, _, moved = c
        return (step < max_steps) & moved

    def body(c):
        step, ep, d_ep, _ = c
        row = adj[ep]
        valid = (row >= 0) & live[jnp.clip(row, 0, cap - 1)]
        safe = jnp.clip(row, 0, cap - 1)
        diff = vectors[safe] - q[None, :]
        d = jnp.where(valid, jnp.sum(diff * diff, axis=-1), INF)
        j = jnp.argmin(d)
        better = d[j] < d_ep
        return (step + 1, jnp.where(better, row[j], ep),
                jnp.where(better, d[j], d_ep), better)

    _, ep, d_ep, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), entry, entry_dist, jnp.bool_(True)))
    return ep, d_ep
