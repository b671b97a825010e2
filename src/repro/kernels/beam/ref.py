"""Pure-JAX reference oracle for the fused beam-search megakernel.

`beam_search_ref` runs the *entire* bottom-layer beam search for a block
of queries as one fused JAX computation over dense operands.  It is the
semantics contract for `kernel.py`'s Pallas megakernel and the CPU /
interpret-host fallback that `ops.fused_beam_search` dispatches to.

It mirrors `repro.core.traversal.beam_search` step for step (same loop
trip structure, same merge order and tie-breaks, which `top_k` gives here
and a payload sort there, same SimHash/Hoeffding filter and
sampling-rank math), specialized to the serving path's dense operands:

 - adjacency comes from a resolved snapshot (`lsm.snapshot_rows` view),
   i.e. `_snapshot_adj_fn` semantics — one gather per popped row,
   ``n_probes = 1`` per active expansion;
 - distances come from the dense vector table through the fused
   `gather_l2` kernel family (hot lane) and, under ``tier``, the int8
   cold lane merged by elementwise min — `_dist_fn` / `_tier_dist_fn`
   semantics, including the exact +inf non-owning-lane masking;
 - the SimHash collision / Hoeffding-threshold math is inlined (the
   kernels package must not import `repro.core`; the parity suite in
   `tests/test_beam_kernel.py` pins this transcription against
   `repro.core.simhash`).

Bit-parity with the `while_loop` path at every config point the suite
exercises (lazy deletes, tier, ``n_expand`` > 1, masked pad lanes) is
the whole point of this module.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.gather_l2.ops import gather_l2, gather_l2_q8

INF = jnp.inf


def _rank_desc(score: jax.Array) -> jax.Array:
    """rank[i] = position of i when sorting score descending (stable).

    Same double-stable-argsort as `traversal._rank_desc` — the sampling
    cap must pick the identical rho-prefix.
    """
    order = jnp.argsort(-score, stable=True)
    return jnp.argsort(order, stable=True)


def _collisions(code_q: jax.Array, codes_u: jax.Array,
                m_bits: int) -> jax.Array:
    """#Col(q, u) per Eq. 5 — transcribed from `repro.core.simhash`."""
    ham = jnp.sum(jax.lax.population_count(code_q[None, :] ^ codes_u),
                  axis=-1)
    return (m_bits - ham).astype(jnp.int32)


def _hoeffding_threshold(m_bits: int, eps: float, delta_sq: jax.Array,
                         q_norm: jax.Array,
                         mean_norm: jax.Array) -> jax.Array:
    """T_eps for the dynamic delta (Eq. 6) — transcribed from
    `simhash.cos_from_l2` + `simhash.hoeffding_threshold`."""
    denom = jnp.maximum(2.0 * q_norm * mean_norm, 1e-12)
    cos = jnp.clip((q_norm ** 2 + mean_norm ** 2 - delta_sq) / denom,
                   -1.0, 1.0)
    theta = jnp.arccos(jnp.clip(cos, -1.0, 1.0))
    p = 1.0 - theta / jnp.pi
    slack = math.sqrt(m_bits * math.log(1.0 / eps) / 2.0)
    return p * m_bits - slack


def beam_iter_cap(max_iters: int, n_expand: int, ef: int) -> int:
    """Trip cap shared with `traversal.beam_search` (heat arrays are
    sized by it, so callers on either path see identical shapes)."""
    b = max(1, min(n_expand, ef))
    return min(max_iters, -(-max_iters // b) + 3)


def _beam_one(q, entry, entry_dist, code_q, q_norm, act,
              adjacency, vectors, codes, live, returnable, resident,
              qvecs, qscale, mean_norm, *, ef, k, m_bits, eps, rho,
              max_iters, use_filter, n_expand, has_active, record_heat):
    """Single-query transcription of `traversal.beam_search` over dense
    operands.  vmapped over the query block by `beam_search_ref`."""
    cap, M = adjacency.shape
    B = max(1, min(n_expand, ef))
    iter_cap = beam_iter_cap(max_iters, n_expand, ef)
    heat_len = iter_cap
    tier = resident is not None

    def dist_fn(ids):
        if not tier:
            return gather_l2(q[None, :], vectors, ids[None, :])[0]
        res = resident[jnp.maximum(ids, 0)]
        hot_ids = jnp.where((ids >= 0) & res, ids, -1)
        cold_ids = jnp.where((ids >= 0) & ~res, ids, -1)
        d_hot = gather_l2(q[None, :], vectors, hot_ids[None, :])[0]
        d_cold = gather_l2_q8(q[None, :], qvecs, qscale,
                              cold_ids[None, :])[0]
        return jnp.minimum(d_hot, d_cold)

    if not has_active:
        entry_n_vec = jnp.ones((), jnp.int32)
    else:
        entry_dist = jnp.where(act, entry_dist, INF)
        entry = jnp.where(act, entry, -1)
        entry_n_vec = jnp.asarray(act, jnp.int32)
    beam_ids = jnp.full((ef,), -1, jnp.int32).at[0].set(entry)
    beam_d = jnp.full((ef,), INF, jnp.float32).at[0].set(entry_dist)
    expanded = jnp.zeros((ef,), jnp.bool_)
    visited = jnp.zeros((cap + 1,), jnp.bool_).at[
        jnp.maximum(entry, 0)].set(entry >= 0)
    n_adj = jnp.zeros((), jnp.int32)
    n_vec = entry_n_vec
    n_filtered = jnp.zeros((), jnp.int32)
    n_hops = jnp.zeros((), jnp.int32)
    if record_heat:
        heat_nodes = jnp.full((heat_len, B), -1, jnp.int32)
        heat_mask = jnp.zeros((heat_len, B, M), jnp.bool_)
    else:
        heat_nodes = jnp.zeros((), jnp.int32)
        heat_mask = jnp.zeros((), jnp.bool_)

    fidx = ef - 1   # classic HNSW frontier cut, as in traversal

    def cond(carry):
        it, beam_ids, beam_d, expanded, _, _, _, _, hops, *_ = carry
        thresh = beam_d[fidx]
        frontier = (~expanded) & jnp.isfinite(beam_d) & (beam_d <= thresh)
        return (it < iter_cap) & (hops < max_iters) & jnp.any(frontier)

    def body(carry):
        (it, beam_ids, beam_d, expanded, visited,
         n_adj, n_vec, n_filtered, n_hops, heat_nodes, heat_mask) = carry

        frontier_d = jnp.where(expanded, INF, beam_d)
        thresh = beam_d[fidx]
        if B == 1:
            slots = jnp.argmin(frontier_d)[None]
        else:
            _, slots = jax.lax.top_k(-frontier_d, B)
        sel_d = frontier_d[slots]
        active = jnp.isfinite(sel_d) & (sel_d <= thresh)
        expanded = expanded.at[slots].set(expanded[slots] | active)
        nodes = jnp.where(active, beam_ids[slots], -1)

        # snapshot adjacency: one gather per row, n_probes = 1
        rows = adjacency[jnp.maximum(nodes, 0)]
        rows = jnp.where((nodes >= 0)[:, None], rows, -1)
        n_probes = jnp.ones_like(nodes)
        row = rows.reshape(B * M)
        valid = (row >= 0) & (row <= cap - 1)
        safe = jnp.where(valid, row, cap)
        seen = visited[safe]
        alive = jnp.where(valid, live[jnp.minimum(safe, cap - 1)], False)
        eligible = valid & (~seen) & alive
        if B > 1:
            eq = safe[None, :] == safe[:, None]
            earlier = jnp.tril(eq, k=-1)
            eligible = eligible & ~jnp.any(earlier, axis=1)

        cand_codes = codes[jnp.minimum(safe, cap - 1)]
        cols = _collisions(code_q, cand_codes, m_bits)
        delta_sq = beam_d[k - 1]
        if use_filter:
            thr = _hoeffding_threshold(m_bits, eps, delta_sq, q_norm,
                                       mean_norm)
            pass_thr = (cols.astype(jnp.float32) >= thr) \
                | ~jnp.isfinite(delta_sq)
        else:
            pass_thr = jnp.ones_like(eligible)
        pre_mask = eligible & pass_thr

        if isinstance(rho, (int, float)) and rho >= 1.0:
            fetch_mask = pre_mask
        else:
            score = jnp.where(pre_mask, cols, -1)
            rank = _rank_desc(score)
            n_elig = jnp.sum(pre_mask)
            cap_dyn = jnp.ceil(rho * n_elig).astype(jnp.int32)
            fetch_mask = pre_mask & (rank < cap_dyn)
        fetch_ids = jnp.where(fetch_mask, row, -1)

        dists = dist_fn(fetch_ids)

        visited = visited.at[jnp.where(fetch_mask, safe, cap)].set(True)
        n_fetch = jnp.sum(fetch_mask).astype(jnp.int32)
        n_adj = n_adj + jnp.sum(jnp.where(active, n_probes, 0))
        n_vec = n_vec + n_fetch
        n_filtered = n_filtered \
            + jnp.sum(eligible).astype(jnp.int32) - n_fetch
        n_hops = n_hops + jnp.sum(active).astype(jnp.int32)
        if record_heat:
            heat_nodes = heat_nodes.at[it].set(nodes)
            heat_mask = heat_mask.at[it].set(fetch_mask.reshape(B, M))

        all_ids = jnp.concatenate([beam_ids, fetch_ids])
        all_d = jnp.concatenate([beam_d, dists])
        all_exp = jnp.concatenate([expanded, jnp.ones((B * M,), jnp.bool_)])
        all_exp = all_exp.at[ef:].set(~fetch_mask)
        _, order = jax.lax.top_k(-all_d, ef)
        return (it + 1, all_ids[order], all_d[order], all_exp[order],
                visited, n_adj, n_vec, n_filtered, n_hops,
                heat_nodes, heat_mask)

    init = (jnp.int32(0), beam_ids, beam_d, expanded, visited,
            n_adj, n_vec, n_filtered, n_hops, heat_nodes, heat_mask)
    (_, beam_ids, beam_d, _, _, n_adj, n_vec, n_filtered, n_hops,
     heat_nodes, heat_mask) = jax.lax.while_loop(cond, body, init)
    if returnable is not None:
        ok = (beam_ids >= 0) & returnable[jnp.clip(beam_ids, 0, cap - 1)]
        beam_d = jnp.where(ok, beam_d, INF)
        neg_d, order = jax.lax.top_k(-beam_d, ef)
        beam_d = -neg_d
        beam_ids = jnp.where(jnp.isfinite(beam_d), beam_ids[order], -1)
    if record_heat:
        heat_nodes = heat_nodes.reshape(heat_len * B)
        heat_mask = heat_mask.reshape(heat_len * B, M)
    else:
        heat_nodes = jnp.full((heat_len * B,), -1, jnp.int32)
        heat_mask = jnp.zeros((heat_len * B, M), jnp.bool_)
    stats = jnp.stack([n_adj, n_vec, n_filtered, n_hops])
    return beam_ids, beam_d, stats, heat_nodes, heat_mask


def beam_search_ref(qs, entries, entry_dists, adjacency, vectors, codes,
                    code_qs, live, q_norms, mean_norm, *,
                    returnable=None, resident=None, qvecs=None,
                    qscale=None, active=None, ef, k, m_bits, eps, rho,
                    max_iters, use_filter, n_expand=1, record_heat=True):
    """Whole-block beam search over dense operands, one fused launch.

    qs f32[Bq, dim]; entries int32[Bq]; entry_dists f32[Bq];
    adjacency int32[cap, M] (resolved snapshot rows, -1 pads);
    vectors f32[cap, dim]; codes uint32[cap, W]; code_qs uint32[Bq, W];
    live bool[cap] (routable); q_norms f32[Bq]; mean_norm f32[].
    Optional lanes: `returnable` bool[cap] (lazy-delete repack),
    `resident`/`qvecs`/`qscale` (tier split), `active` bool[Bq]
    (pad-lane masking).  Returns
    ``(ids [Bq, ef], dists [Bq, ef], stats int32[Bq, 4],
    heat_nodes [Bq, heat_len*B], heat_mask [Bq, heat_len*B, M])``
    where the stats columns are (n_adj, n_vec, n_filtered, n_hops).
    """
    has_active = active is not None
    if active is None:
        active = jnp.ones(qs.shape[0], jnp.bool_)
    one = partial(
        _beam_one, adjacency=adjacency, vectors=vectors, codes=codes,
        live=live, returnable=returnable, resident=resident, qvecs=qvecs,
        qscale=qscale, mean_norm=mean_norm, ef=ef, k=k, m_bits=m_bits,
        eps=eps, rho=rho, max_iters=max_iters, use_filter=use_filter,
        n_expand=n_expand, has_active=has_active, record_heat=record_heat)
    return jax.vmap(one)(qs, entries, entry_dists, code_qs, q_norms,
                         active)
