"""Pallas TPU megakernel: the whole beam-search loop in one launch.

The per-hop `gather_l2` kernel already fuses fetch+distance for one
expansion block; the loop *around* it still round-trips the frontier
pop, heap merge and visited filter through XLA-generated sort/top-k ops
every trip (`traversal.beam_search`'s `while_loop`).  This kernel runs
the entire bottom-layer search for a query block in one launch — one
grid program per query:

 - the candidate heap (``ef`` slots: ids / distances / expanded flags)
   and the visited filter (``bool[cap+1]``, same spare-slot contract as
   the host loop) live in VMEM-resident loop carries across every
   expansion — they never touch HBM until the final result write;
 - adjacency rows and candidate vector rows stay in HBM (`pl.ANY`) and
   are gathered per trip with explicit `make_async_copy` DMAs into VMEM
   scratch — issue-all-then-wait, so the row fetches overlap like the
   scalar-prefetch pipeline in `gather_l2` (the ids are data-dependent
   on the heap state, so they cannot come from a prefetch operand);
 - SimHash codes, liveness/returnable/resident lanes and per-row cold
   scales are VMEM-resident tables (they are the "in-memory" half of
   the paper's hybrid layout);
 - the tier split fetches the f32 lane for resident rows and the int8
   lane (fused dequant) for cold rows, merged by elementwise min with
   +inf in the non-owning lane — `_tier_dist_fn` semantics.

Selection ops: Mosaic has no `top_k`/`argsort`, so every pop / merge /
repack uses stable *rank-by-comparison*: ``rank[i] = #{j: d[j] < d[i]}
+ #{j < i: d[j] == d[i]}`` — exactly the position a stable ascending
sort assigns, which is also exactly `lax.top_k`'s tie-break on ``-d``
(ties prefer the lower index).  Rank-selection therefore reproduces the
host loop's tie behavior identically; see DESIGN.md §15.

The `while_loop` becomes a `fori_loop` over the same static trip cap
with a monotone-false ``go`` carry: a trip whose continuation predicate
fails is a provable no-op (all updates are gated by the empty ``active``
set), so the fori/while results are bit-identical.

Dimensions must be padded to a lane multiple of 128 (`ops.py` pads;
zero pad lanes add exactly +0.0 to every squared distance).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INF = jnp.inf


def _ranks_asc(d: jax.Array) -> jax.Array:
    """Stable ascending rank of each element (ties -> lower index first).

    Equivalent to ``argsort(argsort(d, stable), stable)`` and to the
    index positions `lax.top_k(-d, n)` would emit — without a sort op.
    """
    n = d.shape[0]
    less = d[None, :] < d[:, None]
    eq = d[None, :] == d[:, None]
    col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    return jnp.sum((less | (eq & (col < row))).astype(jnp.int32), axis=1)


def _ranks_desc(s: jax.Array) -> jax.Array:
    """Stable descending rank — `traversal._rank_desc` without sorts."""
    n = s.shape[0]
    gt = s[None, :] > s[:, None]
    eq = s[None, :] == s[:, None]
    col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    return jnp.sum((gt | (eq & (col < row))).astype(jnp.int32), axis=1)


def _sel_matrix(ranks: jax.Array, m: int) -> jax.Array:
    """sel[s, i] = (ranks[i] == s) for s < m.  Ranks are a permutation,
    so each row has exactly one True — gathers become one-hot reduces."""
    n = ranks.shape[0]
    s = jax.lax.broadcasted_iota(jnp.int32, (m, n), 0)
    return s == ranks[None, :]


def _take(sel: jax.Array, a: jax.Array) -> jax.Array:
    """out[s] = a[i] where sel[s, i] — exact for ints, floats (inc. inf)
    and bools because each row of `sel` selects exactly one element."""
    if a.dtype == jnp.bool_:
        return jnp.any(sel & a[None, :], axis=1)
    return jnp.sum(jnp.where(sel, a[None, :], jnp.zeros_like(a)[None, :]),
                   axis=1)


def _gather_dma(table_ref, idxs: jax.Array, scratch, sems, n: int,
                sem_base: int):
    """DMA `n` data-dependent rows of `table_ref` (HBM) into `scratch`
    (VMEM): issue every copy, then wait — the issue-all window is what
    lets the DMA engine overlap row fetches across the block."""
    copies = []
    for j in range(n):
        c = pltpu.make_async_copy(table_ref.at[pl.ds(idxs[j], 1)],
                                  scratch.at[pl.ds(j, 1)],
                                  sems.at[sem_base + j])
        c.start()
        copies.append(c)
    for c in copies:
        c.wait()


def _onehot_cols(idxs: jax.Array, n_rows: int) -> jax.Array:
    """oh[c, j] = (idxs[j] == c) — VMEM-table gather as a masked reduce."""
    m = idxs.shape[0]
    c = jax.lax.broadcasted_iota(jnp.int32, (n_rows, m), 0)
    return c == idxs[None, :]


def _make_beam_kernel(*, B, M, ef, k, cap, dpad, W, iter_cap, max_iters,
                      m_bits, eps, rho, use_filter, tier, lazy,
                      record_heat):
    BM = B * M
    fidx = ef - 1   # classic HNSW frontier cut, as in traversal
    import math
    if use_filter:
        slack = math.sqrt(m_bits * math.log(1.0 / eps) / 2.0)

    def kernel(q_ref, entry_ref, entryd_ref, codeq_ref, qn_ref, act_ref,
               mn_ref, adj_ref, vec_ref, codes_ref, live_ref, ret_ref,
               *rest):
        if tier:
            res_ref, qvec_ref, qscale_ref = rest[:3]
            rest = rest[3:]
        ids_out, d_out, stats_out, heatn_out, heatm_out = rest[:5]
        scratch = rest[5:]
        if tier:
            adj_s, vec_s, qvec_s, sems = scratch
        else:
            adj_s, vec_s, sems = scratch

        q = q_ref[0, :]                                  # [dpad]
        entry = entry_ref[0, 0]
        entry_d = entryd_ref[0, 0]
        code_q = codeq_ref[0, :]                         # [W]
        q_norm = qn_ref[0, 0]
        mean_norm = mn_ref[0, 0]
        lane = act_ref[0, 0] != 0
        codes = codes_ref[...]                           # [cap, W]
        live = live_ref[..., 0] != 0                     # [cap]
        iota_cap1 = jax.lax.broadcasted_iota(
            jnp.int32, (cap + 1, 1), 0)[:, 0]

        # -- init: entry seeds slot 0; masked lanes never enter --------
        entry_d = jnp.where(lane, entry_d, INF)
        entry = jnp.where(lane, entry, -1)
        beam_ids = jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, (ef, 1), 0)[:, 0] == 0,
            entry, -1)
        beam_d = jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, (ef, 1), 0)[:, 0] == 0,
            entry_d, INF)
        expanded = jnp.zeros((ef,), jnp.bool_)
        visited = (iota_cap1 == jnp.maximum(entry, 0)) & (entry >= 0)
        n_adj = jnp.zeros((), jnp.int32)
        n_vec = lane.astype(jnp.int32)
        n_filt = jnp.zeros((), jnp.int32)
        n_hops = jnp.zeros((), jnp.int32)

        def trip(it, carry):
            if record_heat:
                (beam_ids, beam_d, expanded, visited,
                 n_adj, n_vec, n_filt, n_hops, go,
                 heat_nodes, heat_mask) = carry
            else:
                (beam_ids, beam_d, expanded, visited,
                 n_adj, n_vec, n_filt, n_hops, go) = carry

            # continuation predicate of the host while_loop; a False
            # trip zeroes `act` below and the whole body is a no-op
            thresh = beam_d[fidx]
            frontier = (~expanded) & jnp.isfinite(beam_d) \
                & (beam_d <= thresh)
            go = go & (n_hops < max_iters) & jnp.any(frontier)

            # -- pop the B closest unexpanded (stable rank select) ----
            frontier_d = jnp.where(expanded, INF, beam_d)
            ranks = _ranks_asc(frontier_d)
            sel = _sel_matrix(ranks, B)                  # [B, ef]
            sel_d = _take(sel, frontier_d)
            act = go & jnp.isfinite(sel_d) & (sel_d <= thresh)
            expanded = expanded | jnp.any(sel & act[:, None], axis=0)
            nodes = jnp.where(act, _take(sel, beam_ids), -1)

            # -- adjacency rows: B data-dependent DMAs from HBM -------
            _gather_dma(adj_ref, jnp.maximum(nodes, 0), adj_s, sems,
                        B, 0)
            rows = jnp.where((nodes >= 0)[:, None], adj_s[...], -1)
            row = rows.reshape(BM)
            valid = (row >= 0) & (row <= cap - 1)
            safe = jnp.where(valid, row, cap)
            oh1 = _onehot_cols(safe, cap + 1)            # [cap+1, BM]
            seen = jnp.any(visited[:, None] & oh1, axis=0)
            ohc = oh1[:cap, :]                           # [cap, BM]
            alive = jnp.where(valid,
                              jnp.any(live[:, None] & ohc, axis=0),
                              False)
            eligible = valid & (~seen) & alive
            if B > 1:
                eq = safe[None, :] == safe[:, None]
                colj = jax.lax.broadcasted_iota(jnp.int32, (BM, BM), 1)
                rowi = jax.lax.broadcasted_iota(jnp.int32, (BM, BM), 0)
                earlier = eq & (colj < rowi)
                eligible = eligible & ~jnp.any(earlier, axis=1)

            # -- SimHash prefilter from the VMEM code table -----------
            cand_codes = jnp.stack(
                [jnp.sum(jnp.where(ohc, codes[:, w][:, None],
                                   jnp.uint32(0)), axis=0)
                 for w in range(W)], axis=1)             # [BM, W]
            ham = jnp.sum(jax.lax.population_count(
                code_q[None, :] ^ cand_codes), axis=-1)
            cols = (m_bits - ham).astype(jnp.int32)
            delta_sq = beam_d[k - 1]
            if use_filter:
                denom = jnp.maximum(2.0 * q_norm * mean_norm, 1e-12)
                cos = jnp.clip(
                    (q_norm ** 2 + mean_norm ** 2 - delta_sq) / denom,
                    -1.0, 1.0)
                theta = jnp.arccos(jnp.clip(cos, -1.0, 1.0))
                thr = (1.0 - theta / jnp.pi) * m_bits - slack
                pass_thr = (cols.astype(jnp.float32) >= thr) \
                    | ~jnp.isfinite(delta_sq)
            else:
                pass_thr = jnp.ones_like(eligible)
            pre_mask = eligible & pass_thr

            if isinstance(rho, (int, float)) and rho >= 1.0:
                fetch_mask = pre_mask
            else:
                score = jnp.where(pre_mask, cols, -1)
                rank_s = _ranks_desc(score)
                n_elig = jnp.sum(pre_mask)
                cap_dyn = jnp.ceil(rho * n_elig).astype(jnp.int32)
                fetch_mask = pre_mask & (rank_s < cap_dyn)
            fetch_ids = jnp.where(fetch_mask, row, -1)

            # -- candidate vectors: BM data-dependent DMAs, fused L2 --
            if not tier:
                _gather_dma(vec_ref, jnp.maximum(fetch_ids, 0), vec_s,
                            sems, BM, 0)
                diff = q[None, :] - vec_s[...]
                dists = jnp.where(fetch_ids >= 0,
                                  jnp.sum(diff * diff, axis=1), INF)
            else:
                res = jnp.any((res_ref[..., 0] != 0)[:, None]
                              & _onehot_cols(jnp.maximum(fetch_ids, 0),
                                             cap), axis=0)
                hot_ids = jnp.where((fetch_ids >= 0) & res,
                                    fetch_ids, -1)
                cold_ids = jnp.where((fetch_ids >= 0) & ~res,
                                     fetch_ids, -1)
                _gather_dma(vec_ref, jnp.maximum(hot_ids, 0), vec_s,
                            sems, BM, 0)
                _gather_dma(qvec_ref, jnp.maximum(cold_ids, 0), qvec_s,
                            sems, BM, BM)
                diff = q[None, :] - vec_s[...]
                d_hot = jnp.where(hot_ids >= 0,
                                  jnp.sum(diff * diff, axis=1), INF)
                ohq = _onehot_cols(jnp.maximum(cold_ids, 0), cap)
                scale = jnp.sum(jnp.where(ohq, qscale_ref[...],
                                          0.0), axis=0)        # [BM]
                deq = qvec_s[...].astype(jnp.float32) * scale[:, None]
                diff_c = q[None, :] - deq
                d_cold = jnp.where(cold_ids >= 0,
                                   jnp.sum(diff_c * diff_c, axis=1),
                                   INF)
                dists = jnp.minimum(d_hot, d_cold)

            # -- bookkeeping (visited scatter as a masked reduce) -----
            visited = visited | jnp.any(oh1 & fetch_mask[None, :],
                                        axis=1)
            n_fetch = jnp.sum(fetch_mask).astype(jnp.int32)
            n_adj = n_adj + jnp.sum(act.astype(jnp.int32))
            n_vec = n_vec + n_fetch
            n_filt = n_filt \
                + jnp.sum(eligible).astype(jnp.int32) - n_fetch
            n_hops = n_hops + jnp.sum(act).astype(jnp.int32)
            if record_heat:
                at_it = jax.lax.broadcasted_iota(
                    jnp.int32, (iter_cap, 1), 0)[:, 0] == it
                heat_nodes = jnp.where(at_it[:, None], nodes[None, :],
                                       heat_nodes)
                heat_mask = jnp.where(
                    at_it[:, None, None],
                    fetch_mask.reshape(1, B, M), heat_mask)

            # -- single stable-rank merge of the whole block ----------
            all_ids = jnp.concatenate([beam_ids, fetch_ids])
            all_d = jnp.concatenate([beam_d, dists])
            all_exp = jnp.concatenate(
                [expanded, ~fetch_mask])
            mranks = _ranks_asc(all_d)
            msel = _sel_matrix(mranks, ef)               # [ef, ef+BM]
            out = (_take(msel, all_ids), _take(msel, all_d),
                   _take(msel, all_exp), visited,
                   n_adj, n_vec, n_filt, n_hops, go)
            if record_heat:
                out = out + (heat_nodes, heat_mask)
            return out

        carry = (beam_ids, beam_d, expanded, visited,
                 n_adj, n_vec, n_filt, n_hops, jnp.bool_(True))
        if record_heat:
            carry = carry + (jnp.full((iter_cap, B), -1, jnp.int32),
                             jnp.zeros((iter_cap, B, M), jnp.bool_))
        carry = jax.lax.fori_loop(0, iter_cap, trip, carry)
        beam_ids, beam_d = carry[0], carry[1]
        n_adj, n_vec, n_filt, n_hops = carry[4:8]

        if lazy:
            ret = ret_ref[..., 0] != 0                   # [cap]
            ohb = _onehot_cols(jnp.clip(beam_ids, 0, cap - 1), cap)
            ok = (beam_ids >= 0) & jnp.any(ret[:, None] & ohb, axis=0)
            beam_d = jnp.where(ok, beam_d, INF)
            rranks = _ranks_asc(beam_d)
            rsel = _sel_matrix(rranks, ef)
            beam_d = _take(rsel, beam_d)
            beam_ids = jnp.where(jnp.isfinite(beam_d),
                                 _take(rsel, beam_ids), -1)

        ids_out[...] = beam_ids[None, :]
        d_out[...] = beam_d[None, :]
        stats_out[...] = jnp.stack([n_adj, n_vec, n_filt,
                                    n_hops])[None, :]
        if record_heat:
            heatn_out[...] = carry[9].reshape(1, iter_cap * B)
            heatm_out[...] = carry[10].reshape(1, iter_cap * B * M)
        else:
            heatn_out[...] = jnp.full((1, 1), -1, jnp.int32)
            heatm_out[...] = jnp.zeros((1, 1), jnp.bool_)

    return kernel


@functools.partial(jax.jit, static_argnames=(
    "ef", "k", "m_bits", "eps", "rho", "max_iters", "use_filter",
    "n_expand", "record_heat", "interpret"))
def beam_search_fused_pallas(qs, entries, entry_dists, adjacency,
                             vectors, codes, code_qs, live, q_norms,
                             mean_norm, returnable=None, resident=None,
                             qvecs=None, qscale=None, active=None, *,
                             ef, k, m_bits, eps, rho, max_iters,
                             use_filter, n_expand=1, record_heat=True,
                             interpret=False):
    """One-launch beam search for a query block.  Same operand contract
    and return tuple as `ref.beam_search_ref`; `dim` must already be
    padded to a multiple of 128 (`ops.py` pads)."""
    bq, dpad = qs.shape
    cap, M = adjacency.shape
    W = codes.shape[1]
    assert dpad % 128 == 0, "pad dim to a lane multiple"
    B = max(1, min(n_expand, ef))
    BM = B * M
    iter_cap = min(max_iters, -(-max_iters // B) + 3)
    tier = resident is not None
    lazy = returnable is not None
    heat_len = iter_cap * B

    def as_col(a, dt):
        return a.astype(dt).reshape(-1, 1)

    ops = [qs,
           as_col(entries, jnp.int32),
           as_col(entry_dists, jnp.float32),
           code_qs,
           as_col(q_norms, jnp.float32),
           (jnp.ones((bq, 1), jnp.int32) if active is None
            else as_col(active, jnp.int32)),
           mean_norm.astype(jnp.float32).reshape(1, 1),
           adjacency, vectors, codes,
           as_col(live, jnp.int32),
           (jnp.ones((cap, 1), jnp.int32) if returnable is None
            else as_col(returnable, jnp.int32))]
    def per_q(w):
        return pl.BlockSpec((1, w), lambda i: (i, 0))

    def shared(shp):
        return pl.BlockSpec(shp, lambda i: tuple(0 for _ in shp))

    in_specs = [per_q(dpad), per_q(1), per_q(1), per_q(W), per_q(1),
                per_q(1), shared((1, 1)),
                pl.BlockSpec(memory_space=pltpu.ANY),
                pl.BlockSpec(memory_space=pltpu.ANY),
                shared((cap, W)), shared((cap, 1)), shared((cap, 1))]
    scratch = [pltpu.VMEM((B, M), jnp.int32),
               pltpu.VMEM((BM, dpad), jnp.float32)]
    if tier:
        ops += [as_col(resident, jnp.int32), qvecs,
                as_col(qscale, jnp.float32)]
        in_specs += [shared((cap, 1)),
                     pl.BlockSpec(memory_space=pltpu.ANY),
                     shared((cap, 1))]
        scratch.append(pltpu.VMEM((BM, dpad), jnp.int8))
    scratch.append(pltpu.SemaphoreType.DMA((2 * BM,)))

    hn = heat_len if record_heat else 1
    hm = heat_len * M if record_heat else 1
    out_shape = [jax.ShapeDtypeStruct((bq, ef), jnp.int32),
                 jax.ShapeDtypeStruct((bq, ef), jnp.float32),
                 jax.ShapeDtypeStruct((bq, 4), jnp.int32),
                 jax.ShapeDtypeStruct((bq, hn), jnp.int32),
                 jax.ShapeDtypeStruct((bq, hm), jnp.bool_)]
    out_specs = [per_q(ef), per_q(ef), per_q(4), per_q(hn), per_q(hm)]

    kernel = _make_beam_kernel(
        B=B, M=M, ef=ef, k=k, cap=cap, dpad=dpad, W=W,
        iter_cap=iter_cap, max_iters=max_iters, m_bits=m_bits, eps=eps,
        rho=rho, use_filter=use_filter, tier=tier, lazy=lazy,
        record_heat=record_heat)
    ids, dists, stats, heatn, heatm = pl.pallas_call(
        kernel, grid=(bq,), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        interpret=interpret)(*ops)
    if not record_heat:
        heatn = jnp.full((bq, heat_len), -1, jnp.int32)
        heatm = jnp.zeros((bq, heat_len, M), jnp.bool_)
    else:
        heatm = heatm.reshape(bq, heat_len, M)
    return ids, dists, stats, heatn, heatm
