"""Tie order of the beam merge (`traversal.merge_block`).

The merge must keep exactly the rows, in exactly the order, that the
classic formula keeps: `top_k(-all_d, ef)` over beam + block, then the
ids, distances and expanded flags gathered by that order.  Answers, stats
and heat records of every `beam_search` caller depend on it bit for bit,
so each case compares ids, distance bits and flags exactly, on ties
inside the beam, inside the block and across the two, on +inf pads,
masked (inert) candidates and signed zeros.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.traversal import merge_block

CASES = ["ties_in_beam", "ties_in_block", "ties_across", "inf_pads",
         "masked", "signed_zero"]


def _merge_ref(beam_ids, beam_d, expanded, cand_ids, cand_d, cand_exp):
    """The formula the merge replaces: top_k on the negated distances,
    then one gather per carried array."""
    ef = beam_ids.shape[0]
    all_ids = jnp.concatenate([beam_ids, cand_ids])
    all_d = jnp.concatenate([beam_d, cand_d])
    all_exp = jnp.concatenate([expanded, cand_exp])
    _, order = jax.lax.top_k(-all_d, ef)
    return all_ids[order], all_d[order], all_exp[order]


def _operands(case, ef, width, seed=0):
    rng = np.random.default_rng(seed)
    beam_ids = rng.permutation(10 * (ef + width))[:ef].astype(np.int32)
    cand_ids = (10 * (ef + width)
                + rng.permutation(width)).astype(np.int32)
    expanded = rng.random(ef) < 0.5
    cand_exp = np.zeros(width, bool)
    beam_d = np.sort(rng.random(ef).astype(np.float32) * 100)
    cand_d = rng.random(width).astype(np.float32) * 100
    if case == "ties_in_beam":
        beam_d = np.sort(rng.choice([1.0, 2.0, 3.0], ef)).astype(np.float32)
    elif case == "ties_in_block":
        cand_d = rng.choice([1.5, 2.5], width).astype(np.float32)
        beam_d = np.sort(rng.random(ef).astype(np.float32) * 4)
    elif case == "ties_across":
        beam_d = np.sort(rng.choice([1.0, 2.0, 3.0], ef)).astype(np.float32)
        cand_d = rng.choice([1.0, 2.0, 3.0], width).astype(np.float32)
    elif case == "inf_pads":
        n_live = ef // 3
        beam_d[n_live:] = np.inf
        beam_ids[n_live:] = -1
        expanded[n_live:] = False
        cand_d[rng.random(width) < 0.5] = np.inf
    elif case == "masked":
        # as `beam_search` passes them: a masked slot has id -1, an
        # infinite distance and enters expanded, so it is never popped
        mask = rng.random(width) < 0.5
        cand_ids = np.where(mask, cand_ids, -1).astype(np.int32)
        cand_d = np.where(mask, cand_d, np.inf).astype(np.float32)
        cand_exp = ~mask
        beam_d[ef // 2:] = np.inf
        beam_ids[ef // 2:] = -1
    elif case == "signed_zero":
        vals = np.array([0.0, -0.0, 1.0], np.float32)
        beam_d = vals[np.sort(rng.integers(0, 3, ef))]
        cand_d = vals[rng.integers(0, 3, width)]
    return tuple(jnp.asarray(a) for a in
                 (beam_ids, beam_d, expanded, cand_ids, cand_d, cand_exp))


def _assert_bit_equal(got, want):
    ids, d, exp = got
    rids, rd, rexp = want
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(rids))
    np.testing.assert_array_equal(np.asarray(d).view(np.int32),
                                  np.asarray(rd).view(np.int32))
    np.testing.assert_array_equal(np.asarray(exp), np.asarray(rexp))
    assert exp.dtype == jnp.bool_ and ids.dtype == jnp.int32


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("width", [16, 64])
@pytest.mark.parametrize("ef", [8, 48, 1024])
def test_merge_matches_top_k_gather(ef, width, case):
    ops = _operands(case, ef, width)
    _assert_bit_equal(jax.jit(merge_block)(*ops), jax.jit(_merge_ref)(*ops))


def test_signed_zero_and_inf_order():
    """-0.0 sorts before 0.0 and +inf rows come last, ties by position."""
    beam_d = jnp.array([0.0, 1.0, jnp.inf, jnp.inf], jnp.float32)
    beam_ids = jnp.array([10, 11, -1, -1], jnp.int32)
    cand_d = jnp.array([1.0, -0.0], jnp.float32)
    cand_ids = jnp.array([20, 21], jnp.int32)
    ids, d, exp = merge_block(beam_ids, beam_d, jnp.zeros(4, bool),
                              cand_ids, cand_d, jnp.array([True, False]))
    assert ids.tolist() == [21, 10, 11, 20]
    assert np.signbit(np.asarray(d)).tolist() == [True, False, False, False]
    assert exp.tolist() == [False, False, False, True]


def test_merge_vmapped_under_jit_matches():
    """Lanes of a batch, as `beam_search` runs it under vmap and jit."""
    lanes = [_operands(c, 48, 16, seed=s) for s, c in enumerate(CASES)]
    batched = tuple(jnp.stack(a) for a in zip(*lanes))
    got = jax.jit(jax.vmap(merge_block))(*batched)
    want = jax.vmap(_merge_ref)(*batched)
    _assert_bit_equal(got, want)
