"""Shape/dtype sweeps: every Pallas kernel vs its pure-jnp oracle.

Kernels run in interpret mode on CPU (the kernel body executes in Python);
TPU is the compile target.  Tolerances follow FlashAttention-style practice:
rtol 1e-3 on f32, 2e-2 on bf16 inputs (f32 accumulation inside the kernel).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st  # skips gracefully when absent

from repro.kernels.gather_l2.kernel import gather_l2_pallas
from repro.kernels.gather_l2.ops import gather_l2, gather_l2_q8
from repro.kernels.gather_l2.ref import gather_l2_q8_ref, gather_l2_ref
from repro.kernels.l2_distance.kernel import l2_distance_pallas
from repro.kernels.l2_distance.ops import l2_distance
from repro.kernels.l2_distance.ref import l2_distance_ref
from repro.kernels.simhash.kernel import collision_count_pallas, simhash_encode_pallas
from repro.kernels.simhash.ops import collision_count, simhash_encode
from repro.kernels.simhash.ref import collision_count_ref, simhash_encode_ref

TOL = {jnp.float32: dict(rtol=1e-3, atol=1e-3),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-1)}


# ---------------------------------------------------------------------------
# l2_distance
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("q,n,d", [(8, 128, 128), (128, 256, 128),
                                   (16, 128, 256), (8, 128, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_l2_distance_kernel_sweep(q, n, d, dtype):
    kq, kc = jax.random.split(jax.random.key(q * n + d))
    queries = jax.random.normal(kq, (q, d), dtype)
    cands = jax.random.normal(kc, (n, d), dtype)
    out = l2_distance_pallas(queries, cands, block_q=8, block_n=128,
                             interpret=True)
    ref = l2_distance_ref(queries, cands)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL[dtype])


def test_l2_distance_ops_ragged_shapes():
    """The ops wrapper pads/unpads non-tile-aligned shapes."""
    kq, kc = jax.random.split(jax.random.key(0))
    queries = jax.random.normal(kq, (5, 100))
    cands = jax.random.normal(kc, (77, 100))
    out = l2_distance(queries, cands, use_pallas=True, interpret=True)
    ref = l2_distance_ref(queries, cands)
    assert out.shape == (5, 77)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-3,
                               atol=1e-3)


def test_l2_distance_zero_on_identical():
    x = jax.random.normal(jax.random.key(0), (128, 128))
    out = l2_distance_pallas(x, x, block_q=128, block_n=128, interpret=True)
    diag = np.asarray(out)[np.arange(128), np.arange(128)]
    np.testing.assert_allclose(diag, 0.0, atol=1e-3)


# ---------------------------------------------------------------------------
# gather_l2
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,k,n,d", [(4, 16, 64, 128), (2, 8, 256, 128),
                                     (8, 32, 128, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gather_l2_kernel_sweep(b, k, n, d, dtype):
    kq, kt, ki = jax.random.split(jax.random.key(b * k + n + d), 3)
    queries = jax.random.normal(kq, (b, d), dtype)
    table = jax.random.normal(kt, (n, d), dtype)
    ids = jax.random.randint(ki, (b, k), 0, n, jnp.int32)
    out = gather_l2_pallas(queries, table, ids, interpret=True)
    ref = gather_l2_ref(queries, table, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL[dtype])


def test_gather_l2_negative_ids_are_inf():
    queries = jax.random.normal(jax.random.key(0), (2, 128))
    table = jax.random.normal(jax.random.key(1), (16, 128))
    ids = jnp.array([[0, -1, 3, -1], [2, 2, -1, 5]], jnp.int32)
    out = gather_l2_pallas(queries, table, ids, interpret=True)
    out = np.asarray(out)
    assert np.isinf(out[0, 1]) and np.isinf(out[0, 3]) and np.isinf(out[1, 2])
    assert np.isfinite(out[0, 0]) and np.isfinite(out[1, 0])


def test_gather_l2_ops_pads_dim():
    queries = jax.random.normal(jax.random.key(0), (3, 100))
    table = jax.random.normal(jax.random.key(1), (32, 100))
    ids = jax.random.randint(jax.random.key(2), (3, 7), 0, 32, jnp.int32)
    out = gather_l2(queries, table, ids, use_pallas=True, interpret=True)
    ref = gather_l2_ref(queries, table, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-3,
                               atol=1e-3)


def test_gather_l2_pad_lane_roundtrip_dim65_exact():
    """dim=65 (not a lane multiple) must round-trip bit-exactly.

    Pad lanes are zero in query and table, contributing +0.0 each, so
    with integer-valued inputs (sums exact in f32) the padded kernel
    reduction must equal the unpadded oracle bit-for-bit.
    """
    kq, kt, ki = jax.random.split(jax.random.key(65), 3)
    queries = jax.random.randint(kq, (4, 65), -8, 8).astype(jnp.float32)
    table = jax.random.randint(kt, (48, 65), -8, 8).astype(jnp.float32)
    ids = jax.random.randint(ki, (4, 9), -1, 48, jnp.int32)
    out = gather_l2(queries, table, ids, use_pallas=True, interpret=True)
    ref = gather_l2_ref(queries, table, ids)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_gather_l2_q8_pad_lane_roundtrip_dim65_exact():
    kq, kt, ks, ki = jax.random.split(jax.random.key(66), 4)
    queries = jax.random.randint(kq, (4, 65), -8, 8).astype(jnp.float32)
    qtable = jax.random.randint(kt, (48, 65), -127, 128).astype(jnp.int8)
    # power-of-two scales keep dequant products exact in f32
    scales = 2.0 ** jax.random.randint(ks, (48,), -3, 3).astype(jnp.float32)
    ids = jax.random.randint(ki, (4, 9), -1, 48, jnp.int32)
    out = gather_l2_q8(queries, qtable, scales, ids, use_pallas=True,
                       interpret=True)
    ref = gather_l2_q8_ref(queries, qtable, scales, ids)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("dim", [65, 128])
@pytest.mark.parametrize("n", [48, 1000, 1001])
def test_gather_l2_row_dma_kernels_bit_identical(dim, n):
    """The row-DMA kernels (each candidate's row fetched by its own copy;
    int8 rows in 8-row groups, the id's row kept) equal the references
    bit for bit, tables whose row count is not a group multiple and -1
    ids included.  Integer-valued inputs and power-of-two scales keep
    every sum exact in f32."""
    kq, kt, kt8, ks, ki = jax.random.split(jax.random.key(dim * n), 5)
    queries = jax.random.randint(kq, (4, dim), -8, 8).astype(jnp.float32)
    table = jax.random.randint(kt, (n, dim), -8, 8).astype(jnp.float32)
    qtable = jax.random.randint(kt8, (n, dim), -127, 128).astype(jnp.int8)
    scales = 2.0 ** jax.random.randint(ks, (n,), -3, 3).astype(jnp.float32)
    ids = jax.random.randint(ki, (4, 24), -1, n, jnp.int32)
    ids = ids.at[:, 0].set(n - 1).at[:, 1].set(-1)   # last row, a sentinel
    out = gather_l2(queries, table, ids, use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(gather_l2_ref(queries, table,
                                                           ids)))
    out8 = gather_l2_q8(queries, qtable, scales, ids, use_pallas=True,
                        interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out8),
        np.asarray(gather_l2_q8_ref(queries, qtable, scales, ids)))


def test_gather_l2_kernel_under_vmap_matches_ref():
    """The search path calls the kernel per query under vmap (one query,
    K candidates); batching must keep every query's ids its own."""
    q = jax.random.normal(jax.random.key(1), (5, 128))
    table = jax.random.normal(jax.random.key(2), (100, 128))
    ids = jax.random.randint(jax.random.key(3), (5, 16), -1, 100, jnp.int32)
    per_query = jax.vmap(lambda qq, ii: gather_l2(
        qq[None], table, ii[None], use_pallas=True, interpret=True)[0])
    np.testing.assert_array_equal(np.asarray(per_query(q, ids)),
                                  np.asarray(gather_l2_ref(q, table, ids)))


def test_gather_l2_dim_mismatch_guard():
    queries = jnp.zeros((2, 65))
    table = jnp.zeros((8, 64))
    ids = jnp.zeros((2, 3), jnp.int32)
    with pytest.raises(ValueError, match="dim"):
        gather_l2(queries, table, ids, use_pallas=True, interpret=True)
    with pytest.raises(ValueError, match="dim"):
        gather_l2_q8(queries, table.astype(jnp.int8), jnp.ones(8), ids,
                     use_pallas=True, interpret=True)


# ---------------------------------------------------------------------------
# simhash
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,d,m", [(256, 128, 64), (512, 64, 128),
                                   (256, 256, 32)])
def test_simhash_encode_kernel_sweep(n, d, m):
    kx, kp = jax.random.split(jax.random.key(n + d + m))
    x = jax.random.normal(kx, (n, d))
    proj = jax.random.normal(kp, (m, d))
    out = simhash_encode_pallas(x, proj, block_n=256, interpret=True)
    ref = simhash_encode_ref(x, proj)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("q,n,m", [(8, 512, 64), (16, 1024, 128)])
def test_collision_count_kernel_sweep(q, n, m):
    kx, ky, kp = jax.random.split(jax.random.key(q + n + m), 3)
    proj = jax.random.normal(kp, (m, 32))
    cq = simhash_encode_ref(jax.random.normal(kx, (q, 32)), proj)
    cc = simhash_encode_ref(jax.random.normal(ky, (n, 32)), proj)
    out = collision_count_pallas(cq, cc, m, block_q=8, block_n=512,
                                 interpret=True)
    ref = collision_count_ref(cq, cc, m)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_simhash_ops_ragged():
    x = jax.random.normal(jax.random.key(0), (100, 48))
    proj = jax.random.normal(jax.random.key(1), (64, 48))
    out = simhash_encode(x, proj, use_pallas=True, interpret=True)
    ref = simhash_encode_ref(x, proj)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    cols = collision_count(out[:10], out, 64, use_pallas=True, interpret=True)
    refc = collision_count_ref(ref[:10], ref, 64)
    np.testing.assert_array_equal(np.asarray(cols), np.asarray(refc))


# ---------------------------------------------------------------------------
# property: kernel/oracle agreement on random shapes
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=1, max_value=6),
       st.integers(min_value=1, max_value=40),
       st.sampled_from([64, 128, 200]))
def test_property_l2_ops_any_shape(q, n, d):
    kq, kc = jax.random.split(jax.random.key(q * 1000 + n * 10 + d))
    queries = jax.random.normal(kq, (q, d))
    cands = jax.random.normal(kc, (n, d))
    out = l2_distance(queries, cands, use_pallas=True, interpret=True)
    ref = l2_distance_ref(queries, cands)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-3,
                               atol=1e-3)
