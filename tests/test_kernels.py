"""Per-kernel validation: Pallas (interpret mode on CPU) vs pure-jnp oracle.

Sweeps shapes/dtypes per kernel and asserts allclose; includes hypothesis
property tests for the geometric kernels.
"""
import numpy as np
import pytest
import jax.numpy as jnp
from helpers.hypo import given, settings, st

from repro.kernels import ops, ref


RNG = np.random.default_rng(42)


def mk_rects(n, rng=RNG, scale=1.0):
    lo = rng.uniform(-scale, scale, size=(n, 2))
    w = rng.uniform(0, scale, size=(n, 2))
    return np.concatenate([lo, lo + w], axis=1).astype(np.float32)


# ---------------------------------------------------------------------------
# mbr_intersect
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,N", [(1, 1), (7, 130), (64, 512), (257, 1000),
                                 (1024, 64), (3, 4096)])
def test_mbr_intersect_shapes(B, N):
    q, m = mk_rects(B), mk_rects(N)
    out = ops.mbr_intersect(jnp.asarray(q), jnp.asarray(m))
    exp = ref.mbr_intersect(jnp.asarray(q), jnp.asarray(m))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_mbr_intersect_dtypes(dtype):
    q, m = mk_rects(33).astype(dtype), mk_rects(65).astype(dtype)
    out = ops.mbr_intersect(jnp.asarray(q), jnp.asarray(m))
    exp = ref.mbr_intersect(jnp.asarray(q), jnp.asarray(m))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


def test_mbr_intersect_touching_counts():
    q = np.array([[0.0, 0.0, 1.0, 1.0]], np.float32)
    m = np.array([[1.0, 1.0, 2.0, 2.0],   # corner touch → intersects
                  [1.0000001, 1.0, 2.0, 2.0],  # just past → no
                  [-1.0, -1.0, 0.0, 0.0]], np.float32)
    out = np.asarray(ops.mbr_intersect(jnp.asarray(q), jnp.asarray(m)))
    assert out.tolist() == [[True, False, True]]


@pytest.mark.slow
@settings(max_examples=30, deadline=None)
@given(st.integers(1, 40), st.integers(1, 300), st.integers(0, 2**31 - 1))
def test_mbr_intersect_property(B, N, seed):
    rng = np.random.default_rng(seed)
    q, m = mk_rects(B, rng), mk_rects(N, rng)
    out = np.asarray(ops.mbr_intersect(jnp.asarray(q), jnp.asarray(m)))
    exp = np.asarray(ref.mbr_intersect(jnp.asarray(q), jnp.asarray(m)))
    np.testing.assert_array_equal(out, exp)
    # symmetry: swapping roles transposes the mask
    out_t = np.asarray(ops.mbr_intersect(jnp.asarray(m), jnp.asarray(q)))
    np.testing.assert_array_equal(out_t, exp.T)


# ---------------------------------------------------------------------------
# leaf_refine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,K,L,M", [(1, 1, 1, 8), (9, 5, 40, 16),
                                     (64, 16, 200, 32), (17, 64, 1000, 200)])
def test_leaf_refine_shapes(B, K, L, M):
    q = mk_rects(B)
    entries = RNG.uniform(-1, 1, size=(L, 2, M)).astype(np.float32)
    idx = RNG.integers(0, L, size=(B, K)).astype(np.int32)
    valid = (RNG.uniform(size=(B, K)) > 0.3).astype(np.int32)
    out = ops.leaf_refine(jnp.asarray(q), jnp.asarray(entries),
                          jnp.asarray(idx), jnp.asarray(valid))
    exp = ref.leaf_refine(jnp.asarray(q), jnp.asarray(entries),
                          jnp.asarray(idx), jnp.asarray(valid))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


@pytest.mark.parametrize("B,K,L,M", [(1, 1, 1, 8), (9, 5, 40, 16),
                                     (64, 16, 200, 32), (17, 64, 1000, 200)])
def test_leaf_refine_grid_forms_bit_identical(B, K, L, M):
    """The folded interpret form (whole-array block, XLA-level gather) and
    the (B, K) scalar-prefetch TPU form must agree bit for bit."""
    from repro.kernels import leaf_refine as lr
    q = mk_rects(B)
    entries = RNG.uniform(-1, 1, size=(L, 2, M)).astype(np.float32)
    idx = RNG.integers(0, L, size=(B, K)).astype(np.int32)
    valid = (RNG.uniform(size=(B, K)) > 0.3).astype(np.int32)
    args = (jnp.asarray(q), jnp.asarray(entries), jnp.asarray(idx),
            jnp.asarray(valid))
    prefetch = lr.leaf_refine(*args, interpret=True, fold_k=False)
    folded = lr.leaf_refine(*args, interpret=True, fold_k=True)
    np.testing.assert_array_equal(np.asarray(prefetch), np.asarray(folded))


def test_leaf_refine_inf_padding_never_matches():
    q = np.array([[-1e30, -1e30, 1e30, 1e30]], np.float32)  # huge query
    entries = np.full((4, 2, 8), np.inf, np.float32)        # all padding
    idx = np.zeros((1, 2), np.int32)
    valid = np.ones((1, 2), np.int32)
    out = np.asarray(ops.leaf_refine(jnp.asarray(q), jnp.asarray(entries),
                                     jnp.asarray(idx), jnp.asarray(valid)))
    assert not out.any()


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 16), st.integers(1, 12), st.integers(1, 50),
       st.integers(0, 2**31 - 1))
def test_leaf_refine_property(B, K, L, seed):
    rng = np.random.default_rng(seed)
    M = int(rng.integers(4, 40))
    q = mk_rects(B, rng)
    entries = rng.uniform(-1, 1, size=(L, 2, M)).astype(np.float32)
    idx = rng.integers(0, L, size=(B, K)).astype(np.int32)
    valid = (rng.uniform(size=(B, K)) > 0.5).astype(np.int32)
    out = np.asarray(ops.leaf_refine(jnp.asarray(q), jnp.asarray(entries),
                                     jnp.asarray(idx), jnp.asarray(valid)))
    # invalid slots are all-false; valid slots match direct containment
    for b in range(B):
        for k in range(K):
            if not valid[b, k]:
                assert not out[b, k].any()
            else:
                px, py = entries[idx[b, k]]
                exp = ((px >= q[b, 0]) & (px <= q[b, 2])
                       & (py >= q[b, 1]) & (py <= q[b, 3]))
                np.testing.assert_array_equal(out[b, k], exp)


# ---------------------------------------------------------------------------
# forest_infer
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,T,D,C", [(1, 1, 1, 8), (33, 4, 5, 24),
                                     (128, 16, 8, 128), (7, 2, 10, 64)])
def test_forest_infer_shapes(B, T, D, C):
    F = 6
    feats = RNG.uniform(-1, 1, size=(B, F)).astype(np.float32)
    fidx = RNG.integers(0, F, size=(T, D)).astype(np.int32)
    th = RNG.uniform(-1, 1, size=(T, D)).astype(np.float32)
    tables = RNG.uniform(0, 1, size=(T, 2 ** D, C)).astype(np.float32)
    out = ops.forest_infer(jnp.asarray(feats), jnp.asarray(fidx),
                           jnp.asarray(th), jnp.asarray(tables))
    exp = ref.forest_infer(jnp.asarray(feats[:, fidx]), jnp.asarray(th),
                           jnp.asarray(tables))
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=1e-5)


def test_forest_infer_single_path():
    # One tree, depth 2; feature 0 decides bit0, feature 1 decides bit1.
    feats = np.array([[2.0, -3.0]], np.float32)   # bit0=1 (2>0), bit1=0 → leaf 2
    fidx = np.array([[0, 1]], np.int32)
    th = np.zeros((1, 2), np.float32)
    tables = np.zeros((1, 4, 3), np.float32)
    tables[0, 2] = [1, 2, 3]
    out = np.asarray(ops.forest_infer(jnp.asarray(feats), jnp.asarray(fidx),
                                      jnp.asarray(th), jnp.asarray(tables)))
    np.testing.assert_allclose(out, [[1, 2, 3]])


# ---------------------------------------------------------------------------
# wkv6
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("BH,T,dk,dv,chunk", [
    (1, 16, 8, 8, 16), (3, 64, 8, 16, 16), (2, 48, 16, 16, 16),
    (1, 33, 8, 8, 16),  # padded-T path
    (2, 128, 32, 32, 64),
])
def test_wkv6_shapes(BH, T, dk, dv, chunk):
    r = RNG.normal(size=(BH, T, dk)).astype(np.float32)
    k = RNG.normal(size=(BH, T, dk)).astype(np.float32)
    v = RNG.normal(size=(BH, T, dv)).astype(np.float32)
    w = RNG.uniform(0.05, 0.999, size=(BH, T, dk)).astype(np.float32)
    u = RNG.normal(size=(BH, dk)).astype(np.float32)
    out = ops.wkv6(*map(jnp.asarray, (r, k, v, w, u)), chunk=chunk)
    exp = ref.wkv6(*map(jnp.asarray, (r, k, v, w, u)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=5e-4, atol=5e-4)


def test_wkv6_extreme_decay_is_stable():
    """Per-channel ≤0 exponents ⇒ no overflow even for near-zero decay."""
    BH, T, dk, dv = 2, 64, 8, 8
    r = RNG.normal(size=(BH, T, dk)).astype(np.float32)
    k = RNG.normal(size=(BH, T, dk)).astype(np.float32)
    v = RNG.normal(size=(BH, T, dv)).astype(np.float32)
    w = RNG.uniform(1e-8, 0.1, size=(BH, T, dk)).astype(np.float32)
    u = RNG.normal(size=(BH, dk)).astype(np.float32)
    out = ops.wkv6(*map(jnp.asarray, (r, k, v, w, u)), chunk=16)
    exp = ref.wkv6(*map(jnp.asarray, (r, k, v, w, u)))
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=5e-4, atol=5e-4)


def test_wkv6_bf16_inputs():
    BH, T, dk, dv = 1, 32, 8, 8
    r = jnp.asarray(RNG.normal(size=(BH, T, dk)), jnp.bfloat16)
    k = jnp.asarray(RNG.normal(size=(BH, T, dk)), jnp.bfloat16)
    v = jnp.asarray(RNG.normal(size=(BH, T, dv)), jnp.bfloat16)
    w = jnp.asarray(RNG.uniform(0.3, 0.99, size=(BH, T, dk)), jnp.bfloat16)
    u = jnp.asarray(RNG.normal(size=(BH, dk)), jnp.bfloat16)
    out = ops.wkv6(r, k, v, w, u, chunk=16)
    exp = ref.wkv6(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(exp, dtype=np.float32),
                               rtol=5e-2, atol=5e-2)
