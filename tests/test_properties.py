"""Property tests for the traversal/compaction contracts.

Pins down conventions that previously lived only in docstrings:

* ``alpha``'s empty-visit convention — queries that visit no leaves get
  α = 1 exactly (nothing was extraneous), and α ∈ [0, 1] whenever
  TN ≤ VN;
* ``compact_mask`` / ``compact_mask_counted`` at the overflow boundary —
  rows with exactly ``k``, ``k ± 1`` set bits, against the ``top_k``
  oracle;
* ``gather_result_ids`` at exactly ``max_results`` qualifying entries
  and at the edges of its loop's chunks, against its ``top_k`` oracle.

Runs under real hypothesis when installed, else the fixed-seed example
fallback in ``tests/helpers/hypo.py``.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from helpers.hypo import given, settings, st

from repro.core import traversal


# ---------------------------------------------------------------------------
# alpha
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(st.integers(1, 64), st.integers(0, 2**31 - 1))
def test_alpha_empty_visit_is_one(B, seed):
    """n_visited == 0 ⟹ α == 1 exactly, whatever n_true claims."""
    rng = np.random.default_rng(seed)
    n_true = jnp.asarray(rng.integers(0, 5, B), jnp.int32)
    n_visited = jnp.zeros((B,), jnp.int32)
    a = np.asarray(traversal.alpha(n_true, n_visited))
    np.testing.assert_array_equal(a, np.ones(B, np.float32))


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 64), st.integers(0, 2**31 - 1))
def test_alpha_bounded_and_exact_on_perfect_overlap(B, seed):
    rng = np.random.default_rng(seed)
    n_visited = jnp.asarray(rng.integers(0, 40, B), jnp.int32)
    n_true = jnp.asarray(
        rng.integers(0, np.asarray(n_visited) + 1), jnp.int32)
    a = np.asarray(traversal.alpha(n_true, n_visited))
    assert ((a >= 0) & (a <= 1)).all()
    # TN == VN > 0 ⟹ α == 1; TN == 0 < VN ⟹ α == 0
    nv = np.asarray(n_visited)
    nt = np.asarray(n_true)
    np.testing.assert_array_equal(a[(nt == nv) | (nv == 0)], 1.0)
    np.testing.assert_array_equal(a[(nt == 0) & (nv > 0)], 0.0)


# ---------------------------------------------------------------------------
# compact_mask at the overflow boundary
# ---------------------------------------------------------------------------

def _mask_with_count(rng, L, count):
    """A [L] bool row with exactly ``count`` set bits, random positions."""
    row = np.zeros(L, bool)
    row[rng.choice(L, size=count, replace=False)] = True
    return row


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 20), st.integers(1, 200), st.integers(0, 2**31 - 1))
def test_compact_mask_overflow_boundary(k, extra, seed):
    """Rows with exactly k-1 / k / k+1 set bits: overflow fires only past
    k, validity tracks min(count, k), and idx matches the top_k oracle."""
    rng = np.random.default_rng(seed)
    L = k + extra
    counts = [max(0, k - 1), k, min(L, k + 1)]
    mask = jnp.asarray(np.stack([_mask_with_count(rng, L, c)
                                 for c in counts]))
    idx, valid, count = traversal.compact_mask_counted(mask, k)
    np.testing.assert_array_equal(np.asarray(count), counts)
    # overflow == count > k: only the k+1 row (when L admits it)
    np.testing.assert_array_equal(np.asarray(count) > k,
                                  [False, False, counts[2] > k])
    np.testing.assert_array_equal(
        np.asarray(valid).sum(1), np.minimum(counts, k))
    i_old, v_old = traversal.compact_mask_topk(mask, k)
    np.testing.assert_array_equal(np.asarray(valid), np.asarray(v_old))
    np.testing.assert_array_equal(np.asarray(idx * valid),
                                  np.asarray(i_old * v_old))


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 16), st.integers(1, 120), st.integers(1, 24),
       st.integers(0, 2**31 - 1))
def test_compact_mask_random_matches_topk(B, L, k, seed):
    rng = np.random.default_rng(seed)
    mask = jnp.asarray(rng.uniform(size=(B, L)) < rng.uniform(0, 0.6))
    i_new, v_new, count = traversal.compact_mask_counted(mask, k)
    i_old, v_old = traversal.compact_mask_topk(mask, k)
    np.testing.assert_array_equal(np.asarray(v_new), np.asarray(v_old))
    np.testing.assert_array_equal(np.asarray(i_new * v_new),
                                  np.asarray(i_old * v_old))
    np.testing.assert_array_equal(np.asarray(count),
                                  np.asarray(mask).sum(1))
    np.testing.assert_array_equal(np.asarray(traversal.overflowed(mask, k)),
                                  np.asarray(count) > k)


# ---------------------------------------------------------------------------
# gather_result_ids at the truncation boundary
# ---------------------------------------------------------------------------

class _FakeTree:
    def __init__(self, rng, L, M):
        self.leaf_entry_ids = jnp.asarray(
            rng.integers(0, 10_000, (L, M)), jnp.int32)


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 8), st.integers(2, 12), st.integers(0, 2**31 - 1))
def test_gather_result_ids_truncation_boundary(K, M, seed):
    """Batches engineered to have exactly mr-1 / mr / mr+1 qualifying
    entries: truncation fires only past mr; ids match the top_k oracle."""
    rng = np.random.default_rng(seed)
    L = 30
    mr = max(2, (K * M) // 2)
    rows = []
    for count in (mr - 1, mr, min(K * M, mr + 1)):
        rows.append(_mask_with_count(rng, K * M, count).reshape(K, M))
    inside = jnp.asarray(np.stack(rows))
    leaf_idx = jnp.asarray(rng.integers(0, L, (3, K)), jnp.int32)
    valid = jnp.ones((3, K), bool)
    refine = traversal.RefineResult(
        counts=jnp.sum(inside.astype(jnp.int32), -1),
        inside=inside, leaf_idx=leaf_idx, valid=valid)
    tree = _FakeTree(rng, L, M)
    new_ids, new_tr = traversal.gather_result_ids(tree, refine, mr)
    old_ids, old_tr = traversal.gather_result_ids_topk(tree, refine, mr)
    np.testing.assert_array_equal(np.asarray(new_ids), np.asarray(old_ids))
    np.testing.assert_array_equal(np.asarray(new_tr), np.asarray(old_tr))
    np.testing.assert_array_equal(
        np.asarray(new_tr), [False, False, min(K * M, mr + 1) > mr])


_C = traversal.GATHER_CHUNK


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("mr", [300, 64])
def test_gather_result_ids_chunk_boundaries(mr, seed):
    """One batch whose rows hold 0, 1, C−1, C, C+1, 2C+1, R−1, R and R+1
    hits around the loop's chunk edges, at a ``max_results`` R that is
    not a multiple of C (300) or is under it (64), under ``jax.jit``:
    ids and truncation flags bit-identical to the top_k oracle."""
    rng = np.random.default_rng(seed)
    L, K, M = 40, 16, 20
    counts = [0, 1, _C - 1, _C, _C + 1, 2 * _C + 1, mr - 1, mr, mr + 1]
    inside = jnp.asarray(np.stack([
        _mask_with_count(rng, K * M, c).reshape(K, M) for c in counts]))
    B = len(counts)
    leaf_idx = jnp.asarray(rng.integers(0, L, (B, K)), jnp.int32)
    refine = traversal.RefineResult(
        counts=jnp.sum(inside.astype(jnp.int32), -1), inside=inside,
        leaf_idx=leaf_idx, valid=jnp.ones((B, K), bool))
    tree = _FakeTree(rng, L, M)
    new_ids, new_tr = jax.jit(
        lambda r: traversal.gather_result_ids(tree, r, mr))(refine)
    old_ids, old_tr = traversal.gather_result_ids_topk(tree, refine, mr)
    assert new_ids.shape == old_ids.shape == (B, mr)
    assert new_ids.dtype == old_ids.dtype
    np.testing.assert_array_equal(np.asarray(new_ids), np.asarray(old_ids))
    np.testing.assert_array_equal(np.asarray(new_tr), np.asarray(old_tr))
    np.testing.assert_array_equal(np.asarray(new_tr),
                                  np.asarray(counts) > mr)


@pytest.mark.slow
@settings(max_examples=20, deadline=None)
@given(st.integers(1, 10), st.integers(1, 8), st.integers(1, 30),
       st.integers(0, 2**31 - 1))
def test_gather_result_ids_random_matches_topk(B, K, mr, seed):
    rng = np.random.default_rng(seed)
    L, M = 25, int(rng.integers(2, 16))
    mr = min(mr, K * M)   # the top_k oracle requires mr ≤ flat width
    inside = jnp.asarray(rng.uniform(size=(B, K, M)) < 0.3)
    leaf_idx = jnp.asarray(rng.integers(0, L, (B, K)), jnp.int32)
    valid = jnp.asarray(rng.uniform(size=(B, K)) > 0.2)
    refine = traversal.RefineResult(
        counts=jnp.sum(inside.astype(jnp.int32), -1),
        inside=inside, leaf_idx=leaf_idx, valid=valid)
    tree = _FakeTree(rng, L, M)
    new_ids, new_tr = traversal.gather_result_ids(tree, refine, mr)
    old_ids, old_tr = traversal.gather_result_ids_topk(tree, refine, mr)
    np.testing.assert_array_equal(np.asarray(new_ids), np.asarray(old_ids))
    np.testing.assert_array_equal(np.asarray(new_tr), np.asarray(old_tr))
