"""kNN serving: distance browsing on the slot-table contract.

Both kernel forms of ``kernels.knn_browse`` must be bit-identical to the
jnp oracle; ``knn_query`` must match the all-pairs brute-force oracle
bit-for-bit on every non-truncated row (the d2 arithmetic is evaluated
under jit on both sides, so XLA's FMA contraction is identical), and on
the in-radius *prefix* of truncated rows; the radius-doubling wide tier
resolves flagged rows through the same two-tier ``serve_workload``
machinery the range path uses; and the kernel path's lowered HLO carries
no dense [B, L] visited mask.
"""
import functools
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import device_tree as dt, knn, schedule, traversal
from repro.core.device_tree import DeviceTree, Level
from repro.core.rtree import RTree
from repro.kernels import knn_browse as kb, ops, ref
from tests.helpers.hypo import given, settings, st


@functools.lru_cache(maxsize=None)
def _world(n=2500, seed=0):
    rng = np.random.default_rng(seed)
    pts = rng.normal(size=(n, 2))
    dtree = dt.flatten(RTree.str_bulk(pts, max_entries=16))
    return pts, dtree


def _centers(pts, rng, n):
    c = pts[rng.integers(0, pts.shape[0], n)].astype(np.float32)
    return c + rng.normal(scale=1e-3, size=c.shape).astype(np.float32)


def _degenerate(centers):
    return np.concatenate([centers, centers], axis=1).astype(np.float32)


@functools.partial(jax.jit)
def _d2(pts, centers):
    dx = pts[..., 0] - centers[:, None, 0]
    dy = pts[..., 1] - centers[:, None, 1]
    return dx * dx + dy * dy


# ---------------------------------------------------------------------------
# kernel forms vs jnp oracle
# ---------------------------------------------------------------------------

def test_kernel_forms_bit_identical():
    """TPU grid form, folded form, the jnp oracle, and the ops wrapper
    all agree bit-for-bit on a real visited set."""
    pts, tree = _world()
    rng = np.random.default_rng(1)
    centers = _centers(pts, rng, 32)
    r = knn.default_radius(tree, 8)
    box = np.concatenate([centers - r, centers + r], 1).astype(np.float32)
    cv = traversal.visited_leaves_compact(tree, jnp.asarray(box), 32,
                                          use_kernel=False)
    c3 = jnp.asarray(np.concatenate(
        [centers, np.full((32, 1), r * r, np.float32)], 1))
    ent = tree.leaf_entries
    safe = jnp.clip(cv.leaf_idx, 0, ent.shape[0] - 1)
    # the oracle must run under jit: eager jax dispatches op-by-op and
    # never FMA-contracts dx*dx + dy*dy, so it differs from any jitted
    # form by 1 ulp wherever XLA fuses the multiply-add
    want = np.asarray(jax.jit(ref.knn_browse)(c3, ent, safe, cv.valid))
    assert np.isfinite(want).any(), "fixture too weak: no in-radius hits"
    for fold in (False, True):
        got = kb.knn_browse(c3, ent, safe, cv.valid, interpret=True,
                            fold_k=fold)
        np.testing.assert_array_equal(np.asarray(got), want,
                                      err_msg=f"fold_k={fold}")
    got = ops.knn_browse(c3, tree.leaf_entries, cv.leaf_idx, cv.valid)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_padded_slots_are_inert():
    """Invalid slots (valid == 0) come back +inf on every form even when
    their clipped leaf index aliases a real leaf; inside a valid slot,
    the leaf tile's own entry padding is inert too (exactly
    ``leaf_counts`` finite candidates)."""
    pts, tree = _world()
    rng = np.random.default_rng(2)
    centers = _centers(pts, rng, 8)
    # huge radius: every real entry is in range — only `valid` and the
    # tile's entry padding can mask candidates out
    c3 = jnp.asarray(np.concatenate(
        [centers, np.full((8, 1), 1e9, np.float32)], 1))
    K = 8
    idx = jnp.zeros((8, K), jnp.int32)          # all alias leaf 0
    valid = jnp.zeros((8, K), jnp.int32).at[:, :2].set(1)
    ent = tree.leaf_entries
    n0 = int(tree.leaf_counts[0])
    assert 0 < n0 < ent.shape[2], "fixture: want a padded tile"
    for form in ("oracle", "tpu", "folded"):
        if form == "oracle":
            d2 = jax.jit(ref.knn_browse)(c3, ent, idx, valid)
        else:
            d2 = kb.knn_browse(c3, ent, idx, valid, interpret=True,
                               fold_k=form == "folded")
        d2 = np.asarray(d2)
        assert (np.isfinite(d2[:, :2]).sum(axis=-1) == n0).all(), form
        assert not np.isfinite(d2[:, 2:]).any(), form


# ---------------------------------------------------------------------------
# knn_query vs brute force
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_kernel", [False, True])
def test_knn_query_matches_brute(use_kernel):
    pts, tree = _world()
    rng = np.random.default_rng(3)
    centers = _centers(pts, rng, 48)
    r = knn.default_radius(tree, 8)
    res = knn.knn_query(tree, jnp.asarray(_degenerate(centers)), k=8,
                        radius=r, max_visited=64, use_kernel=use_kernel)
    bd2, _ = knn.knn_brute(pts, centers, 8)
    tr = np.asarray(res.truncated)
    nw = np.asarray(res.n_within)
    got = np.asarray(res.neighbor_d2)
    assert (~tr).sum() >= 32, "fixture too weak: mostly truncated"
    np.testing.assert_array_equal(got[~tr], bd2[~tr])
    # truncated rows: the in-radius neighbors are exactly the brute
    # prefix (anything closer than an in-radius point is also in radius)
    for j in np.flatnonzero(tr):
        kk = min(int(nw[j]), 8)
        np.testing.assert_array_equal(got[j, :kk], bd2[j, :kk])
    # ids point at the distances they claim (recomputed under jit)
    ids = np.asarray(res.neighbor_ids)
    hit = np.isfinite(got)
    assert (ids[hit] >= 0).all() and (ids[~hit] == -1).all()
    d2c = np.asarray(_d2(jnp.asarray(pts.astype(np.float32))[
        np.clip(ids, 0, None)], jnp.asarray(centers)))
    np.testing.assert_array_equal(d2c[hit], got[hit])


def test_knn_accepts_point_queries():
    """[B, 2] point input and the equivalent degenerate rect agree."""
    pts, tree = _world()
    rng = np.random.default_rng(4)
    centers = _centers(pts, rng, 16)
    r = knn.default_radius(tree, 4)
    a = knn.knn_query(tree, jnp.asarray(centers), k=4, radius=r)
    b = knn.knn_query(tree, jnp.asarray(_degenerate(centers)), k=4,
                      radius=r)
    for f in a._fields:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)), err_msg=f)


@given(st.integers(1, 32), st.integers(1, 12), st.integers(0, 4))
@settings(max_examples=12, deadline=None)
def test_knn_prefix_property(n, k, seed):
    """Property: for any (batch, k, seed), every row's reported
    neighbors are a bit-exact prefix of the brute kNN — full length on
    non-truncated rows, the in-radius prefix otherwise. Zero silent
    drops by construction."""
    pts, tree = _world()
    rng = np.random.default_rng(seed)
    centers = _centers(pts, rng, n)
    r = knn.default_radius(tree, k)
    res = knn.knn_query(tree, jnp.asarray(_degenerate(centers)), k=k,
                        radius=r, max_visited=64)
    bd2, _ = knn.knn_brute(pts, centers, k)
    got = np.asarray(res.neighbor_d2)
    tr = np.asarray(res.truncated)
    nw = np.asarray(res.n_within)
    for j in range(n):
        kk = k if not tr[j] else min(int(nw[j]), k)
        np.testing.assert_array_equal(got[j, :kk], bd2[j, :kk])


# ---------------------------------------------------------------------------
# two-tier radius doubling
# ---------------------------------------------------------------------------

def test_two_tier_radius_doubling():
    """A deliberately tight narrow radius truncates rows; the wide tier
    (2x radius, wider slot table) resolves them through the standard
    serve_workload re-serve, leaving non-truncated rows untouched."""
    pts, tree = _world()
    rng = np.random.default_rng(5)
    centers = _centers(pts, rng, 64)
    q = _degenerate(centers)
    r = knn.default_radius(tree, 16, margin=1.0)
    narrow, wide = knn.make_knn_steps(tree, k=16, radius=r,
                                      max_visited=64)
    rep_n = schedule.serve_workload(narrow, q, batch=16, sort="hilbert")
    tr = np.asarray(rep_n.stats.truncated)
    assert tr.any(), "fixture too weak: nothing truncated"
    assert not tr.all(), "fixture too weak: everything truncated"
    rep = schedule.serve_workload(narrow, q, batch=16, sort="hilbert",
                                  wide_fn=wide, trunc_field="truncated")
    assert rep.n_reserved == int(tr.sum())
    tr2 = np.asarray(rep.stats.truncated)
    assert tr2.sum() < tr.sum(), "wide tier resolved nothing"
    bd2, _ = knn.knn_brute(pts, centers, 16)
    np.testing.assert_array_equal(
        np.asarray(rep.stats.neighbor_d2)[~tr2], bd2[~tr2])
    keep = ~tr
    for f in type(rep.stats)._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(rep.stats, f))[keep],
            np.asarray(getattr(rep_n.stats, f))[keep], err_msg=f)


def test_sorted_knn_stream_bit_identical():
    pts, tree = _world()
    rng = np.random.default_rng(6)
    centers = _centers(pts, rng, 53)
    q = _degenerate(centers)
    r = knn.default_radius(tree, 8)
    narrow, wide = knn.make_knn_steps(tree, k=8, radius=r)
    base = schedule.serve_workload(narrow, q, batch=16, sort="none",
                                   wide_fn=wide, trunc_field="truncated")
    srt = schedule.serve_workload(narrow, q, batch=16, sort="hilbert",
                                  wide_fn=wide, trunc_field="truncated")
    for f in type(base.stats)._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(base.stats, f)),
            np.asarray(getattr(srt.stats, f)), err_msg=f)


# ---------------------------------------------------------------------------
# HLO contract: no dense [B, L] mask on the kernel path
# ---------------------------------------------------------------------------

def _synth_tree(L=1000, M=8):
    from repro.data.synth_tree import synth_levels
    rng = np.random.default_rng(0)
    mbrs, parents = synth_levels(L, 4, rng)
    return DeviceTree(
        levels=tuple(Level(mbrs=jnp.asarray(m), parent=jnp.asarray(p))
                     for m, p in zip(mbrs, parents)),
        leaf_entries=jnp.zeros((L, 2, M), jnp.float32),
        leaf_entry_ids=jnp.zeros((L, M), jnp.int32),
        leaf_counts=jnp.zeros((L,), jnp.int32),
        n_points=0, max_entries=4)


def test_knn_hlo_no_dense_mask():
    """The kernel-path kNN serving HLO must carry no [B, L]-shaped
    tensor (L = 1000, padded 1024); the jnp oracle rung is the positive
    control."""
    tree = _synth_tree()
    B = 256
    q = jnp.zeros((B, 4), jnp.float32)

    def lowered(uk):
        return jax.jit(lambda t, qq: knn.knn_query(
            t, qq, k=8, radius=0.1, max_visited=64, use_kernel=uk,
            tile_b=128)).lower(tree, q).as_text()

    dense = re.compile(r"<256x(1000|1024)x")
    assert not dense.search(lowered(True)), \
        "kNN kernel path materialized the dense [B, L] mask"
    assert dense.search(lowered(False)), \
        "oracle control lost its dense mask"
