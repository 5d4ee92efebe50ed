"""Batched spatial join: pair-set exactness and wide-tier preservation.

``join_step`` (both kernel forms) and ``spatial_join`` must reproduce
the brute-force pair set exactly; overflowing rows re-serve on the wide
tier with their pairs kept at that tier's full static width (the
payload-preservation property ``schedule._merge_rows`` alone cannot
give); and the kernel path's serving HLO carries no dense [B, L] mask.
"""
import functools
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import device_tree as dt, joins
from repro.core.device_tree import DeviceTree, Level
from repro.core.rtree import RTree
from tests.helpers.hypo import given, settings, st


@functools.lru_cache(maxsize=None)
def _world(n=2500, seed=0):
    rng = np.random.default_rng(seed)
    pts = rng.normal(size=(n, 2))
    dtree = dt.flatten(RTree.str_bulk(pts, max_entries=16))
    return pts, dtree


def _rects(pts, rng, n, w=0.08):
    lo = pts[rng.integers(0, pts.shape[0], n)].astype(np.float32)
    wd = rng.uniform(0, w, (n, 2)).astype(np.float32)
    return np.concatenate([lo - wd, lo + wd], axis=1)


def _pair_set(stats, rows=None):
    ids = np.asarray(stats.pair_ids)
    nps = np.asarray(stats.n_pairs)
    rows = range(ids.shape[0]) if rows is None else rows
    return {(int(i), int(p)) for i in rows
            for p in ids[i, :min(int(nps[i]), ids.shape[1])]}


# ---------------------------------------------------------------------------
# join_step vs brute force
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_kernel", [False, True])
def test_join_step_pairs_match_brute(use_kernel):
    pts, tree = _world()
    rng = np.random.default_rng(1)
    outer = _rects(pts, rng, 48)
    res = joins.join_step(tree, jnp.asarray(outer), max_pairs=64,
                          max_visited=64, use_kernel=use_kernel)
    assert not np.asarray(res.truncated).any(), "fixture: bounds too tight"
    bp = joins.join_brute(pts, outer)
    assert bp.shape[0] > 48, "fixture too weak: joins barely populated"
    assert _pair_set(res) == {tuple(r) for r in bp}
    np.testing.assert_array_equal(np.asarray(res.n_pairs),
                                  np.bincount(bp[:, 0], minlength=48))


def test_join_step_kernel_forms_agree():
    pts, tree = _world()
    rng = np.random.default_rng(2)
    outer = jnp.asarray(_rects(pts, rng, 32))
    a = joins.join_step(tree, outer, max_pairs=32, use_kernel=False)
    b = joins.join_step(tree, outer, max_pairs=32, use_kernel=True)
    for f in a._fields:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)), err_msg=f)


# ---------------------------------------------------------------------------
# spatial_join: exactness, order canonicality, wide-tier preservation
# ---------------------------------------------------------------------------

def test_spatial_join_matches_brute():
    pts, tree = _world()
    rng = np.random.default_rng(3)
    outer = _rects(pts, rng, 120)
    rep = joins.spatial_join(tree, outer, batch=32, max_pairs=16,
                             max_visited=64)
    assert rep.residual_truncated == 0
    bp = joins.join_brute(pts, outer)
    np.testing.assert_array_equal(rep.pairs, bp)
    assert rep.n_pairs == bp.shape[0] and rep.n_outer == 120


def test_spatial_join_order_canonical_across_sorts():
    """The (outer, point)-lexsorted pair array is identical whatever
    curve formed the batches."""
    pts, tree = _world()
    rng = np.random.default_rng(4)
    outer = _rects(pts, rng, 90)
    reps = [joins.spatial_join(tree, outer, batch=16, max_pairs=16,
                               sort=s) for s in ("none", "hilbert",
                                                 "morton")]
    for rep in reps[1:]:
        np.testing.assert_array_equal(rep.pairs, reps[0].pairs)
        assert rep.n_pairs == reps[0].n_pairs


def test_wide_tier_preserves_pairs():
    """Rows overflowing the narrow pair table re-serve wide and keep
    every pair at the wide tier's full width — no silent slicing back
    to the narrow width."""
    pts, tree = _world()
    rng = np.random.default_rng(5)
    outer = _rects(pts, rng, 80, w=0.25)     # fat rects: many pairs/row
    narrow = joins.join_step(tree, jnp.asarray(outer), max_pairs=4,
                             max_visited=64)
    tr = np.asarray(narrow.truncated)
    assert tr.any(), "fixture too weak: nothing overflowed max_pairs=4"
    assert not tr.all(), "fixture too weak: everything overflowed"
    rep = joins.spatial_join(tree, outer, batch=16, max_pairs=4,
                             max_visited=64, wide_factor=64)
    assert rep.n_reserved == int(tr.sum())
    assert rep.residual_truncated == 0
    bp = joins.join_brute(pts, outer)
    np.testing.assert_array_equal(rep.pairs, bp)
    # a truncated row really did carry more pairs than the narrow width
    counts = np.bincount(bp[:, 0], minlength=80)
    assert counts[tr].max() > 4
    # merged per-row stats carry the full counts
    np.testing.assert_array_equal(np.asarray(rep.stats.n_pairs), counts)


@given(st.integers(1, 60), st.integers(2, 30), st.booleans())
@settings(max_examples=10, deadline=None)
def test_join_property_pair_set_exact(n, batch, hilbert):
    """Property: for any stream length / batch size / curve, the join
    reproduces the brute-force pair set exactly (wide tier sized to
    cover everything)."""
    pts, tree = _world()
    rng = np.random.default_rng(n * 31 + batch)
    outer = _rects(pts, rng, n, w=0.15)
    rep = joins.spatial_join(tree, outer, batch=batch, max_pairs=8,
                             max_visited=64, wide_factor=64,
                             sort="hilbert" if hilbert else "none")
    assert rep.residual_truncated == 0
    np.testing.assert_array_equal(rep.pairs, joins.join_brute(pts, outer))


def test_join_empty_result():
    """Outer rects that hit nothing: zero pairs, well-formed report."""
    pts, tree = _world()
    outer = np.tile(np.array([[50.0, 50.0, 51.0, 51.0]], np.float32),
                    (9, 1))
    rep = joins.spatial_join(tree, outer, batch=4)
    assert rep.n_pairs == 0 and rep.pairs.shape == (0, 2)
    assert not np.asarray(rep.stats.n_pairs).any()


# ---------------------------------------------------------------------------
# HLO contract
# ---------------------------------------------------------------------------

def test_join_step_hlo_stays_compact():
    """The kernel-path join batch lowers without any [B, L]-shaped
    tensor; the jnp oracle rung is the positive control."""
    from repro.data.synth_tree import synth_levels
    rng = np.random.default_rng(0)
    L, M, B = 1000, 8, 256
    mbrs, parents = synth_levels(L, 4, rng)
    tree = DeviceTree(
        levels=tuple(Level(mbrs=jnp.asarray(m), parent=jnp.asarray(p))
                     for m, p in zip(mbrs, parents)),
        leaf_entries=jnp.zeros((L, 2, M), jnp.float32),
        leaf_entry_ids=jnp.zeros((L, M), jnp.int32),
        leaf_counts=jnp.zeros((L,), jnp.int32),
        n_points=0, max_entries=4)
    q = jnp.zeros((B, 4), jnp.float32)

    def lowered(uk):
        return jax.jit(lambda t, qq: joins.join_step(
            t, qq, max_pairs=16, max_visited=64, use_kernel=uk,
            tile_b=128)).lower(tree, q).as_text()

    dense = re.compile(r"<256x(1000|1024)x")
    assert not dense.search(lowered(True)), \
        "join kernel path materialized the dense [B, L] mask"
    assert dense.search(lowered(False)), \
        "oracle control lost its dense mask"
