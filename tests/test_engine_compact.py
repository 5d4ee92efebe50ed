"""Serving-path edge cases exposed by the compacted query pipeline.

Covers the single-level (root == leaf) traversal regression — the former
``make_serve_step``-local visited loop unconditionally applied the leaf
``parent`` gather, self-gathering the root mask's column 0 across the row;
the serve step now routes through ``traversal.visited_leaves_compact`` /
``visited_leaf_mask``, which these tests pin on the degenerate shape — and
the engine R path's fused traverse+compact adoption (``use_kernel=True``
must be bit-identical to the mask-based path, ServeStats field for field).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import engine, geometry as geo, traversal
from repro.core.device_tree import DeviceTree, Level
from repro.kernels import ops


def _single_level_tree(L=6, seed=5):
    """A degenerate tree whose only level is the leaf level (root == leaf),
    the shape a 1-deep build or a sharding-padded leaf row produces."""
    rng = np.random.default_rng(seed)
    lo = rng.uniform(-1, 1, (L, 2))
    w = rng.uniform(0.1, 0.5, (L, 2))
    mbrs = jnp.asarray(np.concatenate([lo, lo + w], 1).astype(np.float32))
    tree = DeviceTree(
        levels=(Level(mbrs=mbrs, parent=jnp.zeros((L,), jnp.int32)),),
        leaf_entries=jnp.full((L, 2, 8), jnp.inf, jnp.float32),
        leaf_entry_ids=jnp.full((L, 8), -1, jnp.int32),
        leaf_counts=jnp.zeros((L,), jnp.int32),
        n_points=0, max_entries=8)
    # one query per leaf, slightly inflated so query i covers leaf i (and
    # possibly neighbours — the point is rows differ from column 0)
    q = jnp.asarray(np.concatenate([lo - 0.01, lo + w + 0.01], 1)
                    .astype(np.float32))
    return tree, q, mbrs


@pytest.mark.parametrize("use_kernel", [False, True])
def test_single_level_tree_visited_mask(use_kernel):
    """Regression: a 1-level tree's visited mask is the plain intersection;
    the old engine-inline loop returned column 0 broadcast across the
    row. The serve step's traversal entry point must handle the shape."""
    tree, q, mbrs = _single_level_tree()
    exp = np.asarray(geo.jnp_cross_intersects(q, mbrs))
    got = np.asarray(
        traversal.visited_leaf_mask(tree, q, use_kernel=use_kernel))
    np.testing.assert_array_equal(got, exp)
    # the bug was invisible only when every row matched column 0 — make
    # sure this fixture actually discriminates
    buggy = exp[:, [0] * exp.shape[1]] & exp
    assert not np.array_equal(buggy, exp), "fixture too weak to catch bug"


def test_single_level_tree_per_level_and_compact():
    """visited_leaf_mask_per_level and the compacted variants agree on the
    degenerate single-level shape (audit from the same regression)."""
    tree, q, mbrs = _single_level_tree()
    exp = np.asarray(geo.jnp_cross_intersects(q, mbrs))
    np.testing.assert_array_equal(
        np.asarray(traversal.visited_leaf_mask_per_level(tree, q)), exp)
    np.testing.assert_array_equal(
        np.asarray(ops.traverse_fused(
            q, [lv.mbrs for lv in tree.levels],
            [lv.parent for lv in tree.levels])), exp)
    exp_i, exp_v, exp_c = traversal.compact_mask_counted(jnp.asarray(exp), 4)
    for use_kernel in (False, True):
        cv = traversal.visited_leaves_compact(tree, q, 4,
                                              use_kernel=use_kernel)
        np.testing.assert_array_equal(np.asarray(cv.leaf_idx),
                                      np.asarray(exp_i))
        np.testing.assert_array_equal(np.asarray(cv.valid),
                                      np.asarray(exp_v))
        np.testing.assert_array_equal(np.asarray(cv.n_visited),
                                      np.asarray(exp_c))


def test_engine_r_path_kernel_bit_identical():
    """make_serve_step with use_kernel=True (fused traverse+compact +
    scalar-prefetch refine) == use_kernel=False, every ServeStats field.

    Deliberately NOT marked slow: this is the only in-process coverage of
    the rewired shard_map serve path, so it must run in the per-PR fast
    selection (the 8-fake-device subprocess equivalence stays nightly).
    """
    from repro.core import build, device_tree as dt, labels
    from repro.core.rtree import RTree
    from repro.data import synth
    from repro.launch import mesh as pmesh

    pts = synth.tweets_like(3000, seed=0)
    tree = RTree(max_entries=32).insert_all(pts)
    dtree = dt.flatten(tree)
    qs = synth.synth_queries(pts, 1e-4, 200, seed=1)
    wl = labels.make_workload(dtree, qs)
    hyb, _ = build.fit_airtree(dtree, wl, kind="knn", grid_sizes=(6,))
    mesh = pmesh.make_mesh((1, 1, 1), ("pod", "data", "model"))
    q = jnp.asarray(wl.queries[:64])
    stats = {}
    for uk in (False, True):
        step = engine.make_serve_step(mesh, engine.EngineConfig(
            max_visited=64, max_pred=32, use_kernel=uk), kind="knn")
        with jax.set_mesh(mesh):
            stats[uk] = step(hyb, q)
    for f in stats[False]._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(stats[False], f)),
            np.asarray(getattr(stats[True], f)), err_msg=f)
