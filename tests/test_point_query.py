"""Point-query fast path: degenerate rects, single-cell AI routing.

A zero-extent query overlaps exactly one grid cell, so ``point_query``
serves it with ``max_cells=1`` and narrowed traversal bounds, and — with
no wide tier behind it — must be *provably* exact: zero truncated rows,
counts matching brute-force f32 containment, and results identical to
the full-width ``hybrid_query`` on the same rows.
"""
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import build, device_tree as dt, engine, hybrid, labels, \
    schedule
from repro.core import geometry as geo
from repro.core.rtree import RTree
from repro.data import synth
from repro.launch import mesh as pmesh


@functools.lru_cache(maxsize=None)
def _world():
    pts = synth.tweets_like(3000, seed=0)
    dtree = dt.flatten(RTree(max_entries=16).insert_all(pts))
    qs = synth.synth_queries(pts, 2e-3, 160, seed=1)
    wl = labels.make_workload(dtree, qs)
    hyb, _ = build.fit_airtree(dtree, wl, kind="knn", grid_sizes=(6,))
    return pts, hyb


def _point_queries(pts, rng, n, n_miss=8):
    hit = pts[rng.integers(0, pts.shape[0], n - n_miss)].astype(np.float32)
    miss = rng.uniform(200.0, 300.0, (n_miss, 2)).astype(np.float32)
    p = np.concatenate([hit, miss])
    rng.shuffle(p)
    return np.concatenate([p, p], axis=1)


def _brute_counts(pts, q):
    bf = pts.astype(np.float32)
    return geo.np_contains_point(q[:, None, :],
                                 bf[None, :, :]).sum(axis=1)


# ---------------------------------------------------------------------------
# detection twins
# ---------------------------------------------------------------------------

def test_point_mask_twins_agree():
    rng = np.random.default_rng(0)
    lo = rng.uniform(-1, 1, (40, 2)).astype(np.float32)
    w = rng.uniform(0, 0.2, (40, 2)).astype(np.float32)
    w[rng.uniform(size=40) < 0.5] = 0.0
    q = np.concatenate([lo, lo + w], axis=1)
    host = schedule.point_query_mask(q)
    dev = np.asarray(hybrid.is_point_query(jnp.asarray(q)))
    np.testing.assert_array_equal(host, dev)
    assert host.any() and not host.all()


# ---------------------------------------------------------------------------
# exactness
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_kernel", [False, True])
def test_point_query_exact(use_kernel):
    pts, hyb = _world()
    rng = np.random.default_rng(1)
    q = _point_queries(pts, rng, 64)
    res = hybrid.point_query(hyb, jnp.asarray(q), use_kernel=use_kernel)
    assert not np.asarray(res.truncated).any(), \
        "point path truncated — narrowed bounds failed to cover"
    exp = _brute_counts(pts, q)
    np.testing.assert_array_equal(np.asarray(res.n_results), exp)
    assert (exp > 0).sum() >= 48 and (exp == 0).any(), "weak fixture"
    # single-cell routing: a degenerate rect can never overflow the
    # max_cells=1 window, so the anchor cell is always resolved
    assert (np.asarray(res.cell_id) >= 0).all()


def test_point_query_matches_full_width_hybrid():
    """The narrowed bounds change cost, not answers: n_results and
    result id sets equal hybrid_query at full width."""
    pts, hyb = _world()
    rng = np.random.default_rng(2)
    q = jnp.asarray(_point_queries(pts, rng, 48))
    a = hybrid.point_query(hyb, q)
    b = hybrid.hybrid_query(hyb, q, max_visited=256, max_results=512)
    np.testing.assert_array_equal(np.asarray(a.n_results),
                                  np.asarray(b.n_results))
    ida, idb = np.asarray(a.result_ids), np.asarray(b.result_ids)
    for j in range(ida.shape[0]):
        assert (set(ida[j][ida[j] >= 0].tolist())
                == set(idb[j][idb[j] >= 0].tolist())), j
    # and it really is cheaper per row on the R-path cost unit
    assert (np.asarray(a.leaf_accesses)
            <= np.asarray(b.leaf_accesses)).all()


def test_point_query_through_scheduler():
    """Full scheduler pass, no wide tier: sorted ≡ unsorted and zero
    truncation (the driver's assert, exercised here)."""
    pts, hyb = _world()
    rng = np.random.default_rng(3)
    q = _point_queries(pts, rng, 53)
    fn = jax.jit(lambda qq: hybrid.point_query(hyb, qq))
    base = schedule.serve_workload(fn, q, batch=16, sort="none")
    srt = schedule.serve_workload(fn, q, batch=16, sort="hilbert")
    for f in type(base.stats)._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(base.stats, f)),
            np.asarray(getattr(srt.stats, f)), err_msg=f)
    assert not np.asarray(srt.stats.truncated).any()
    np.testing.assert_array_equal(np.asarray(srt.stats.n_results),
                                  _brute_counts(pts, q))


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

def test_point_config_narrows():
    cfg = engine.EngineConfig(max_visited=64, max_cells=4)
    pc = engine.point_config(cfg)
    assert pc.max_cells == 1 and pc.max_visited == 32
    # an already-narrow config is not widened
    assert engine.point_config(engine.EngineConfig(max_visited=8)) \
        .max_visited == 8


def test_engine_point_serve_step_exact():
    pts, hyb = _world()
    rng = np.random.default_rng(4)
    q = jnp.asarray(_point_queries(pts, rng, 64))
    mesh = pmesh.make_mesh((1, 1, 1), ("pod", "data", "model"))
    cfg = engine.EngineConfig(max_visited=64, max_pred=16)
    step = engine.make_point_serve_step(mesh, cfg, kind="knn")
    with jax.set_mesh(mesh):
        out = step(hyb, q)
    assert not np.asarray(out.r_truncated).any()
    np.testing.assert_array_equal(np.asarray(out.n_results),
                                  _brute_counts(pts, np.asarray(q)))
    assert (np.asarray(out.cell_id) >= 0).all()
