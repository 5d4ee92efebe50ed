"""Spatial batch scheduler: permutation identity + two-tier re-serve.

The scheduler's whole contract is that it is *invisible* in the results:
key-sorted serving must be a bit-identical permutation of unsorted serving
(per-query results and counts), including ragged tails and the degenerate
root == leaf tree, and the wide-tier re-serve must clear ``r_truncated``
without touching non-overflow rows.
"""
from typing import NamedTuple

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import engine, schedule, traversal
from repro.core.device_tree import DeviceTree, Level
from repro.kernels import ops, ref
from tests.helpers.hypo import given, settings, st


def _queries(n, seed=0, big_frac=0.0, span=2.0):
    rng = np.random.default_rng(seed)
    lo = rng.uniform(-1, 1, (n, 2))
    w = rng.uniform(0, 0.1, (n, 2))
    big = rng.uniform(size=n) < big_frac
    w[big] = rng.uniform(0.5, span, (int(big.sum()), 2))
    return np.concatenate([lo, lo + w], 1).astype(np.float32)


def _tree(L=64, fanout=4, seed=0):
    from repro.data.synth_tree import synth_levels
    rng = np.random.default_rng(seed)
    mbrs, parents = synth_levels(L, fanout, rng, str_pack=True)
    entries = jnp.asarray(rng.uniform(-1, 1, (L, 2, 8)), jnp.float32)
    return DeviceTree(
        levels=tuple(Level(mbrs=jnp.asarray(m), parent=jnp.asarray(p))
                     for m, p in zip(mbrs, parents)),
        leaf_entries=entries,
        leaf_entry_ids=jnp.arange(L * 8, dtype=jnp.int32).reshape(L, 8),
        leaf_counts=jnp.full((L,), 8, jnp.int32),
        n_points=L * 8, max_entries=fanout)


def _single_level_tree(L=6, seed=5):
    rng = np.random.default_rng(seed)
    lo = rng.uniform(-1, 1, (L, 2))
    w = rng.uniform(0.1, 0.5, (L, 2))
    mbrs = jnp.asarray(np.concatenate([lo, lo + w], 1).astype(np.float32))
    return DeviceTree(
        levels=(Level(mbrs=mbrs, parent=jnp.zeros((L,), jnp.int32)),),
        leaf_entries=jnp.asarray(
            rng.uniform(-1, 1, (L, 2, 8)), jnp.float32),
        leaf_entry_ids=jnp.arange(L * 8, dtype=jnp.int32).reshape(L, 8),
        leaf_counts=jnp.full((L,), 8, jnp.int32),
        n_points=L * 8, max_entries=8)


# ---------------------------------------------------------------------------
# spatial_key kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("curve", ["hilbert", "morton"])
@pytest.mark.parametrize("n", [1, 7, 128, 333])
def test_spatial_key_kernel_matches_ref(curve, n):
    """ops.spatial_key (padded kernel dispatch) == jnp reference."""
    q = jnp.asarray(_queries(n, seed=3))
    bbox = jnp.asarray(schedule.workload_bbox(np.asarray(q)))
    got = np.asarray(ops.spatial_key(q, bbox=bbox, curve=curve))
    c = (q[:, :2] + q[:, 2:]) / 2
    span = jnp.maximum(bbox[2:] - bbox[:2], 1e-12)
    cxy = (c - bbox[None, :2]) / span[None, :]
    exp = np.asarray(ref.spatial_key(cxy, curve=curve))
    np.testing.assert_array_equal(got, exp)


def test_hilbert_sort_improves_locality():
    """Sorted-adjacent query centers are much closer than arrival order —
    the property the whole scheduling layer exists to manufacture."""
    q = _queries(512, seed=1)
    c = (q[:, :2] + q[:, 2:]) / 2
    d_arrival = np.linalg.norm(np.diff(c, axis=0), axis=1).mean()
    for curve in ("hilbert", "morton"):
        sched = schedule.make_schedule(q, batch=64, sort=curve)
        d_sorted = np.linalg.norm(
            np.diff(c[sched.order], axis=0), axis=1).mean()
        assert d_sorted < 0.5 * d_arrival, (curve, d_sorted, d_arrival)


# ---------------------------------------------------------------------------
# schedule formation
# ---------------------------------------------------------------------------

@given(st.integers(1, 200), st.integers(1, 70), st.booleans())
@settings(max_examples=25, deadline=None)
def test_schedule_is_permutation(n, batch, hilbert):
    q = _queries(n, seed=n)
    sort = "hilbert" if hilbert else "morton"
    sched = schedule.make_schedule(q, batch=batch, sort=sort)
    assert sorted(sched.order.tolist()) == list(range(n))
    np.testing.assert_array_equal(sched.order[sched.inv], np.arange(n))
    assert sched.n_batches == -(-n // batch)
    # batches tile the sorted stream exactly once, tail padded
    seen = []
    for chunk, n_valid in schedule.iter_batches(q, sched):
        assert chunk.shape == (sched.batch, 4)
        seen.append(chunk[:n_valid])
    np.testing.assert_array_equal(np.concatenate(seen), q[sched.order])


def test_sort_none_preserves_arrival_order():
    q = _queries(37)
    sched = schedule.make_schedule(q, batch=8, sort="none")
    np.testing.assert_array_equal(sched.order, np.arange(37))


# ---------------------------------------------------------------------------
# sorted serving ≡ unsorted serving (bit-identical permutation)
# ---------------------------------------------------------------------------

import functools


@functools.lru_cache(maxsize=None)
def _tree64():
    return _tree(L=64)


def _serve_fn(tree, k=8, max_results=32):
    # range_query_compact is itself jit'd with static bounds, so reusing
    # it across property examples hits the same trace cache
    return lambda q: traversal.range_query_compact(
        tree, q, max_visited=k, max_results=max_results, use_kernel=False)


def _assert_same(a, b):
    for f in type(a)._fields:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)), err_msg=f)


@given(st.integers(3, 90), st.integers(2, 40), st.booleans())
@settings(max_examples=10, deadline=None)
def test_sorted_serving_bit_identical(n, batch, hilbert):
    """Property: for any stream length / batch size (ragged tails
    included), sorted serving returns exactly what unsorted serving
    returns, row for row, field for field."""
    tree = _tree64()
    q = _queries(n, seed=n, big_frac=0.1)
    fn = _serve_fn(tree)
    sort = "hilbert" if hilbert else "morton"
    base = schedule.serve_workload(fn, q, batch=batch, sort="none")
    srt = schedule.serve_workload(fn, q, batch=batch, sort=sort)
    _assert_same(base.stats, srt.stats)
    # and the unsorted scheduled stream equals direct whole-batch serving
    direct = jax.tree.map(np.asarray, fn(jnp.asarray(q[:batch])))
    head = jax.tree.map(lambda a: np.asarray(a)[:batch], base.stats)
    _assert_same(head, jax.tree.map(lambda a: a[:min(batch, n)], direct))


def test_sorted_serving_single_level_tree():
    """Degenerate root == leaf tree through the full scheduler path."""
    tree = _single_level_tree()
    q = _queries(23, seed=9, span=1.0, big_frac=0.3)
    fn = _serve_fn(tree, k=4)
    base = schedule.serve_workload(fn, q, batch=8, sort="none")
    for sort in ("morton", "hilbert"):
        srt = schedule.serve_workload(fn, q, batch=8, sort=sort)
        _assert_same(base.stats, srt.stats)


def test_stream_serves_every_query():
    """No-drop oracle: aggregate n_results over the scheduled stream ==
    unscheduled ground truth for every query (ragged tail included)."""
    tree = _tree64()
    q = _queries(71, seed=2)
    oracle = traversal.range_query(tree, jnp.asarray(q), max_visited=64,
                                   max_results=64, use_kernel=False)
    rep = schedule.serve_workload(_serve_fn(tree, k=64, max_results=64), q,
                                  batch=16, sort="hilbert")
    assert rep.n_queries == 71 and rep.n_batches == 5
    np.testing.assert_array_equal(np.asarray(rep.stats.n_results),
                                  np.asarray(oracle.n_results))


# ---------------------------------------------------------------------------
# two-tier re-serve
# ---------------------------------------------------------------------------

def test_wide_tier_clears_truncation_without_touching_rest():
    """Regression for the ServeStats.r_truncated contract (here at the
    range_query_compact level: field ``truncated``): overflow rows get
    exact wide-tier answers, non-overflow rows are byte-identical."""
    tree = _tree64()
    q = _queries(60, seed=4, big_frac=0.4)   # big rects overflow k=4
    narrow = _serve_fn(tree, k=4, max_results=256)
    wide = _serve_fn(tree, k=64, max_results=256)
    rep_n = schedule.serve_workload(narrow, q, batch=16, sort="hilbert")
    trunc = np.asarray(rep_n.stats.truncated)
    assert trunc.any(), "fixture too weak: nothing overflowed"
    assert not trunc.all(), "fixture too weak: everything overflowed"
    rep = schedule.serve_workload(narrow, q, batch=16, sort="hilbert",
                                  wide_fn=wide, trunc_field="truncated")
    assert rep.n_reserved == int(trunc.sum())
    assert not np.asarray(rep.stats.truncated).any()
    # overflow rows now exact
    oracle = traversal.range_query(tree, jnp.asarray(q), max_visited=64,
                                   max_results=256, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(rep.stats.n_results),
                                  np.asarray(oracle.n_results))
    # non-overflow rows untouched by the merge
    keep = ~trunc
    for f in type(rep.stats)._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(rep.stats, f))[keep],
            np.asarray(getattr(rep_n.stats, f))[keep], err_msg=f)


def test_engine_two_tier_clears_r_truncated():
    """End-to-end ServeStats contract: make_two_tier_steps + scheduler.
    The narrow tier's r_truncated rows are re-served wide; merged stats
    carry exact counts everywhere and no residual truncation."""
    from repro.core import build, device_tree as dt, labels
    from repro.core.rtree import RTree
    from repro.data import synth
    from repro.launch import mesh as pmesh

    pts = synth.tweets_like(3000, seed=0)
    rtree = RTree(max_entries=16).insert_all(pts)
    dtree = dt.flatten(rtree)
    qs = synth.synth_queries(pts, 2e-3, 120, seed=1)
    wl = labels.make_workload(dtree, qs)
    hyb, _ = build.fit_airtree(dtree, wl, kind="knn", grid_sizes=(6,))
    mesh = pmesh.make_mesh((1, 1, 1), ("pod", "data", "model"))
    cfg = engine.EngineConfig(max_visited=4, max_pred=32)
    narrow, wide = engine.make_two_tier_steps(mesh, cfg, kind="knn",
                                              wide_factor=64)
    with jax.set_mesh(mesh):
        nf = jax.jit(lambda q: narrow(hyb, q))
        wf = jax.jit(lambda q: wide(hyb, q))
        rep_n = schedule.serve_workload(nf, wl.queries, batch=32,
                                        sort="hilbert")
        trunc = np.asarray(rep_n.stats.r_truncated)
        assert trunc.any(), "fixture too weak: nothing overflowed"
        rep = schedule.serve_workload(nf, wl.queries, batch=32,
                                      sort="hilbert", wide_fn=wf)
    assert rep.n_reserved == int(trunc.sum())
    assert not np.asarray(rep.stats.r_truncated).any()
    np.testing.assert_array_equal(np.asarray(rep.stats.n_results),
                                  wl.n_results)
    keep = ~trunc
    for f in type(rep.stats)._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(rep.stats, f))[keep],
            np.asarray(getattr(rep_n.stats, f))[keep], err_msg=f)


# ---------------------------------------------------------------------------
# degenerate workloads: zero-extent bbox must still produce valid keys
# ---------------------------------------------------------------------------

def test_workload_bbox_guards_zero_extent():
    """A single query / coincident centers collapse the center bbox to a
    point; the guard must widen it to positive area (a zero span would
    push the key normalization onto an epsilon clamp that amplifies f32
    rounding into arbitrary key orderings)."""
    q = _queries(1, seed=0)
    bbox = schedule.workload_bbox(q)
    assert bbox[2] - bbox[0] > 0 and bbox[3] - bbox[1] > 0
    # coincident centers along one axis only: that axis alone widens
    qx = _queries(8, seed=1)
    qx[:, 1] = 0.25
    qx[:, 3] = 0.25     # all centers share y = 0.25
    bbox = schedule.workload_bbox(qx)
    assert bbox[3] - bbox[1] == pytest.approx(1.0)
    c = (qx[:, :2] + qx[:, 2:]) / 2
    assert bbox[2] - bbox[0] == pytest.approx(
        c[:, 0].max() - c[:, 0].min(), abs=1e-5)


@pytest.mark.parametrize("curve", ["hilbert", "morton"])
def test_degenerate_workload_keys_valid(curve):
    """All-coincident centers → one shared key; single query → key
    computable; a degenerate caller-passed bbox gets the same guard."""
    q1 = _queries(1, seed=2)
    k1 = schedule.spatial_keys(q1, curve)
    assert k1.shape == (1,) and k1.dtype == np.int32
    qc = np.repeat(q1, 7, axis=0)
    kc = schedule.spatial_keys(qc, curve)
    assert np.unique(kc).size == 1     # coincident centers, one curve cell
    # caller-passed zero-extent bbox (not via workload_bbox)
    flat = np.array([0.5, 0.5, 0.5, 0.5], np.float32)
    kf = schedule.spatial_keys(qc, curve, bbox=flat)
    np.testing.assert_array_equal(kf, np.full((7,), kf[0]))
    # and the full scheduling + serving path stays well-formed
    tree = _tree64()
    sched = schedule.make_schedule(qc, batch=4, sort=curve)
    assert sorted(sched.order.tolist()) == list(range(7))
    rep = schedule.serve_workload(_serve_fn(tree), qc, batch=4, sort=curve)
    base = schedule.serve_workload(_serve_fn(tree), qc, batch=4,
                                   sort="none")
    _assert_same(rep.stats, base.stats)


# ---------------------------------------------------------------------------
# serve_workload edges the streaming runtime leans on
# ---------------------------------------------------------------------------

def test_two_tier_with_empty_truncated_set():
    """wide_fn wired but nothing overflows: the wide tier must not fire
    (no re-served rows, no wide batches) and results must equal the
    narrow-only stream byte for byte."""
    tree = _tree64()
    q = _queries(40, seed=6)            # small rects: k=64 never overflows
    narrow = _serve_fn(tree, k=64, max_results=256)
    calls = []

    def wide(batch_q):
        calls.append(1)
        return narrow(batch_q)

    rep = schedule.serve_workload(narrow, q, batch=16, sort="hilbert",
                                  wide_fn=wide, trunc_field="truncated")
    assert not np.asarray(rep.stats.truncated).any()
    assert rep.n_reserved == 0 and rep.wide_batches == 0
    assert not calls, "wide tier served an empty re-serve set"
    base = schedule.serve_workload(narrow, q, batch=16, sort="hilbert")
    _assert_same(rep.stats, base.stats)


def test_serve_workload_batch_one():
    """batch=1: every batch is a single query (the runtime's deadline
    dispatch degenerates to this under extreme pressure) — permutation,
    two-tier merge, and padding must all hold."""
    tree = _tree64()
    q = _queries(13, seed=8, big_frac=0.4)
    narrow = _serve_fn(tree, k=4, max_results=256)
    wide = _serve_fn(tree, k=64, max_results=256)
    rep = schedule.serve_workload(narrow, q, batch=1, sort="hilbert",
                                  wide_fn=wide, trunc_field="truncated")
    assert rep.n_batches == 13
    ref = schedule.serve_workload(narrow, q, batch=8, sort="none",
                                  wide_fn=wide, trunc_field="truncated")
    _assert_same(rep.stats, ref.stats)
    assert not np.asarray(rep.stats.truncated).any()


def test_heterogeneous_point_range_stream_bit_identical():
    """A mixed point/range stream served through a per-row dispatching
    step (degenerate rects take narrowed bounds, range rects the full
    ones) keeps the scheduler contract: sorted serving is a bit-identical
    inverse-permutation of unsorted serving, and both equal direct
    whole-stream serving — batch composition (which rows of each type
    land together) must not leak into any result field."""
    from repro.core import hybrid as hybmod
    from tests.test_point_query import _world

    pts, hyb = _world()
    rng = np.random.default_rng(13)
    q = _queries(57, seed=13)
    pt = rng.uniform(size=57) < 0.5
    # point rows: degenerate rects at real dataset points (so the point
    # path has hits); range rows keep their rects
    hitp = pts[rng.integers(0, pts.shape[0], int(pt.sum()))].astype(
        np.float32)
    q[pt, :2] = hitp
    q[pt, 2:] = hitp
    assert pt.any() and not pt.all()
    np.testing.assert_array_equal(schedule.point_query_mask(q), pt)

    def fn(batch_q):
        isp = hybmod.is_point_query(batch_q)
        pr = hybmod.point_query(hyb, batch_q, max_visited=16,
                                max_results=32)
        rr = hybmod.hybrid_query(hyb, batch_q, max_visited=64,
                                 max_results=32)
        return jax.tree.map(
            lambda a, b: jnp.where(
                isp.reshape((-1,) + (1,) * (a.ndim - 1)), a, b),
            pr, rr)

    base = schedule.serve_workload(fn, q, batch=16, sort="none")
    for sort in ("hilbert", "morton"):
        srt = schedule.serve_workload(fn, q, batch=16, sort=sort)
        _assert_same(base.stats, srt.stats)
    # inverse-permutation restoration == direct whole-stream serving
    direct = jax.tree.map(np.asarray, fn(jnp.asarray(q)))
    _assert_same(base.stats, direct)
    # the dispatch is actually heterogeneous *within* sorted batches,
    # not just across the stream — otherwise this tests nothing new
    sched = schedule.make_schedule(q, batch=16, sort="hilbert")
    per_batch = [pt[sched.order[i:i + 16]]
                 for i in range(0, 57, 16)]
    assert any(m.any() and not m.all() for m in per_batch), \
        "fixture too weak: batches are type-homogeneous"


def test_two_tier_final_ragged_batch_all_overflow():
    """The final ragged batch overflows on every valid row: the merge
    must replace exactly those rows (pad rows dropped, non-overflow rows
    from earlier batches untouched)."""
    tree = _tree64()
    q_small = _queries(16, seed=10)                  # fills one batch
    q_big = _queries(3, seed=12, big_frac=1.0)       # ragged tail
    q_big[:, 2:] = q_big[:, :2] + 1.5                # guarantee overflow
    q = np.concatenate([q_small, q_big])
    narrow = _serve_fn(tree, k=2, max_results=256)
    wide = _serve_fn(tree, k=64, max_results=256)
    rep_n = schedule.serve_workload(narrow, q, batch=16, sort="none")
    trunc = np.asarray(rep_n.stats.truncated).astype(bool)
    assert trunc[16:].all(), "fixture too weak: tail row not truncated"
    rep = schedule.serve_workload(narrow, q, batch=16, sort="none",
                                  wide_fn=wide, trunc_field="truncated")
    assert rep.n_reserved == int(trunc.sum())
    assert not np.asarray(rep.stats.truncated).any()
    keep = ~trunc
    for f in type(rep.stats)._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(rep.stats, f))[keep],
            np.asarray(getattr(rep_n.stats, f))[keep], err_msg=f)
    # overflow rows exact vs the unbounded oracle
    oracle = traversal.range_query(tree, jnp.asarray(q), max_visited=64,
                                   max_results=256, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(rep.stats.n_results),
                                  np.asarray(oracle.n_results))


# ---------------------------------------------------------------------------
# host spans and counters of serve_workload
# ---------------------------------------------------------------------------

import contextlib

from repro.core import telemetry


class _SpanRecorder:
    """Stands in for the profiler's span factory: records each span's
    name, attributes and parent (the span open around it)."""

    def __init__(self):
        self.spans = []     # [(name, attrs, parent index or None)]
        self._open = []

    @contextlib.contextmanager
    def __call__(self, name, **attrs):
        self.spans.append((name, attrs,
                           self._open[-1] if self._open else None))
        self._open.append(len(self.spans) - 1)
        try:
            yield
        finally:
            self._open.pop()


def _tier_spans(tier, request, rows, batch, parent):
    """The spans one tier's pass over ``rows`` queries writes."""
    n_batches = -(-rows // batch)
    tag = {"request": request, "tier": tier}
    out = [("serve.keys", dict(rows=rows, **tag), parent),
           ("serve.sort", dict(rows=rows, **tag), parent)]
    for b in range(n_batches):
        out += [("serve.step",
                 dict(batch=b, rows=min(batch, rows - b * batch), **tag),
                 parent),
                ("serve.pull", dict(batch=b, **tag), parent)]
    return out + [("serve.unpermute", dict(rows=rows, **tag), parent)]


def _two_tier_stream():
    """60 queries, batch 16: the narrow bound k=4 truncates 17 of them,
    which the wide tier (k=64) re-serves."""
    tree = _tree64()
    q = _queries(60, seed=4, big_frac=0.4)
    narrow = _serve_fn(tree, k=4, max_results=256)
    wide = _serve_fn(tree, k=64, max_results=256)
    trunc = np.asarray(schedule.serve_workload(
        narrow, q, batch=16, sort="hilbert").stats.truncated)
    assert int(trunc.sum()) == 17, "fixture changed"
    return q, narrow, wide


def test_serve_spans_name_tag_and_nest(monkeypatch):
    q, narrow, wide = _two_tier_stream()
    rec = _SpanRecorder()
    monkeypatch.setattr(telemetry, "span_factory", rec)
    schedule.serve_workload(narrow, q, batch=16, sort="hilbert",
                            wide_fn=wide, trunc_field="truncated")
    request = rec.spans[0][1]["request"]
    tag = {"request": request, "tier": "wide", "rows": 17}
    want = [("serve.request", {"request": request, "tier": "narrow",
                               "rows": 60}, None)]
    want += _tier_spans("narrow", request, 60, 16, 0)
    wide_at = len(want)
    want += [("serve.wide", tag, 0)]
    want += _tier_spans("wide", request, 17, 16, wide_at)
    want += [("serve.merge", tag, 0)]
    assert rec.spans == want
    # the next call takes a new request id
    rec.spans.clear()
    schedule.serve_workload(narrow, q[:5], batch=16, sort="hilbert")
    assert rec.spans[0][1]["request"] != request
    assert {a["request"] for _, a, _ in rec.spans} == \
        {rec.spans[0][1]["request"]}


def test_serve_spans_without_a_wide_batch(monkeypatch):
    """Nothing truncates: the wide tier writes no span."""
    tree = _tree64()
    q = _queries(40, seed=6)            # small rects: k=64 never overflows
    narrow = _serve_fn(tree, k=64, max_results=256)
    rec = _SpanRecorder()
    monkeypatch.setattr(telemetry, "span_factory", rec)
    rep = schedule.serve_workload(narrow, q, batch=16, sort="hilbert",
                                  wide_fn=narrow, trunc_field="truncated")
    assert rep.wide_batches == 0
    request = rec.spans[0][1]["request"]
    assert rec.spans == [("serve.request", {"request": request,
                                            "tier": "narrow", "rows": 40},
                          None)] + _tier_spans("narrow", request, 40, 16, 0)
    # 3 batches of 16 rows for 40 queries; each row of a k=64,
    # max_results=256 step returns 5·64 + 4·256 + 13 bytes
    assert (rep.pad_rows, rep.wide_pad_rows) == (8, 0)
    assert rep.pulled_bytes == 3 * 16 * (5 * 64 + 4 * 256 + 13)


def test_serve_report_counts_pad_rows_and_pulled_bytes():
    q, narrow, wide = _two_tier_stream()
    rep = schedule.serve_workload(narrow, q, batch=16, sort="hilbert",
                                  wide_fn=wide, trunc_field="truncated")
    assert (rep.n_batches, rep.n_reserved, rep.wide_batches) == (4, 17, 2)
    assert rep.pad_rows == 4 * 16 - 60
    assert rep.wide_pad_rows == 2 * 16 - 17
    # a row of range_query_compact at bound k and max_results m returns
    # leaf_idx i32[k], valid bool[k], three i32 counts, result_ids
    # i32[m] and one bool: 5k + 4m + 13 bytes
    narrow_row, wide_row = 5 * 4 + 4 * 256 + 13, 5 * 64 + 4 * 256 + 13
    assert (narrow_row, wide_row) == (1057, 1357)
    assert rep.pulled_bytes == 4 * 16 * narrow_row + 2 * 16 * wide_row
    # one tier alone: no wide rows, no wide padding
    alone = schedule.serve_workload(narrow, q, batch=16, sort="hilbert")
    assert (alone.pad_rows, alone.wide_pad_rows, alone.pulled_bytes) == \
        (4, 0, 4 * 16 * narrow_row)


def test_served_log_keeps_each_calls_counts_without_stats():
    q, narrow, wide = _two_tier_stream()
    before = len(schedule.SERVED)
    reps = [schedule.serve_workload(narrow, q, batch=16, sort="hilbert",
                                    wide_fn=wide, trunc_field="truncated"),
            schedule.serve_workload(narrow, q[:20], batch=16, sort="none")]
    # one entry per top-level call (the wide tier adds none), newest last
    assert len(schedule.SERVED) == min(before + 2, schedule.SERVED.maxlen)
    logged = list(schedule.SERVED)[-2:]
    assert all(r.stats is None for r in logged)
    assert logged == [r._replace(stats=None) for r in reps]
    assert (logged[1].pad_rows, logged[1].wide_pad_rows) == (12, 0)


class _HitStats(NamedTuple):
    n_results: np.ndarray
    result_ids: np.ndarray
    truncated: np.ndarray


def _hit_step(max_results):
    """A step whose rows hold the hit count written in each query's first
    coordinate, with a result-id table ``max_results`` wide."""
    def step(q):
        hits = np.asarray(q)[:, 0].astype(np.int32)
        return _HitStats(
            n_results=hits,
            result_ids=np.zeros((hits.size, max_results), np.int32),
            truncated=hits > max_results)
    return step


def test_serve_report_counts_gather_chunks():
    """Batches of 4 in submission order, narrow table 256 wide, wide
    table 1024: ceil(min(max hits, width) / C) chunks per step."""
    C = traversal.GATHER_CHUNK
    assert C == 128, "hand count below assumes 128"
    hits = [3, 0, 1, 0,          # max 3: 1 chunk
            127, 128, 2, 2,      # max 128: 1
            129, 300, 2000, 7,   # max 2000, table 256: 2
            0, 0]                # max 0 (pads repeat a 0 row): 0
    q = np.zeros((len(hits), 4), np.float32)
    q[:, 0] = q[:, 2] = hits
    rep = schedule.serve_workload(_hit_step(256), q, batch=4, sort="none",
                                  wide_fn=_hit_step(1024),
                                  trunc_field="truncated")
    # 300 and 2000 pass the narrow table: one wide batch, its pad rows
    # repeating 2000, table 1024: 8 chunks
    assert (rep.n_batches, rep.n_reserved, rep.wide_batches) == (4, 2, 1)
    assert rep.gather_chunks == (1 + 1 + 2 + 0) + 8
    assert list(schedule.SERVED)[-1].gather_chunks == 12


def test_gather_chunks_one_per_step_at_few_hits():
    """Every row of the real compact R path holds at most C hits: one
    chunk per step of both tiers."""
    tree = _tree64()
    q = _queries(60, seed=4, big_frac=0.4, span=0.6)
    rep = schedule.serve_workload(
        _serve_fn(tree, k=4, max_results=256), q, batch=16, sort="hilbert",
        wide_fn=_serve_fn(tree, k=64, max_results=256),
        trunc_field="truncated")
    assert (rep.n_batches, rep.n_reserved, rep.wide_batches) == (4, 8, 1)
    n = np.asarray(rep.stats.n_results)
    assert 0 < n.max() <= traversal.GATHER_CHUNK
    assert rep.gather_chunks == rep.n_batches + rep.wide_batches == 5
