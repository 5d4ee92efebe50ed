"""Freshness subsystem tests: delta store, probe kernel, guard, repack.

The correctness anchor: serving with a populated delta buffer must be
bit-identical to serving a from-scratch ``str_bulk`` tree containing the
same points (result counts and result-id sets — structural stats like
visit counts legitimately differ between the two trees), and the online
repack must preserve that. The delta path must add no dense ``[B, cap]``
containment mask to the serving HLO. The guard must recover the silently
dropped hits of an ``exact_fit < 1`` bank while leaving exact-fit banks'
dispatch unchanged.
"""
import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import build, device_tree as dt, engine, labels
from repro.core import delta as deltalib
from repro.core import geometry as geo
from repro.core.hybrid import hybrid_query
from repro.core.monitor import FreshServer, FreshnessMonitor
from repro.core.rtree import RTree
from repro.data import synth
from repro.kernels import delta_probe as dpk
from repro.kernels import ops, ref
from tests.helpers.banks import synth_bank, synth_hybrid
from tests.helpers.hypo import given, settings, st

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))


def _buffer(rng, cap, fill):
    pts = np.full((cap, 2), np.inf, np.float32)
    pts[:fill] = rng.uniform(-1, 1, (fill, 2))
    return jnp.asarray(pts)


def _rects(rng, B, w=0.5):
    lo = rng.uniform(-1, 1, (B, 2))
    wd = rng.uniform(0, w, (B, 2))
    return jnp.asarray(np.concatenate([lo, lo + wd], 1), jnp.float32)


# ---------------------------------------------------------------------------
# kernel vs oracle, both forms + ops wrapper
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,cap,fill,k", [
    (37, 300, 211, 8),     # nothing tile-aligned, partial fill
    (64, 1000, 1000, 16),  # full buffer, multi-tile shapes
    (8, 100, 0, 4),        # empty buffer
])
def test_ops_wrapper_matches_oracle(B, cap, fill, k):
    rng = np.random.default_rng(3)
    q = _rects(rng, B)
    pts = _buffer(rng, cap, fill)
    exp = ref.delta_probe(q, pts, k)
    got = ops.delta_probe(q, pts, k=k)
    for g, e, name in zip(got, exp, ("idx", "valid", "count")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(e),
                                      err_msg=name)
    if fill == 0:
        assert not np.asarray(got[2]).any(), "empty buffer must hit nothing"


@pytest.mark.parametrize("tpu_form", [True, False])
@pytest.mark.parametrize("cap,tn", [
    (1000, 256),   # multi-buffer-tile: rank base carried across j
    (200, 128),
])
def test_kernel_forms_match_oracle(cap, tn, tpu_form):
    """Both kernel forms (chunked rank-equality scatter on the TPU graph,
    searchsorted on the interpret graph) against the dense oracle, with
    the compaction rank base exercised across buffer tiles, a no-hit row
    and padding tiles (+inf) mixed in."""
    rng = np.random.default_rng(5)
    B, k, fill = 21, 8, cap - cap // 4
    q = _rects(rng, B)
    q = q.at[0].set(jnp.asarray([5.0, 5.0, 6.0, 6.0]))  # hits nothing
    pts = _buffer(rng, cap, fill)
    exp = ref.delta_probe(q, pts, k)

    tb = (B + 7) // 8 * 8
    qp = jnp.concatenate([q, jnp.zeros((tb - B, 4), jnp.float32)])
    Np = (cap + tn - 1) // tn * tn
    pp = jnp.concatenate(
        [pts, jnp.full((Np - cap, 2), jnp.inf, jnp.float32)])
    idx, cnt = dpk.delta_probe_t(qp.T, pp.T, k=k, tb=tb, tn=tn,
                                 interpret=True, tpu_form=tpu_form)
    count = np.asarray(cnt)[:B, 0]
    np.testing.assert_array_equal(count, np.asarray(exp[2]))
    valid = np.arange(k)[None, :] < count[:, None]
    np.testing.assert_array_equal(
        np.where(valid, np.asarray(idx)[:B, :k], 0), np.asarray(exp[0]))
    assert (np.asarray(idx)[:B, :k][~valid] == 0).all()
    assert not count[0], "no-hit row must probe empty"


def test_exactly_k_and_overflow_boundary():
    """A row hitting exactly k buffer points must not overflow; k-1 slots
    must — and the count stays the *full* hit total either way (result
    counts never truncate)."""
    rng = np.random.default_rng(7)
    cap, m = 64, 5
    pts = np.full((cap, 2), np.inf, np.float32)
    pts[:m] = rng.uniform(0.2, 0.4, (m, 2))        # all inside the query
    pts = jnp.asarray(pts)
    q = jnp.asarray([[0.0, 0.0, 1.0, 1.0]], jnp.float32)
    for k, over in ((m, False), (m - 1, True)):
        idx, valid, count = ops.delta_probe(q, pts, k=k)
        assert int(count[0]) == m
        assert int(np.asarray(valid).sum()) == min(m, k)
        assert bool(count[0] > k) == over


def test_escape_hatch_and_vmem_gate(monkeypatch):
    """Kernels-off and over-VMEM-budget rungs of the fallback ladder stay
    bit-identical to the kernel path."""
    from repro.kernels import traverse_fused as tf
    rng = np.random.default_rng(11)
    q = _rects(rng, 19)
    pts = _buffer(rng, 250, 180)
    base = ops.delta_probe(q, pts, k=8)
    monkeypatch.setenv("REPRO_KERNELS", "off")
    got_off = ops.delta_probe(q, pts, k=8)
    monkeypatch.delenv("REPRO_KERNELS")
    real = tf.VMEM_BUDGET
    try:
        tf.VMEM_BUDGET = 1
        got_gate = ops.delta_probe(q, pts, k=8)
    finally:
        tf.VMEM_BUDGET = real
    for got in (got_off, got_gate):
        for g, e in zip(got, base):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(e))


# ---------------------------------------------------------------------------
# store mechanics
# ---------------------------------------------------------------------------

def test_stage_append_ids_and_overflow():
    rng = np.random.default_rng(0)
    store = deltalib.make_delta(16, base=100)
    a = rng.uniform(-1, 1, (10, 2))
    store = deltalib.stage_inserts(store, a)
    assert store.n == 10 and store.base == 100
    np.testing.assert_allclose(deltalib.staged_points(store),
                               a.astype(np.float32))
    assert np.isinf(np.asarray(store.xy)[10:]).all()
    store = deltalib.stage_inserts(store, rng.uniform(-1, 1, (6, 2)))
    assert store.n == 16
    with pytest.raises(ValueError, match="overflow"):
        deltalib.stage_inserts(store, rng.uniform(-1, 1, (1, 2)))
    # probe ids continue the tree's numbering: base + slot
    q = jnp.asarray([[-1, -1, 1, 1]], jnp.float32)
    hits = deltalib.probe(store.xy, q, k=16, base=store.base)
    ids = np.asarray(hits.ids)[0]
    assert set(ids[ids >= 0]) == set(range(100, 116))


def test_merge_hybrid_result_placement_and_truncation():
    """Delta ids land in the result table's -1 padding after the tree's
    ids; counts add exactly; rows whose merged ids no longer fit (or
    whose hits overflow the probe slots) raise ``truncated``."""
    from repro.core.hybrid import HybridResult
    B, mr, k = 3, 6, 4
    z = jnp.zeros((B,), jnp.int32)
    zb = jnp.zeros((B,), bool)
    rid = jnp.asarray([[7, 8, -1, -1, -1, -1],
                       [-1] * 6,
                       [1, 2, 3, 4, 5, -1]], jnp.int32)
    res = HybridResult(routed_high=zb, used_ai=zb,
                       n_results=jnp.asarray([2, 0, 5], jnp.int32),
                       result_ids=rid, leaf_accesses=z, n_visited_r=z,
                       n_true=z, truncated=zb, guarded=zb,
                       mispredict=zb, cell_id=z - 1)
    hits = deltalib.DeltaHits(
        slot_idx=jnp.asarray([[0, 1, 0, 0], [2, 0, 0, 0], [0, 1, 2, 3]],
                             jnp.int32),
        valid=jnp.asarray([[1, 1, 0, 0], [1, 0, 0, 0], [1, 1, 1, 1]],
                          bool),
        count=jnp.asarray([2, 1, 9], jnp.int32),
        ids=jnp.asarray([[100, 101, -1, -1], [102, -1, -1, -1],
                         [100, 101, 102, 103]], jnp.int32))
    out = deltalib.merge_hybrid_result(res, hits)
    np.testing.assert_array_equal(np.asarray(out.n_results), [4, 1, 14])
    np.testing.assert_array_equal(
        np.asarray(out.result_ids[0]), [7, 8, 100, 101, -1, -1])
    np.testing.assert_array_equal(
        np.asarray(out.result_ids[1]), [102, -1, -1, -1, -1, -1])
    np.testing.assert_array_equal(
        np.asarray(out.result_ids[2]), [1, 2, 3, 4, 5, 100])
    np.testing.assert_array_equal(np.asarray(out.truncated),
                                  [False, False, True])


def test_monitor_staleness_and_repack():
    from repro.core.grid import Grid
    grid = Grid(bbox=jnp.asarray([0.0, 0.0, 1.0, 1.0], jnp.float32), g=2)
    mon = FreshnessMonitor(grid, np.asarray([True, True, False, True]))
    assert mon.cell_ok().tolist() == [True, True, False, True]
    mon.note_inserts(np.asarray([[0.1, 0.1], [0.9, 0.1]]))  # cells 0, 1
    assert mon.cell_ok().tolist() == [False, False, False, True]
    mon.note_repack()      # bulk reload renumbers every leaf: all stale
    assert not mon.cell_ok().any()
    mon.note_refit(np.asarray([True, False, True, True]))
    assert mon.cell_ok().tolist() == [True, False, True, True]
    # out-of-bbox inserts clamp into edge cells (conservative)
    mon.note_inserts(np.asarray([[5.0, 5.0]]))
    assert mon.cell_ok().tolist() == [True, False, True, False]


# ---------------------------------------------------------------------------
# the correctness anchor: inserts→serve ≡ rebuild→serve, repack ≡ rebuild
# ---------------------------------------------------------------------------

def _synth_fresh_world(rng, n_base, n_ins, n_q):
    """Untrained hybrid over a real STR tree: the bank never predicts
    (all queries fall back to the exact R path), so the property is
    pinned on serving mechanics, not training quality."""
    from repro.core.aitree import make_aitree
    from repro.core.classifiers.router import Router
    from repro.core.grid import Grid
    from repro.core.hybrid import HybridTree
    pts = rng.uniform(-1, 1, (n_base + n_ins, 2))
    base, extra = pts[:n_base], pts[n_base:]
    dtree = dt.flatten(RTree.str_bulk(base, max_entries=8))
    bank = synth_bank(rng, 9, dtree.n_leaves, pos_bias=-30.0)
    ait = make_aitree(
        Grid(bbox=jnp.asarray([-1, -1, 1, 1], jnp.float32), g=3), bank,
        max_cells=4, max_pred=8)
    router = Router(
        feat_idx=jnp.asarray(rng.integers(0, 6, (4, 3)), jnp.int32),
        thresh=jnp.asarray(rng.uniform(-1, 1, (4, 3)), jnp.float32),
        tables=jnp.asarray(rng.uniform(0, 1, (4, 8, 1)), jnp.float32),
        tau=0.75)
    hyb = HybridTree(tree=dtree, ait=ait, router=router)
    lo = rng.uniform(-1, 0.8, (n_q, 2))
    w = rng.uniform(0, 0.4, (n_q, 2))
    q = np.concatenate([lo, lo + w], 1).astype(np.float32)
    return base, extra, hyb, q


def _id_sets(result_ids):
    return [sorted(int(x) for x in row if x >= 0)
            for row in np.asarray(result_ids)]


@settings(max_examples=10, deadline=None)
@given(st.integers(40, 300), st.integers(1, 120), st.integers(0, 2**31 - 1))
def test_fresh_serving_equals_rebuild(n_base, n_ins, seed):
    """Property: serve(base tree + staged buffer) ≡ serve(str_bulk over
    all points) — result counts bit-identical, result-id sets identical —
    and after repack the serve is bit-identical on *every* field."""
    rng = np.random.default_rng(seed)
    base, extra, hyb, q = _synth_fresh_world(rng, n_base, n_ins, 16)
    srv = FreshServer(base, hyb, delta_cap=max(8, n_ins),
                      max_visited=256, max_results=512)
    srv.insert(extra)
    qj = jnp.asarray(q)
    fresh = srv.serve(qj)

    rebuilt_tree = dt.flatten(
        RTree.str_bulk(np.concatenate([base, extra]), max_entries=8))
    hyb2 = dataclasses.replace(hyb, tree=rebuilt_tree)
    rebuilt = hybrid_query(hyb2, qj, max_visited=256, max_results=512)
    np.testing.assert_array_equal(np.asarray(fresh.n_results),
                                  np.asarray(rebuilt.n_results))
    assert _id_sets(fresh.result_ids) == _id_sets(rebuilt.result_ids)

    # repack ≡ rebuild: bit-identical on every shared field. The
    # comparator carries the server's own post-repack guard state (all
    # cells stale until a refit — by design), so what's under test is
    # exactly that the swapped tree is a fresh bulk load of the same
    # points.
    srv.repack()
    packed = srv.serve(qj)
    rebuilt2 = hybrid_query(
        dataclasses.replace(srv.hybrid, tree=rebuilt_tree), qj,
        max_visited=256, max_results=512)
    for f in type(rebuilt2)._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(packed, f)),
            np.asarray(getattr(rebuilt2, f)),
            err_msg=f"repack vs rebuild: {f}")


def test_fresh_serving_trained_world():
    """Integration on a *trained* world (real router traffic, AI-path
    answers live): a mixed stream's counts match brute-force containment
    over each segment's visible points (``serve_mixed_workload`` +
    two-tier + guard all engaged)."""
    from repro.core import schedule
    pts = synth.tweets_like(6000, seed=0)
    base, extra = pts[:5400], pts[5400:]
    dtree = dt.flatten(RTree.str_bulk(base, max_entries=32))
    qs = synth.synth_queries(pts, 2e-4, 300, seed=1)
    wl = labels.make_workload(dtree, qs)
    hyb, rep = build.fit_airtree(dtree, wl, kind="knn", grid_sizes=(6,))
    srv = FreshServer(base, hyb, delta_cap=1024, max_visited=64,
                      max_results=256, wide_factor=8)
    mixed = schedule.serve_mixed_workload(
        srv, wl.queries, extra, batch=64, sort="hilbert", insert_every=1,
        repack_every=400)
    assert mixed.n_repacks >= 1
    assert int(np.asarray(mixed.stats.delta_hits).sum()) > 0
    # visibility from the scheduler's own staging report, not re-derived
    got = np.asarray(mixed.stats.n_results)
    for (lo, hi), visible in schedule.visible_segments(mixed, base):
        exp = geo.np_contains_point(
            wl.queries[lo:hi][:, None, :], visible[None, :, :]).sum(axis=1)
        np.testing.assert_array_equal(got[lo:hi], exp,
                                      err_msg=f"segment {lo}:{hi}")


def test_repack_refit_restores_ai_service():
    """Without a refit the whole bank stays guarded after a repack (its
    labels refer to the dead tree). With ``refit_fn`` the monitor resets
    and AI-path service resumes on the rebuilt tree — still exact."""
    pts = synth.tweets_like(3000, seed=5)
    base, extra = pts[:2700], pts[2700:]
    dtree = dt.flatten(RTree(max_entries=32).insert_all(base))
    # selectivity high enough that the refit router still finds
    # high-overlap traffic on the STR-packed post-repack tree
    qs = synth.synth_queries(pts, 5e-4, 200, seed=6)
    wl = labels.make_workload(dtree, qs)
    hyb, _ = build.fit_airtree(dtree, wl, kind="knn", grid_sizes=(6,))

    def refit(dtree_new):
        wl_new = labels.make_workload(dtree_new, qs)
        # a *different* grid size than the initial build: the monitor
        # must re-anchor to the refit hybrid's grid, not assume shapes
        h2, r2 = build.fit_airtree(dtree_new, wl_new, kind="knn",
                                   grid_sizes=(4,))
        return h2, r2.cell_fit

    srv = FreshServer(base, hyb, delta_cap=512, max_visited=256,
                      max_results=512, refit_fn=refit)
    srv.insert(extra)
    assert srv.stats().stale_cells > 0
    srv.repack()
    fs = srv.stats()
    assert fs.n_repacks == 1 and fs.stale_cells == 0 and fs.delta_fill == 0
    out = srv.serve(jnp.asarray(wl.queries))
    assert np.asarray(out.used_ai).any(), "refit must restore AI service"
    exp = geo.np_contains_point(
        wl.queries[:, None, :],
        np.concatenate([base, extra]).astype(np.float32)[None, :, :]
    ).sum(axis=1)
    np.testing.assert_array_equal(np.asarray(out.n_results), exp)


def test_mixed_single_segment_still_stages_inserts():
    """A stream that fits in one segment has no interleave point — the
    inserts must still land in the server (staged after the stream), not
    be silently dropped."""
    from repro.core import schedule
    rng = np.random.default_rng(13)
    base, extra, hyb, q = _synth_fresh_world(rng, 120, 40, 32)
    srv = FreshServer(base, hyb, delta_cap=64, max_visited=256,
                      max_results=512)
    mixed = schedule.serve_mixed_workload(srv, q, extra, batch=64,
                                          sort="none", insert_every=8)
    assert mixed.n_segments == 1
    assert mixed.n_inserts == 40 and srv.delta_fill == 40
    # no query of this stream saw them (visibility is per later segment):
    # the stream matches read-only serving of the base tree exactly
    assert not np.asarray(mixed.stats.delta_hits).any()
    np.testing.assert_array_equal(
        np.asarray(mixed.stats.n_results),
        np.asarray(hybrid_query(hyb, jnp.asarray(q), max_visited=256,
                                max_results=512).n_results))


def test_engine_delta_matches_rebuild():
    """The engine's ``_delta_path`` (1×1×1 mesh, kernel + oracle rungs):
    n_results with a populated buffer == rebuild; delta_hits nonzero."""
    from repro.launch import mesh as pmesh
    pts = synth.tweets_like(6000, seed=2)
    base, extra = pts[:5500], pts[5500:]
    dtree = dt.flatten(RTree.str_bulk(base, max_entries=32))
    qs = synth.synth_queries(pts, 2e-4, 300, seed=3)
    wl = labels.make_workload(dtree, qs)
    hyb, _ = build.fit_airtree(dtree, wl, kind="knn", grid_sizes=(6,))
    store = deltalib.stage_inserts(
        deltalib.make_delta(1024, base=base.shape[0]), extra)
    _, dtree2, _, _ = deltalib.repack(base, store, max_entries=32)
    # the comparator's bank is stale against the rebuilt tree (leaf ids
    # renumbered) — guard every cell so it answers on the exact R path,
    # exactly what the monitor does to a served repack without a refit
    hyb2 = dataclasses.replace(
        hyb, tree=dtree2,
        ait=dataclasses.replace(hyb.ait,
                                cell_ok=jnp.zeros_like(hyb.ait.cell_ok)))
    mesh = pmesh.make_mesh((1, 1, 1), ("pod", "data", "model"))
    q = jnp.asarray(wl.queries[:64])
    for uk in (False, True):
        step = engine.make_serve_step(mesh, engine.EngineConfig(
            max_visited=256, max_pred=32, use_kernel=uk), kind="knn")
        with jax.set_mesh(mesh):
            with_delta = step(hyb, q, store.xy)
            rebuilt = step(hyb2, q)
        np.testing.assert_array_equal(np.asarray(with_delta.n_results),
                                      np.asarray(rebuilt.n_results),
                                      err_msg=f"use_kernel={uk}")
        assert int(np.asarray(with_delta.delta_hits).sum()) > 0


@pytest.mark.slow
def test_distributed_delta_equivalence_subprocess():
    """Engine freshness equivalence on 8 fake devices at a 2×2×2 mesh."""
    script = os.path.join(REPO, "tests", "helpers", "delta_equiv.py")
    out = subprocess.run([sys.executable, script], env=ENV,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "EQUIVALENT" in out.stdout


# ---------------------------------------------------------------------------
# HLO contract: no dense [B, cap] containment mask on the serving path
# ---------------------------------------------------------------------------

def test_delta_probe_never_materializes_mask():
    """On the kernel path the lowered HLO must contain no [B, cap]-shaped
    tensor (cap deliberately not lane-aligned so in-kernel padded tiles
    stay distinguishable); the jnp oracle rung is the positive control."""
    import re
    rng = np.random.default_rng(9)
    B, cap = 256, 600
    q = _rects(rng, B, w=0.1)
    pts = _buffer(rng, cap, 500)

    txt_k = jax.jit(
        lambda qq, pp: ops.delta_probe(qq, pp, k=16, tb=128)
    ).lower(q, pts).as_text()
    txt_o = jax.jit(
        lambda qq, pp: ref.delta_probe(qq, pp, 16)).lower(q, pts).as_text()
    dense = re.compile(r"<256x600x")
    assert not dense.search(txt_k), "kernel path materialized the mask"
    assert dense.search(txt_o), "oracle should materialize the mask"


def test_engine_delta_path_hlo_stays_compact():
    """The engine serve step with a delta buffer (kernel path, topk
    union) lowers without the [B, cap] probe mask AND still without the
    [B, L] score/visited tables — the freshness stage joins the compact
    slot-table contract instead of breaking it."""
    import re
    from repro.launch import mesh as pmesh
    rng = np.random.default_rng(10)
    hyb = synth_hybrid(rng)                  # L = 1000
    cap = 600
    pts = _buffer(rng, cap, 300)
    mesh = pmesh.make_mesh((1, 1, 1), ("pod", "data", "model"))
    B = 256
    lo = rng.uniform(-1, 0.9, (B, 2))
    q = jnp.asarray(np.concatenate([lo, lo + 0.05], 1), jnp.float32)
    step = engine.make_serve_step(mesh, engine.EngineConfig(
        max_visited=64, max_pred=16, use_kernel=True, score_union="topk"),
        kind="mlp")
    with jax.set_mesh(mesh):
        txt = jax.jit(step).lower(hyb, q, pts).as_text()
    assert not re.search(r"<256x600x", txt), \
        "delta path materialized the [B, cap] mask"
    assert not re.search(r"<256x100[01]x", txt), \
        "serve step regressed to dense [B, L] tables"


# ---------------------------------------------------------------------------
# the guard: under-prediction blind spot closed, exact-fit unchanged
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def under_trained_world():
    """A deliberately under-trained MLP bank (exact_fit ≪ 1) whose AI
    path silently drops results on some queries: predictions are a strict
    subset of the true leaves with every predicted leaf still yielding
    hits, so no fallback signal fires."""
    pts = synth.tweets_like(4000, seed=0)
    tree = RTree(max_entries=32).insert_all(pts)
    dtree = dt.flatten(tree)
    qs = synth.synth_queries(pts, 1e-3, 200, seed=1)
    wl = labels.make_workload(dtree, qs, max_results=2048)
    hyb, rep = build.fit_airtree(dtree, wl, kind="mlp", grid_sizes=(4,),
                                 mlp_hidden=16, mlp_epochs=800)
    return hyb, rep, wl


def test_under_trained_bank_silently_drops_without_guard(
        under_trained_world):
    """Pin the blind spot itself: with the guard off, served results
    disagree with the exact labels on some rows (silent drops reach the
    router-dispatched output); fit < 1 and some cells are flagged."""
    hyb, rep, wl = under_trained_world
    assert rep.exact_fit < 1.0
    assert not rep.cell_fit.all()
    # the public refit-path evaluation reproduces what the build installed
    fit, exact, cell_ok = build.eval_cell_fit(hyb.ait, hyb.tree, wl)
    assert fit == pytest.approx(rep.exact_fit)
    np.testing.assert_array_equal(cell_ok, rep.cell_fit)
    np.testing.assert_array_equal(cell_ok, np.asarray(hyb.ait.cell_ok))
    q = jnp.asarray(wl.queries)
    off = hybrid_query(hyb, q, max_visited=256, max_results=2048,
                       guard=False)
    mism = np.asarray(off.n_results) != wl.n_results
    assert mism.any(), "fixture must exhibit silent drops unguarded"
    # the drops are the blind spot, not truncation or fallbacks
    assert not np.asarray(off.truncated)[mism].any()
    assert np.asarray(off.used_ai)[mism].all()


def test_guard_recovers_dropped_hits(under_trained_world):
    """The fix: guard on (the default) demotes the under-fit cells'
    queries to the exact R path — every previously-dropped hit is
    recovered and the stream matches the labels exactly."""
    hyb, rep, wl = under_trained_world
    q = jnp.asarray(wl.queries)
    on = hybrid_query(hyb, q, max_visited=256, max_results=2048)
    np.testing.assert_array_equal(np.asarray(on.n_results), wl.n_results)
    assert np.asarray(on.guarded).any(), "guard must have fired"


def test_guard_leaves_exact_fit_dispatch_unchanged():
    """An exact-fit bank (memorization-complete kNN, fit 1.0): guard on
    == guard off on every field, and the AI path still answers."""
    pts = synth.tweets_like(3000, seed=5)
    # dynamic (paper-path) build: overlapping leaves give a mixed-α
    # workload, so the router genuinely sends traffic to the AI path
    dtree = dt.flatten(RTree(max_entries=32).insert_all(pts))
    qs = synth.synth_queries(pts, 1e-4, 200, seed=6)
    wl = labels.make_workload(dtree, qs)
    hyb, rep = build.fit_airtree(dtree, wl, kind="knn", grid_sizes=(6,))
    assert rep.exact_fit == 1.0
    q = jnp.asarray(wl.queries)
    a = hybrid_query(hyb, q)
    b = hybrid_query(hyb, q, guard=False)
    for f in type(a)._fields:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)), err_msg=f)
    assert np.asarray(a.used_ai).any()


def test_engine_guard_matches_hybrid(under_trained_world):
    """The engine's shard-local guard (psum over expert shards) agrees
    with the single-device hybrid row for row, and EngineConfig.guard
    defaults on."""
    from repro.launch import mesh as pmesh
    hyb, _, wl = under_trained_world
    mesh = pmesh.make_mesh((1, 1, 1), ("pod", "data", "model"))
    q = jnp.asarray(wl.queries[:64])
    ref_res = hybrid_query(hyb, q, max_visited=256)
    assert engine.EngineConfig().guard
    step = engine.make_serve_step(mesh, engine.EngineConfig(
        max_visited=256, max_pred=64), kind="mlp")
    with jax.set_mesh(mesh):
        stats = step(hyb, q)
    for f in ("n_results", "used_ai", "guarded", "leaf_accesses"):
        np.testing.assert_array_equal(np.asarray(getattr(stats, f)),
                                      np.asarray(getattr(ref_res, f)),
                                      err_msg=f)
