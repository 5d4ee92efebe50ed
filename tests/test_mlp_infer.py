"""Fused AI-path prediction kernel + compact AI query equivalence tests.

The fused kernel (``kernels/mlp_infer.py``) must be bit-identical to the
dense oracle (``predict_scores`` → threshold → ``compact_mask_counted``)
in both kernel forms, including every fallback-signal edge case the
hybrid relies on: *empty* prediction, exactly-``max_pred`` and
overflow-at-``max_pred`` boundaries, grid-routing ``cell_over``, and the
paper's mispredict (zero-count predicted leaf) convention. The serving
pipeline built on it (``ai_query_compact``, the engine's AI slot stage)
must never materialize the dense ``[B, L]`` score table in the lowered
HLO — asserted the way PR 3 pinned the R path's visited mask.
"""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import engine, traversal
from repro.core.aitree import (ai_query, ai_query_compact, make_aitree,
                               predict_compact, predict_scores)
from repro.core.device_tree import DeviceTree, Level
from repro.core.grid import Grid
from repro.kernels import mlp_infer as mi
from repro.kernels import ops, ref
from tests.helpers.banks import synth_bank, synth_hybrid
from tests.helpers.hypo import given, settings, st


def synth_world(rng, g=3, L=300, M=8, Cl=6, max_pred=16, pos_bias=0.0,
                threshold=0.5):
    """Synthetic (tree, aitree, queries): single-level tree (the AI path
    never traverses), g×g grid, random bank — fast, no training."""
    bank = synth_bank(rng, g * g, L, Cl=Cl, pos_bias=pos_bias)
    grid = Grid(bbox=jnp.asarray([-1.0, -1.0, 1.0, 1.0], jnp.float32), g=g)
    ait = make_aitree(grid, bank, max_cells=4, max_pred=max_pred,
                      threshold=threshold)
    lo = rng.uniform(-1, 1, (L, 2))
    mbrs = jnp.asarray(
        np.concatenate([lo, lo + rng.uniform(0.05, 0.3, (L, 2))], 1),
        jnp.float32)
    tree = DeviceTree(
        levels=(Level(mbrs=mbrs, parent=jnp.zeros((L,), jnp.int32)),),
        leaf_entries=jnp.asarray(rng.uniform(-1, 1, (L, 2, M)), jnp.float32),
        leaf_entry_ids=jnp.asarray(
            np.arange(L * M).reshape(L, M), jnp.int32),
        leaf_counts=jnp.full((L,), M, jnp.int32),
        n_points=L * M, max_entries=M)
    lo = rng.uniform(-1, 0.9, (64, 2))
    w = rng.uniform(0, 0.1, (64, 2))
    q = jnp.asarray(np.concatenate([lo, lo + w], 1), jnp.float32)
    return tree, ait, q


def dense_oracle(ait, queries, n_leaves, k):
    scores, _ = predict_scores(ait, queries, n_leaves)
    return traversal.compact_mask_counted(scores > ait.threshold, k)


# ---------------------------------------------------------------------------
# kernel vs dense oracle, both forms
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("C,L,B,Cl,k", [
    (9, 300, 37, 6, 8),      # nothing tile-aligned
    (4, 1000, 64, 3, 16),    # multi-leaf-tile relevant shapes
    (16, 100, 8, 10, 4),     # heavy overflow (k tiny)
])
def test_ops_wrapper_matches_oracle(C, L, B, Cl, k):
    """ops.mlp_predict_compact (interpret form) == dense oracle."""
    rng = np.random.default_rng(3)
    bank = synth_bank(rng, C, L, Cl=Cl)
    q = jnp.asarray(rng.uniform(-1, 1, (B, 4)), jnp.float32)
    cid = jnp.asarray(rng.integers(0, C, (B, 4)), jnp.int32)
    ok = jnp.asarray(rng.uniform(size=(B, 4)) < 0.85)
    x = (q - bank.mu) / bank.sd
    exp = ref.mlp_predict_compact(
        x, cid, ok, bank.w1, bank.b1, bank.w2, bank.b2, bank.label_map,
        bank.lmask, n_leaves=L, k=k, threshold=0.5)
    got = ops.mlp_predict_compact(q, bank, cid, ok, n_leaves=L, k=k,
                                  threshold=0.5)
    for g, e, name in zip(got, exp, ("idx", "valid", "count")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(e),
                                      err_msg=name)


@pytest.mark.parametrize("tpu_form", [True, False])
@pytest.mark.parametrize("L,tl", [
    (1000, 256),   # multi-leaf-tile: rank base carried across j
    (200, 128),
])
def test_kernel_forms_match_oracle(L, tl, tpu_form):
    """Both kernel forms (streamed-bank inference + candidate union +
    rank-loop epilogue on the TPU graph; value-level gathers +
    searchsorted on the interpret graph) against the dense oracle, with
    the compaction rank base exercised across multiple leaf tiles and
    empty rows mixed in."""
    _check_forms(L, tl, tpu_form, Cl=6)


def test_tpu_form_wide_bank_matches_oracle():
    """A bank wider than one lane quantum: each cell slot's segment of
    the TPU form's candidate list spans several lane tiles, and the sort
    between the stages gathers the candidates of all segments."""
    _check_forms(1000, 256, True, Cl=300)


def _check_forms(L, tl, tpu_form, Cl):
    rng = np.random.default_rng(5)
    C, S, B, k = 7, 4, 21, 8
    bank = synth_bank(rng, C, L, Cl=Cl)
    q = jnp.asarray(rng.uniform(-1, 1, (B, 4)), jnp.float32)
    cid = jnp.asarray(rng.integers(0, C, (B, S)), jnp.int32)
    ok = jnp.asarray(rng.uniform(size=(B, S)) < 0.85)
    ok = ok.at[0].set(False)            # empty row (no valid slot)
    x = (q - bank.mu) / bank.sd
    exp = ref.mlp_predict_compact(
        x, cid, ok, bank.w1, bank.b1, bank.w2, bank.b2, bank.label_map,
        bank.lmask, n_leaves=L, k=k, threshold=0.5)

    LANE = mi.LANE
    Cp = (-C) % mi.CELL_BLOCK
    Clp = (-Cl) % LANE if tpu_form else 0
    pad = lambda a, v=0.0: jnp.concatenate(         # noqa: E731
        [a, jnp.full((Cp,) + a.shape[1:], v, a.dtype)])
    padl = lambda a, v=0.0: jnp.concatenate(        # noqa: E731
        [a, jnp.full(a.shape[:-1] + (Clp,), v, a.dtype)], axis=-1)
    tb = (B + 7) // 8 * 8
    padb = lambda a: jnp.concatenate(               # noqa: E731
        [a, jnp.zeros((tb - B,) + a.shape[1:], a.dtype)])
    lp = ((L + LANE - 1) // LANE * LANE + tl - 1) // tl * tl
    lm = jnp.where(bank.lmask, bank.label_map, -1)
    idx, cnt = mi.mlp_predict_compact_t(
        padb(x), padb(cid), padb(ok.astype(jnp.int32)),
        pad(bank.w1), pad(bank.b1), pad(padl(bank.w2)), pad(padl(bank.b2)),
        pad(padl(lm, -1), -1),
        k=k, lp=lp, thr=0.5, tb=tb, tl=tl, interpret=True,
        tpu_form=tpu_form)
    count = np.asarray(cnt)[:B, 0]
    np.testing.assert_array_equal(count, np.asarray(exp[2]))
    valid = np.arange(k)[None, :] < count[:, None]
    np.testing.assert_array_equal(
        np.where(valid, np.asarray(idx)[:B, :k], 0), np.asarray(exp[0]))
    # contract: slots past the count are zero in both forms
    assert (np.asarray(idx)[:B, :k][~valid] == 0).all()
    assert not count[0], "empty-slot row must predict nothing"


def test_escape_hatch_and_vmem_gate(monkeypatch):
    """Kernels-off and over-VMEM-budget rungs of the fallback ladder stay
    bit-identical to the kernel path (dense oracle semantics)."""
    from repro.kernels import traverse_fused as tf
    rng = np.random.default_rng(11)
    bank = synth_bank(rng, 9, 250)
    q = jnp.asarray(rng.uniform(-1, 1, (19, 4)), jnp.float32)
    cid = jnp.asarray(rng.integers(0, 9, (19, 4)), jnp.int32)
    ok = jnp.asarray(rng.uniform(size=(19, 4)) < 0.9)
    base = ops.mlp_predict_compact(q, bank, cid, ok, n_leaves=250, k=8,
                                   threshold=0.5)
    monkeypatch.setenv("REPRO_KERNELS", "off")
    got_off = ops.mlp_predict_compact(q, bank, cid, ok, n_leaves=250, k=8,
                                      threshold=0.5)
    monkeypatch.delenv("REPRO_KERNELS")
    real = tf.VMEM_BUDGET
    try:
        tf.VMEM_BUDGET = 1
        got_gate = ops.mlp_predict_compact(q, bank, cid, ok, n_leaves=250,
                                           k=8, threshold=0.5)
    finally:
        tf.VMEM_BUDGET = real
    for got in (got_off, got_gate):
        for g, e in zip(got, base):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(e))


# ---------------------------------------------------------------------------
# fallback-signal edge cases (the hybrid's exactness contract)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_kernel", [False, True])
def test_empty_prediction_edge(use_kernel):
    """A bank that never crosses the threshold: count 0 everywhere, and
    ai_query_compact raises the *empty* fallback on every row."""
    rng = np.random.default_rng(0)
    tree, ait, q = synth_world(rng, pos_bias=-30.0)   # sigmoid ≈ 0
    _, valid, n_pred, _ = predict_compact(ait, q, tree.n_leaves,
                                          use_kernel=use_kernel)
    assert not np.asarray(n_pred).any() and not np.asarray(valid).any()
    res = ai_query_compact(ait, tree, q, use_kernel=use_kernel)
    assert np.asarray(res.fallback).all()


@pytest.mark.parametrize("use_kernel", [False, True])
def test_exactly_max_pred_boundary(use_kernel):
    """Rows predicting exactly max_pred leaves must NOT overflow; one
    fewer slot must. Exercised by re-binding max_pred to each row's own
    dense count (the compact path's count is the full count, never
    clamped at k — the overflow signal depends on that)."""
    rng = np.random.default_rng(1)
    tree, ait, q = synth_world(rng, pos_bias=2.0)     # dense predictions
    counts = np.asarray(dense_oracle(ait, q, tree.n_leaves,
                                     ait.max_pred)[2])
    row = int(np.argmax(counts >= 3))
    c = int(counts[row])
    assert c >= 3, "fixture must have a multi-leaf prediction row"
    qr = q[row:row + 1]
    for k, over in ((c, False), (c - 1, True)):
        ait_k = dataclasses.replace(ait, max_pred=k)
        idx, valid, n_pred, _ = predict_compact(ait_k, qr, tree.n_leaves,
                                                use_kernel=use_kernel)
        assert int(n_pred[0]) == c          # full count survives overflow
        assert int(np.asarray(valid).sum()) == min(c, k)
        res = ai_query_compact(ait_k, tree, qr, use_kernel=use_kernel)
        ref_res = ai_query(ait_k, tree, qr, use_kernel=use_kernel)
        assert bool(res.fallback[0]) == bool(ref_res.fallback[0])
        if over:
            assert bool(res.fallback[0])


@pytest.mark.parametrize("use_kernel", [False, True])
def test_cell_overflow_edge(use_kernel):
    """Queries spanning more cells than the static window: cell_over set,
    prediction suppressed, fallback raised — identical to the dense path."""
    rng = np.random.default_rng(2)
    tree, ait, _ = synth_world(rng)
    wide = jnp.asarray([[-0.95, -0.95, 0.95, 0.95]], jnp.float32)  # 3x3 cells
    _, valid, n_pred, cell_over = predict_compact(
        ait, wide, tree.n_leaves, use_kernel=use_kernel)
    assert bool(cell_over[0]) and int(n_pred[0]) == 0
    res = ai_query_compact(ait, tree, wide, use_kernel=use_kernel)
    assert bool(res.fallback[0])


@pytest.mark.parametrize("use_kernel", [False, True])
def test_mispredict_zero_count_convention(use_kernel):
    """The paper's misprediction signal: a predicted leaf whose refinement
    finds zero qualifying entries must force fallback — pinned against
    the dense ai_query on a world whose leaf entries never qualify."""
    rng = np.random.default_rng(4)
    tree, ait, q = synth_world(rng, pos_bias=2.0)
    # entries far outside every query: every predicted leaf yields zero
    tree = dataclasses.replace(
        tree, leaf_entries=jnp.full_like(tree.leaf_entries, 50.0))
    res = ai_query_compact(ait, tree, q, use_kernel=use_kernel)
    exp = ai_query(ait, tree, q, use_kernel=use_kernel)
    np.testing.assert_array_equal(np.asarray(res.fallback),
                                  np.asarray(exp.fallback))
    pred_rows = np.asarray(exp.n_pred) > 0
    assert pred_rows.any(), "fixture must predict something"
    assert np.asarray(res.fallback)[pred_rows].all()


# ---------------------------------------------------------------------------
# ai_query_compact == ai_query (the serving pipeline contract)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_kernel", [False, True])
def test_ai_query_compact_matches_dense(use_kernel):
    rng = np.random.default_rng(6)
    tree, ait, q = synth_world(rng, pos_bias=0.5)
    comp = ai_query_compact(ait, tree, q, use_kernel=use_kernel)
    full = ai_query(ait, tree, q, use_kernel=False)
    exp_i, exp_v, _ = traversal.compact_mask_counted(
        full.pred_mask, ait.max_pred)
    np.testing.assert_array_equal(np.asarray(comp.leaf_idx),
                                  np.asarray(exp_i))
    np.testing.assert_array_equal(np.asarray(comp.valid), np.asarray(exp_v))
    for f in ("counts", "n_pred", "n_results", "result_ids", "fallback"):
        np.testing.assert_array_equal(
            np.asarray(getattr(comp, f)), np.asarray(getattr(full, f)),
            err_msg=f)


def test_ai_query_compact_never_materializes_scores():
    """On the kernel path the lowered HLO must contain no [B, L]- or
    [B, L+1]-shaped tensor: the score table exists only tile-by-tile
    inside the kernel (tile_b < B keeps in-kernel tiles distinguishable,
    as PR 3's visited-mask assert did). ai_query, by contrast, does
    materialize it."""
    import re
    rng = np.random.default_rng(7)
    tree, ait, _ = synth_world(rng, L=1000)
    B = 256
    lo = rng.uniform(-1, 0.9, (B, 2))
    q = jnp.asarray(np.concatenate([lo, lo + 0.05], 1), jnp.float32)

    def lowered(fn):
        return jax.jit(lambda t, qq: fn(t, qq)).lower(tree, q).as_text()

    txt_c = lowered(lambda t, qq: ai_query_compact(
        ait, t, qq, use_kernel=True, tile_b=128))
    txt_d = lowered(lambda t, qq: ai_query(ait, t, qq))
    dense = re.compile(r"<256x100[01]x")
    assert not dense.search(txt_c), "compact path materialized the scores"
    assert dense.search(txt_d), "oracle should materialize the scores"


# ---------------------------------------------------------------------------
# compact_candidates (the engine's sort-free candidate-list compaction)
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(st.integers(1, 12), st.integers(1, 24), st.integers(1, 12),
       st.integers(2, 40), st.integers(0, 2**31 - 1))
def test_compact_candidates_matches_mask_compaction(B, N, k, L, seed):
    """compact_candidates == compact_mask_counted of the scattered mask:
    same slots, validity, and distinct count — without the [B, L] table.
    Duplicate ids across candidates (sibling-cell predictions) dedup."""
    rng = np.random.default_rng(seed)
    ids = jnp.asarray(rng.integers(0, L, (B, N)), jnp.int32)
    ok = jnp.asarray(rng.uniform(size=(B, N)) < 0.6)
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    mask = jnp.zeros((B, L), jnp.int32).at[rows, ids].max(
        ok.astype(jnp.int32)) > 0
    exp = traversal.compact_mask_counted(mask, k)
    got = traversal.compact_candidates(ids, ok, k)
    for g, e, name in zip(got, exp, ("idx", "valid", "count")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(e),
                                      err_msg=name)


# ---------------------------------------------------------------------------
# engine: AI slot stage, kernel vs oracle, and the HLO contract
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def trained_world():
    """A small *trained* MLP world — genuine AI-path answers (the random
    banks above always mispredict, so used_ai would never fire)."""
    from repro.core import build, device_tree as dt, labels
    from repro.core.rtree import RTree
    from repro.data import synth
    pts = synth.tweets_like(2500, seed=0)
    tree = RTree(max_entries=32).insert_all(pts)
    dtree = dt.flatten(tree)
    qs = synth.synth_queries(pts, 2e-4, 150, seed=1)
    wl = labels.make_workload(dtree, qs)
    hyb, _ = build.fit_airtree(dtree, wl, kind="mlp", grid_sizes=(4,),
                               mlp_hidden=16, mlp_epochs=800)
    return hyb, wl


@pytest.mark.parametrize("use_kernel", [False, True])
def test_trained_ai_query_compact_matches_dense(trained_world, use_kernel):
    """Trained-bank integration: ai_query_compact == ai_query on real
    logits (not just the synthetic banks above), both kernel settings."""
    hyb, wl = trained_world
    q = jnp.asarray(wl.queries[:64])
    comp = ai_query_compact(hyb.ait, hyb.tree, q, use_kernel=use_kernel)
    full = ai_query(hyb.ait, hyb.tree, q, use_kernel=False)
    assert not np.asarray(full.fallback).all(), \
        "fixture must answer some rows on the AI path"
    for f in ("counts", "n_pred", "n_results", "result_ids", "fallback"):
        np.testing.assert_array_equal(
            np.asarray(getattr(comp, f)), np.asarray(getattr(full, f)),
            err_msg=f)


@pytest.mark.parametrize("union", ["topk", "pmax"])
def test_engine_ai_path_kernel_bit_identical(trained_world, union):
    """make_serve_step with the fused prediction kernel (use_kernel=True,
    mlp bank) == the jnp oracle stage, every ServeStats field, in both
    score_union modes — on a trained bank so the AI path genuinely
    answers rows (not fallback-everywhere)."""
    from repro.launch import mesh as pmesh
    hyb, wl = trained_world
    mesh = pmesh.make_mesh((1, 1, 1), ("pod", "data", "model"))
    q = jnp.asarray(wl.queries[:64])
    stats = {}
    for uk in (False, True):
        step = engine.make_serve_step(mesh, engine.EngineConfig(
            max_visited=64, max_pred=16, use_kernel=uk, score_union=union),
            kind="mlp")
        with jax.set_mesh(mesh):
            stats[uk] = step(hyb, q)
    assert np.asarray(stats[True].used_ai).any(), \
        "fixture must answer some rows on the AI path"
    for f in stats[False]._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(stats[False], f)),
            np.asarray(getattr(stats[True], f)), err_msg=f)


def test_engine_ai_path_never_materializes_scores():
    """The engine's serve step (topk union, kernel path) lowers without
    any [B, L]- or [B, L+1]-shaped tensor: the AI path's only inter-stage
    format is the compact slot table, and the R path is PR 3's compact
    pipeline. (L is deliberately not lane-aligned so in-kernel [B, L_pad]
    tiles stay distinguishable from a dense [B, L] table.)"""
    import re
    from repro.launch import mesh as pmesh
    rng = np.random.default_rng(9)
    hyb = synth_hybrid(rng)                  # L = 1000
    mesh = pmesh.make_mesh((1, 1, 1), ("pod", "data", "model"))
    B = 256
    lo = rng.uniform(-1, 0.9, (B, 2))
    q = jnp.asarray(np.concatenate([lo, lo + 0.05], 1), jnp.float32)
    step = engine.make_serve_step(mesh, engine.EngineConfig(
        max_visited=64, max_pred=16, use_kernel=True, score_union="topk"),
        kind="mlp")
    with jax.set_mesh(mesh):
        txt = jax.jit(step).lower(hyb, q).as_text()
        step_pmax = engine.make_serve_step(mesh, engine.EngineConfig(
            max_visited=64, max_pred=16, use_kernel=True,
            score_union="pmax"), kind="mlp")
        txt_pmax = jax.jit(step_pmax).lower(hyb, q).as_text()
    dense = re.compile(r"<256x100[01]x")
    assert not dense.search(txt), "engine AI path materialized the scores"
    # positive control: the paper-faithful pmax union still goes dense
    assert dense.search(txt_pmax), "pmax union should materialize scores"
