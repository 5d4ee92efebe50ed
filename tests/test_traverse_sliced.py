"""Ancestor-sliced fused traversal: parity, dispatch ladder, and the
at-scale HLO acceptance gate.

The sliced form (``kernels.traverse_fused.traverse_fused_sliced_t`` /
``traverse_compact_sliced_t``) must be **bit-identical** to the jnp oracle
and to the full-VMEM fused form wherever both run — same visited sets,
same compact slot tables, same counts. The dispatch ladder in
``kernels.ops`` must route over-budget trees to it (per-level kernel loop
only as last resort), and at a tree size past ``VMEM_BUDGET`` the lowered
serving step must carry neither a dense ``[B, L]`` mask nor per-level
frontier round-trips — asserted on HLO text with the per-level fallback as
the positive control.
"""
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from tests.helpers.hypo import given, settings, st
from repro.core.device_tree import DeviceTree, Level, build_ancestor_table
from repro.core import traversal
from repro.core.traversal import compact_mask_counted
from repro.data.synth_tree import synth_levels
from repro.kernels import ops, ref
from repro.kernels import traverse_fused as tf


def _tree(L, fanout, rng, slice_tl=None):
    mbrs, parents = synth_levels(L, fanout, rng, str_pack=True)
    lm = [jnp.asarray(m) for m in mbrs]
    lp = [jnp.asarray(p) for p in parents]
    sl = build_ancestor_table(parents, tl=slice_tl)
    return lm, lp, sl


def _device_tree(lm, lp, sl):
    L = lm[-1].shape[0]
    return DeviceTree(
        levels=tuple(Level(mbrs=m, parent=p) for m, p in zip(lm, lp)),
        leaf_entries=jnp.full((L, 2, 8), jnp.inf, jnp.float32),
        leaf_entry_ids=jnp.full((L, 8), -1, jnp.int32),
        leaf_counts=jnp.zeros((L,), jnp.int32),
        n_points=0, max_entries=8, aslices=sl)


def _queries(B, rng, dead_rows=True):
    lo = rng.uniform(-1, 1, (B, 2))
    w = rng.uniform(0, 0.08, (B, 2))
    q = np.concatenate([lo, lo + w], 1).astype(np.float32)
    if dead_rows and B >= 4:
        q[1] = [50.0, 50.0, 51.0, 51.0]        # misses everything
        q[3] = [-2.0, -2.0, 2.0, 2.0]          # hits everything
    return jnp.asarray(q)


@pytest.fixture
def budget_guard():
    """Restore the VMEM budget after tests that force ladder rungs."""
    orig = tf.VMEM_BUDGET
    yield
    tf.VMEM_BUDGET = orig


# ---------------------------------------------------------------------------
# Table + oracle semantics
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("L,fanout,tl", [(700, 4, 128), (2048, 8, 256),
                                         (4096, 4, 512)])
def test_sliced_oracle_matches_full(L, fanout, tl):
    """The windowed oracle under a built table equals the full walk —
    i.e. every tile's true ancestors land inside its windows."""
    rng = np.random.default_rng(L)
    lm, lp, sl = _tree(L, fanout, rng, slice_tl=tl)
    assert sl is not None and sl.tl == tl
    q = _queries(32, rng)
    full = np.asarray(ref.traverse_fused(q, lm, lp))
    sliced = np.asarray(ref.traverse_fused_sliced(
        q, lm, lp, sl.starts, sl.widths, sl.tl))[:, :L]
    np.testing.assert_array_equal(full, sliced)


def test_table_shapes_and_degenerates():
    rng = np.random.default_rng(0)
    _, lp, sl = _tree(1000, 4, rng, slice_tl=128)
    assert sl.starts.shape == (len(lp) - 1, -(-1000 // 128))
    assert len(sl.widths) == len(lp) - 1
    assert all(w >= tf.LANE and w % tf.LANE == 0 for w in sl.widths)
    # root == leaf: nothing to slice
    assert build_ancestor_table([np.zeros(5, np.int32)]) is None


# ---------------------------------------------------------------------------
# Kernel parity (both forms) against oracle and full-VMEM form
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tpu_form", [False, True])
@pytest.mark.parametrize("L,fanout,tl", [(1000, 4, 128), (4096, 4, 512)])
def test_sliced_kernel_bit_identical(tpu_form, L, fanout, tl):
    rng = np.random.default_rng(7)
    lm, lp, sl = _tree(L, fanout, rng, slice_tl=tl)
    B, k = 24, 32
    q = _queries(B, rng)
    oracle = np.asarray(ref.traverse_fused(q, lm, lp))

    qp, imt, ipar, lmt, lpt = ops._sliced_operands(q, lm, lp, sl, 8)
    out = tf.traverse_fused_sliced_t(
        sl.starts, qp.T, imt, ipar, lmt, lpt, widths=sl.widths, tb=8,
        tl=sl.tl, interpret=True, tpu_form=tpu_form)
    np.testing.assert_array_equal(np.asarray(out)[:B, :L], oracle)

    idx, cnt = tf.traverse_compact_sliced_t(
        sl.starts, qp.T, imt, ipar, lmt, lpt, k=k, widths=sl.widths,
        tb=8, tl=sl.tl, interpret=True, tpu_form=tpu_form)
    ridx, rval, rcnt = compact_mask_counted(jnp.asarray(oracle), k)
    np.testing.assert_array_equal(np.asarray(cnt)[:B, 0], np.asarray(rcnt))
    got = np.where(np.asarray(rval), np.asarray(idx)[:B, :k], 0)
    np.testing.assert_array_equal(got, np.asarray(jnp.where(rval, ridx, 0)))


# ---------------------------------------------------------------------------
# Dispatch ladder
# ---------------------------------------------------------------------------


def _force_budget(between_sliced_and_full, lm, sl, tb=1024):
    """A budget that rejects the full form but admits the sliced one."""
    widths = [int(m.shape[0]) for m in lm[:-1]]
    padded = [n + (-n) % tf.LANE for n in widths]
    full = tf.vmem_estimate(padded, tb, lm[-1].shape[0])
    sliced = tf.vmem_estimate_sliced(sl.widths, tb, sl.tl, tpu_form=False)
    assert sliced < full
    return (full + sliced) // 2 if between_sliced_and_full else 1


def test_ladder_routes_over_budget_to_sliced(budget_guard, monkeypatch):
    rng = np.random.default_rng(3)
    lm, lp, sl = _tree(4096, 4, rng, slice_tl=512)
    q = _queries(16, rng)
    oracle = np.asarray(ref.traverse_fused(q, lm, lp))

    calls = []
    real = tf.traverse_fused_sliced_t

    def spy(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(tf, "traverse_fused_sliced_t", spy)
    tf.VMEM_BUDGET = _force_budget(True, lm, sl)
    got = np.asarray(ops.traverse_fused(q, lm, lp, slices=sl))
    np.testing.assert_array_equal(got, oracle)
    assert calls, "over-budget dispatch did not take the sliced kernel"


def test_ladder_compact_sliced_and_table_autobuild(budget_guard):
    """Compact wrapper takes the sliced rung; with no table passed, one is
    built on the fly from the (concrete) parent arrays."""
    rng = np.random.default_rng(4)
    lm, lp, sl = _tree(4096, 4, rng, slice_tl=512)
    q = _queries(16, rng)
    k = 32
    ridx, rval, rcnt = compact_mask_counted(
        jnp.asarray(ref.traverse_fused(q, lm, lp)), k)
    tf.VMEM_BUDGET = _force_budget(True, lm, sl)
    for slices in (sl, None):                  # explicit table / autobuild
        gi, gv, gc = ops.traverse_compact(q, lm, lp, k, slices=slices)
        np.testing.assert_array_equal(np.asarray(gc), np.asarray(rcnt))
        np.testing.assert_array_equal(np.asarray(gv), np.asarray(rval))
        np.testing.assert_array_equal(
            np.asarray(gi), np.asarray(jnp.where(rval, ridx, 0)))


def test_ladder_last_resort_per_level(budget_guard):
    """Budget below even the sliced working set → per-level kernel loop,
    still bit-identical."""
    rng = np.random.default_rng(5)
    lm, lp, sl = _tree(2048, 4, rng, slice_tl=256)
    q = _queries(16, rng)
    oracle = np.asarray(ref.traverse_fused(q, lm, lp))
    tf.VMEM_BUDGET = 1
    got = np.asarray(ops.traverse_fused(q, lm, lp, slices=sl))
    np.testing.assert_array_equal(got, oracle)


def test_slices_usable_rejects_mismatched_tables():
    rng = np.random.default_rng(6)
    lm, lp, sl = _tree(1024, 4, rng, slice_tl=128)
    n_levels, L = len(lm), 1024
    assert ops._slices_usable(sl, n_levels, L)
    assert not ops._slices_usable(None, n_levels, L)
    assert not ops._slices_usable(sl, n_levels - 1, L)   # wrong height
    assert not ops._slices_usable(sl, n_levels, 2048)    # wrong leaf count


# ---------------------------------------------------------------------------
# Satellite: REPRO_VMEM_BUDGET env override
# ---------------------------------------------------------------------------


def test_vmem_budget_env_override():
    assert tf._read_vmem_budget({}) == tf.DEF_VMEM_BUDGET
    assert tf._read_vmem_budget({tf.VMEM_BUDGET_ENV: "123456"}) == 123456
    # invalid / non-positive values must not disable every kernel
    assert tf._read_vmem_budget(
        {tf.VMEM_BUDGET_ENV: "8MB"}) == tf.DEF_VMEM_BUDGET
    assert tf._read_vmem_budget(
        {tf.VMEM_BUDGET_ENV: "-4"}) == tf.DEF_VMEM_BUDGET
    assert tf._read_vmem_budget(
        {tf.VMEM_BUDGET_ENV: "0"}) == tf.DEF_VMEM_BUDGET


# ---------------------------------------------------------------------------
# Satellite: hypothesis property — sliced ≡ oracle ≡ full everywhere,
# including trees straddling the budget and degenerate heights
# ---------------------------------------------------------------------------


@settings(max_examples=12, deadline=None)
@given(st.integers(min_value=0, max_value=5),
       st.integers(min_value=0, max_value=2),
       st.integers(min_value=0, max_value=2),
       st.integers(min_value=0, max_value=10_000))
def test_property_sliced_parity(l_idx, f_idx, t_idx, seed):
    L = (96, 300, 513, 1024, 2048, 4096)[l_idx]
    fanout = (3, 4, 8)[f_idx]
    tl = (128, 256, 512)[t_idx]
    rng = np.random.default_rng(seed)
    lm, lp, sl = _tree(L, fanout, rng, slice_tl=tl)
    B, k = 16, 16
    q = _queries(B, rng)
    oracle = np.asarray(ref.traverse_fused(q, lm, lp))
    ridx, rval, rcnt = compact_mask_counted(jnp.asarray(oracle), k)

    # sliced kernel (interp form exercises the value-level window walk;
    # tpu form the one-hot MXU walk) vs oracle
    qp, imt, ipar, lmt, lpt = ops._sliced_operands(q, lm, lp, sl, 8)
    for tpu_form in (False, True):
        out = tf.traverse_fused_sliced_t(
            sl.starts, qp.T, imt, ipar, lmt, lpt, widths=sl.widths, tb=8,
            tl=sl.tl, interpret=True, tpu_form=tpu_form)
        np.testing.assert_array_equal(np.asarray(out)[:B, :L], oracle)
    idx, cnt = tf.traverse_compact_sliced_t(
        sl.starts, qp.T, imt, ipar, lmt, lpt, k=k, widths=sl.widths,
        tb=8, tl=sl.tl, interpret=True, tpu_form=False)
    np.testing.assert_array_equal(np.asarray(cnt)[:B, 0], np.asarray(rcnt))
    np.testing.assert_array_equal(
        np.where(np.asarray(rval), np.asarray(idx)[:B, :k], 0),
        np.asarray(jnp.where(rval, ridx, 0)))

    # full-VMEM fused form (the ladder's in-budget rung) on the same tree
    full = np.asarray(ops.traverse_fused(q, lm, lp))
    np.testing.assert_array_equal(full, oracle)


@settings(max_examples=6, deadline=None)
@given(st.integers(min_value=2, max_value=64),
       st.integers(min_value=0, max_value=10_000))
def test_property_degenerate_heights(L, seed):
    """root==leaf (no table) and single-internal-level trees survive the
    ladder under a forced-tiny budget."""
    rng = np.random.default_rng(seed)
    lo = rng.uniform(-1, 1, (L, 2))
    w = rng.uniform(0.05, 0.3, (L, 2))
    leaf = jnp.asarray(np.concatenate([lo, lo + w], 1).astype(np.float32))
    q = _queries(8, rng, dead_rows=False)

    orig = tf.VMEM_BUDGET
    try:
        tf.VMEM_BUDGET = 1
        # root == leaf: single level, table is None, ladder takes the
        # plain intersection rung
        got = np.asarray(ops.traverse_fused(
            q, [leaf], [jnp.zeros((L,), jnp.int32)]))
        np.testing.assert_array_equal(
            got, np.asarray(ref.mbr_intersect(q, leaf)))

        # single internal level (root + leaves)
        root = jnp.asarray(np.concatenate([
            np.min(np.asarray(leaf)[:, :2], 0),
            np.max(np.asarray(leaf)[:, 2:], 0)])[None].astype(np.float32))
        lm = [root, leaf]
        lp = [jnp.zeros((1,), jnp.int32), jnp.zeros((L,), jnp.int32)]
        sl = build_ancestor_table([np.asarray(p) for p in lp], tl=128)
        got = np.asarray(ops.traverse_fused(q, lm, lp, slices=sl))
        np.testing.assert_array_equal(
            got, np.asarray(ref.traverse_fused(q, lm, lp)))
    finally:
        tf.VMEM_BUDGET = orig


# ---------------------------------------------------------------------------
# Acceptance: at 64k leaves the serving step's HLO has no dense [B, L]
# mask and no per-level frontier round-trip; per-level fallback is the
# positive control; results bit-identical to the oracle at that shape.
# ---------------------------------------------------------------------------

# fanout 4 → widest internal level 16384: the full-VMEM form's frontier
# alone (256×16384×4B = 16 MB) is past the default budget, so the ladder
# must pick the sliced kernel with no forcing
_SCALE_L, _SCALE_FANOUT, _SCALE_TL = 65536, 4, 2048


def _scale_tree(slice_tl=_SCALE_TL):
    rng = np.random.default_rng(11)
    lm, lp, sl = _tree(_SCALE_L, _SCALE_FANOUT, rng, slice_tl=slice_tl)
    return _device_tree(lm, lp, sl), rng


def _lower_compact(tree, B, k=64):
    fn = jax.jit(lambda t, q: traversal.visited_leaves_compact(
        t, q, k, use_kernel=True))
    q = jnp.zeros((B, 4), jnp.float32)
    return fn.lower(tree, q).as_text()


def test_hlo_no_dense_mask_at_scale():
    tree, _ = _scale_tree()
    B = 256
    widths = [lv.mbrs.shape[0] + (-lv.mbrs.shape[0]) % tf.LANE
              for lv in tree.levels[:-1]]
    # this shape is past the *default* budget — no budget forcing here
    assert tf.vmem_estimate(widths, B, 512) > tf.VMEM_BUDGET

    hlo = _lower_compact(tree, B)
    # StableHLO spells shapes tensor<256x65536xi1>
    dense = re.compile(rf"<{B}x{_SCALE_L}x")
    frontier = re.compile(rf"<{B}x16384x")      # [B, N_l] at the widest
    assert not dense.search(hlo), "dense [B, L] mask present at scale"
    assert not frontier.search(hlo), "per-level frontier present at scale"

    # positive control: drop the table and force the per-level fallback
    # (under jit the parents are tracers, so no on-the-fly table either)
    import dataclasses
    control = dataclasses.replace(tree, aslices=None)
    hlo_pl = _lower_compact(control, B)
    assert dense.search(hlo_pl), "control lost its dense mask"
    assert frontier.search(hlo_pl), "control lost its frontier"


def test_scale_bit_identical_to_oracle():
    tree, rng = _scale_tree()
    B, k = 32, 64
    q = _queries(B, rng)
    lm = [lv.mbrs for lv in tree.levels]
    lp = [lv.parent for lv in tree.levels]
    oracle = np.asarray(ref.traverse_fused(q, lm, lp))
    ridx, rval, rcnt = compact_mask_counted(jnp.asarray(oracle), k)
    cv = traversal.visited_leaves_compact(tree, q, k, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(cv.n_visited),
                                  np.asarray(rcnt))
    np.testing.assert_array_equal(np.asarray(cv.valid), np.asarray(rval))
    np.testing.assert_array_equal(np.asarray(cv.leaf_idx),
                                  np.asarray(jnp.where(rval, ridx, 0)))


# ---------------------------------------------------------------------------
# Engine: sharding pad re-anchors (or drops) the table
# ---------------------------------------------------------------------------


def test_pad_rebuild_keeps_windows_tight():
    """Padding the leaf axis (engine sharding) re-derives a table whose
    real-lane windows still satisfy the oracle equality; the pad lanes'
    repeated last parent keeps the final tile's window from stretching."""
    rng = np.random.default_rng(9)
    mbrs, parents = synth_levels(1000, 4, rng, str_pack=True)
    # simulate pad_tree_for_sharding's leaf padding to 1024
    pad = 24
    never = np.array([np.inf, np.inf, -np.inf, -np.inf], np.float32)
    mbrs = mbrs[:-1] + [np.concatenate(
        [mbrs[-1], np.tile(never[None], (pad, 1))]).astype(np.float32)]
    parents = parents[:-1] + [np.concatenate(
        [parents[-1], np.full((pad,), parents[-1][-1], np.int32)])]
    sl = build_ancestor_table(parents, tl=128)
    assert sl.starts.shape[1] == 1024 // 128
    lm = [jnp.asarray(m) for m in mbrs]
    lp = [jnp.asarray(p) for p in parents]
    q = _queries(16, rng)
    np.testing.assert_array_equal(
        np.asarray(ref.traverse_fused(q, lm, lp)),
        np.asarray(ref.traverse_fused_sliced(
            q, lm, lp, sl.starts, sl.widths, sl.tl)))
