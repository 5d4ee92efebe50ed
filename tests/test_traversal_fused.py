"""Fused-traversal kernel + sort-free compaction equivalence tests.

The fused single-pass kernel (interpret mode on CPU) must produce
bit-identical visited masks to the level-by-level jnp oracle, and the
sort-free cumsum/scatter compaction must match the ``top_k``-based
implementations it replaced — including on adversarial shapes: leaf counts
that are not tile multiples, all-dead frontiers, and overflow rows.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import device_tree as dt, traversal
from repro.core.device_tree import DeviceTree, Level
from repro.core.rtree import RTree
from repro.kernels import ops, ref


RNG = np.random.default_rng(7)


def mk_rects(n, rng=RNG, scale=1.0, width=1.0):
    lo = rng.uniform(-scale, scale, size=(n, 2))
    w = rng.uniform(0, width, size=(n, 2))
    return np.concatenate([lo, lo + w], axis=1).astype(np.float32)


def synth_levels(L, fanout, rng=RNG):
    """Synthetic hierarchy with wide leaf MBRs (dense visited sets)."""
    from repro.data.synth_tree import synth_levels as _synth
    mbrs, parents = _synth(L, fanout, rng, leaf_width=1.0)
    return ([jnp.asarray(m) for m in mbrs],
            [jnp.asarray(p) for p in parents])


# ---------------------------------------------------------------------------
# fused traversal vs jnp oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("L,fanout,B", [
    (37, 4, 7),       # L, B both far from tile multiples
    (130, 3, 64),     # deep tree (6 levels), non-power-of-two everything
    (512, 8, 33),
    (2048, 8, 256),   # multi-leaf-tile grid, multi-query-tile
    (1, 4, 5),        # degenerate: root == single leaf (no fusion possible)
])
def test_fused_matches_oracle(L, fanout, B):
    mbrs, parents = synth_levels(L, fanout)
    q = jnp.asarray(mk_rects(B, width=0.4))
    out = np.asarray(ops.traverse_fused(q, mbrs, parents))
    exp = np.asarray(ref.traverse_fused(q, mbrs, parents))
    np.testing.assert_array_equal(out, exp)


def test_fused_all_dead_frontier():
    """Queries disjoint from the root MBR: the frontier dies at level 0 and
    every leaf tile must take the early-exit path to an all-false mask."""
    mbrs, parents = synth_levels(640, 4)
    q = jnp.asarray(np.tile(np.array([[90.0, 90.0, 91.0, 91.0]], np.float32),
                            (32, 1)))
    out = np.asarray(ops.traverse_fused(q, mbrs, parents))
    assert out.shape == (32, 640) and not out.any()


def test_fused_mixed_dead_and_live_rows():
    """Dead and live queries in one batch tile must not contaminate each
    other through the shared VMEM frontier scratch."""
    mbrs, parents = synth_levels(300, 5)
    live = mk_rects(8, width=2.0)
    dead = np.tile(np.array([[90.0, 90.0, 91.0, 91.0]], np.float32), (8, 1))
    q = jnp.asarray(np.concatenate([dead, live, dead], 0))
    out = np.asarray(ops.traverse_fused(q, mbrs, parents))
    exp = np.asarray(ref.traverse_fused(q, mbrs, parents))
    np.testing.assert_array_equal(out, exp)
    assert not out[:8].any() and not out[16:].any()


def test_fused_on_flattened_rtree():
    """End to end against a real host-built tree: fused visited mask ==
    per-level oracle == visited_leaf_mask(use_kernel=True)."""
    pts = RNG.normal(size=(3000, 2))
    tree = RTree(max_entries=16).insert_all(pts)
    dtree = dt.flatten(tree)
    q = jnp.asarray(mk_rects(41, width=0.5))
    exp = np.asarray(traversal.visited_leaf_mask_per_level(dtree, q))
    fused = np.asarray(traversal.visited_leaf_mask(dtree, q, use_kernel=True))
    np.testing.assert_array_equal(fused, exp)


@pytest.mark.parametrize("L,fanout,B,tl", [
    (2048, 8, 64, 512),   # multi-leaf-tile grid: scratch persists across j
    (300, 5, 16, 128),
])
def test_tpu_form_kernel_matches_oracle(L, fanout, B, tl):
    """The hardware graph (one-hot MXU expansion, pl.when-guarded walk +
    early exit, VMEM-resident frontier scratch) — validated via interpret
    with ``tpu_form=True``, since plain interpret runs the branch-free
    gather form."""
    from repro.kernels import traverse_fused as tf
    mbrs, parents = synth_levels(L, fanout)
    q = jnp.asarray(np.concatenate([
        mk_rects(B - 4, width=0.5),
        np.tile(np.array([[90.0, 90.0, 91.0, 91.0]], np.float32), (4, 1)),
    ]))
    never = jnp.asarray([np.inf, np.inf, -np.inf, -np.inf], jnp.float32)

    def pad_level(m, p, mult):
        n = m.shape[0]
        padn = (-n) % mult
        if padn:
            m = jnp.concatenate([m, jnp.tile(never[None], (padn, 1))])
            p = jnp.concatenate([p, jnp.zeros((padn,), jnp.int32)])
        return m.T.astype(jnp.float32), p[None, :].astype(jnp.int32)

    int_m, int_p = [], []
    for i in range(len(mbrs) - 1):
        mt, pt = pad_level(mbrs[i], parents[i], tf.LANE)
        int_m.append(mt)
        if i > 0:
            int_p.append(pt)
    leaf_m, leaf_p = pad_level(mbrs[-1], parents[-1], tl)
    tb = (B + 7) // 8 * 8
    qp = jnp.concatenate(
        [q, jnp.zeros((tb - B, 4), jnp.float32)]) if tb != B else q
    out = tf.traverse_fused_t(qp.T, tuple(int_m), tuple(int_p), leaf_m,
                              leaf_p, tb=tb, tl=tl, interpret=True,
                              tpu_form=True)
    exp = np.asarray(ref.traverse_fused(q, mbrs, parents))
    np.testing.assert_array_equal(np.asarray(out)[:B, :L], exp)


def test_fused_escape_hatch(monkeypatch):
    """REPRO_KERNELS=off must route through the jnp oracle (still exact)."""
    monkeypatch.setenv("REPRO_KERNELS", "off")
    mbrs, parents = synth_levels(64, 4)
    q = jnp.asarray(mk_rects(9))
    out = np.asarray(ops.traverse_fused(q, mbrs, parents))
    exp = np.asarray(ref.traverse_fused(q, mbrs, parents))
    np.testing.assert_array_equal(out, exp)


def test_fused_vmem_gate_falls_back():
    """Trees whose estimated working set exceeds the VMEM budget route to
    the kernel-accelerated per-level loop — still exact."""
    from repro.kernels import traverse_fused as tf
    mbrs, parents = synth_levels(64, 4)
    q = jnp.asarray(mk_rects(5))
    exp = np.asarray(ref.traverse_fused(q, mbrs, parents))
    real_budget = tf.VMEM_BUDGET
    try:
        tf.VMEM_BUDGET = 1      # force every tree over the budget
        out = np.asarray(ops.traverse_fused(q, mbrs, parents))
    finally:
        tf.VMEM_BUDGET = real_budget
    np.testing.assert_array_equal(out, exp)


def test_vmem_estimate_counts_onehot_operands():
    """The gate must bound the one-hot matmul operands, not just the
    frontier: a wide consecutive level pair dominates the estimate."""
    from repro.kernels import traverse_fused as tf
    # widths 2048 → 8192: the (2048, 8192) one-hot alone is 64 MiB
    est = tf.vmem_estimate([128, 2048, 8192], tb=256, tl=512)
    assert est > 2048 * 8192 * 4
    assert est > tf.VMEM_BUDGET


# ---------------------------------------------------------------------------
# sort-free compaction vs top_k oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,L,k", [
    (16, 100, 8),
    (4, 7, 16),       # k > L
    (32, 257, 4),     # heavy overflow
    (3, 5, 5),        # k == L
    (1, 1, 1),
])
def test_compact_mask_matches_topk(B, L, k):
    mask = jnp.asarray(RNG.uniform(size=(B, L)) < 0.3)
    mask = mask.at[0].set(False)      # all-dead row
    mask = mask.at[-1].set(True)      # overflow row (count == L)
    i_new, v_new = traversal.compact_mask(mask, k)
    i_old, v_old = traversal.compact_mask_topk(mask, k)
    np.testing.assert_array_equal(np.asarray(v_new), np.asarray(v_old))
    # invalid slots carry arbitrary indices in the top_k version — compare
    # only through the validity mask
    np.testing.assert_array_equal(np.asarray(i_new * v_new),
                                  np.asarray(i_old * v_old))


def test_compact_mask_orders_by_leaf_id():
    mask = jnp.asarray([[False, True, False, True, True, False, True]])
    idx, valid = traversal.compact_mask(mask, 3)
    assert idx.tolist() == [[1, 3, 4]]       # first three set bits, in order
    assert valid.tolist() == [[True, True, True]]
    assert bool(traversal.overflowed(mask, 3)[0])


def test_gather_result_ids_matches_topk():
    rng = np.random.default_rng(3)
    B, K, M, L, mr = 12, 6, 16, 30, 20
    inside = jnp.asarray(rng.uniform(size=(B, K, M)) < 0.25)
    inside = inside.at[0].set(False)                       # empty row
    inside = inside.at[1].set(True)                        # overflow row
    leaf_idx = jnp.asarray(rng.integers(0, L, (B, K)), jnp.int32)
    valid = jnp.asarray(rng.uniform(size=(B, K)) > 0.2)
    refine = traversal.RefineResult(
        counts=jnp.sum(inside.astype(jnp.int32), -1),
        inside=inside, leaf_idx=leaf_idx, valid=valid)

    class FakeTree:
        leaf_entry_ids = jnp.asarray(rng.integers(0, 10_000, (L, M)),
                                     jnp.int32)

    new_ids, new_tr = traversal.gather_result_ids(FakeTree, refine, mr)
    old_ids, old_tr = traversal.gather_result_ids_topk(FakeTree, refine, mr)
    np.testing.assert_array_equal(np.asarray(new_ids), np.asarray(old_ids))
    np.testing.assert_array_equal(np.asarray(new_tr), np.asarray(old_tr))


def test_range_query_kernel_path_matches_jnp():
    """Full pipeline (fused traversal + sort-free compaction + kernels) is
    indistinguishable from the pure-jnp reference path."""
    rng = np.random.default_rng(11)
    pts = rng.normal(size=(4000, 2))
    tree = RTree(max_entries=16).insert_all(pts)
    dtree = dt.flatten(tree)
    q = jnp.asarray(mk_rects(41, rng, width=0.4))
    r_jnp = traversal.range_query(dtree, q, use_kernel=False)
    r_ker = traversal.range_query(dtree, q, use_kernel=True)
    for f in r_jnp._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(r_jnp, f)), np.asarray(getattr(r_ker, f)),
            err_msg=f)


# ---------------------------------------------------------------------------
# fused traversal + compaction epilogue (traverse_compact)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("L,fanout,B,k", [
    (37, 4, 7, 8),        # L, B far from tile multiples
    (130, 3, 64, 16),     # deep tree, non-power-of-two everything
    (512, 8, 33, 4),      # heavy overflow (k tiny)
    (2048, 8, 256, 64),   # multi-query-tile
    (1, 4, 5, 4),         # degenerate: root == single leaf
])
def test_traverse_compact_matches_oracle(L, fanout, B, k):
    """ops.traverse_compact == compact_mask_counted(jnp oracle mask)."""
    mbrs, parents = synth_levels(L, fanout)
    q = jnp.asarray(mk_rects(B, width=0.4))
    got = ops.traverse_compact(q, mbrs, parents, k)
    exp = traversal.compact_mask_counted(
        ref.traverse_fused(q, mbrs, parents), k)
    for g, e, name in zip(got, exp, ("idx", "valid", "count")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(e),
                                      err_msg=name)


@pytest.mark.parametrize("tpu_form", [True, False])
@pytest.mark.parametrize("L,fanout,B,tl,k", [
    (2048, 8, 64, 512, 64),   # multi-leaf-tile: rank base carried across j
    (300, 5, 16, 128, 16),
])
def test_traverse_compact_kernel_forms(L, fanout, B, tl, k, tpu_form):
    """Both kernel forms of the compaction epilogue (chunked rank-equality
    scatter on the TPU graph, rowwise binary search on the interpret graph)
    against the jnp oracle, with the running rank base exercised across
    multiple leaf tiles and dead rows mixed in."""
    from repro.kernels import traverse_fused as tf
    mbrs, parents = synth_levels(L, fanout)
    q = jnp.asarray(np.concatenate([
        mk_rects(B - 4, width=0.5),
        np.tile(np.array([[90.0, 90.0, 91.0, 91.0]], np.float32), (4, 1)),
    ]))
    never = jnp.asarray([np.inf, np.inf, -np.inf, -np.inf], jnp.float32)

    def pad_level(m, p, mult):
        n = m.shape[0]
        padn = (-n) % mult
        if padn:
            m = jnp.concatenate([m, jnp.tile(never[None], (padn, 1))])
            p = jnp.concatenate([p, jnp.zeros((padn,), jnp.int32)])
        return m.T.astype(jnp.float32), p[None, :].astype(jnp.int32)

    int_m, int_p = [], []
    for i in range(len(mbrs) - 1):
        mt, pt = pad_level(mbrs[i], parents[i], tf.LANE)
        int_m.append(mt)
        if i > 0:
            int_p.append(pt)
    leaf_m, leaf_p = pad_level(mbrs[-1], parents[-1], tl)
    tb = (B + 7) // 8 * 8
    qp = jnp.concatenate(
        [q, jnp.zeros((tb - B, 4), jnp.float32)]) if tb != B else q
    idx, cnt = tf.traverse_compact_t(
        qp.T, tuple(int_m), tuple(int_p), leaf_m, leaf_p,
        k=k, tb=tb, tl=tl, interpret=True, tpu_form=tpu_form)
    exp_i, exp_v, exp_c = traversal.compact_mask_counted(
        ref.traverse_fused(q, mbrs, parents), k)
    count = np.asarray(cnt)[:B, 0]
    np.testing.assert_array_equal(count, np.asarray(exp_c))
    valid = np.arange(k)[None, :] < count[:, None]
    np.testing.assert_array_equal(
        np.where(valid, np.asarray(idx)[:B, :k], 0), np.asarray(exp_i))
    # contract: slots past the count are zero in both forms
    assert (np.asarray(idx)[:B, :k][~valid] == 0).all()


def test_traverse_compact_escape_hatch_and_vmem_gate(monkeypatch):
    """Kernels-off and over-VMEM-budget fallbacks stay bit-identical."""
    from repro.kernels import traverse_fused as tf
    mbrs, parents = synth_levels(64, 4)
    q = jnp.asarray(mk_rects(9))
    exp = traversal.compact_mask_counted(
        ref.traverse_fused(q, mbrs, parents), 8)

    monkeypatch.setenv("REPRO_KERNELS", "off")
    got_off = ops.traverse_compact(q, mbrs, parents, 8)
    monkeypatch.delenv("REPRO_KERNELS")
    real_budget = tf.VMEM_BUDGET
    try:
        tf.VMEM_BUDGET = 1
        got_gate = ops.traverse_compact(q, mbrs, parents, 8)
    finally:
        tf.VMEM_BUDGET = real_budget
    for got in (got_off, got_gate):
        for g, e in zip(got, exp):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(e))


def _workload_queries(rng, B):
    """uniform / spatially clustered / all-dead query batches."""
    lo = rng.uniform(-1, 1, (B, 2))
    w = rng.uniform(0, 0.3, (B, 2))
    uniform = np.concatenate([lo, lo + w], 1).astype(np.float32)
    c = rng.uniform(-0.8, 0.6, (1, 2))
    lo = c + rng.uniform(0, 0.15, (B, 2))
    w = rng.uniform(0, 0.05, (B, 2))
    clustered = np.concatenate([lo, lo + w], 1).astype(np.float32)
    alldead = np.tile(np.array([[90.0, 90.0, 91.0, 91.0]], np.float32),
                      (B, 1))
    return {"uniform": uniform, "clustered": clustered, "alldead": alldead}


@pytest.mark.parametrize("workload", ["uniform", "clustered", "alldead"])
@pytest.mark.parametrize("use_kernel", [False, True])
def test_range_query_compact_matches_range_query(workload, use_kernel):
    """The serving pipeline (fused traverse+compact → refine) is per-field
    bit-identical to the full-mask range_query oracle."""
    rng = np.random.default_rng(11)
    pts = rng.normal(size=(3000, 2))
    tree = RTree(max_entries=16).insert_all(pts)
    dtree = dt.flatten(tree)
    q = jnp.asarray(_workload_queries(rng, 48)[workload])
    full = traversal.range_query(dtree, q, max_visited=64,
                                 use_kernel=False)
    comp = traversal.range_query_compact(dtree, q, max_visited=64,
                                         use_kernel=use_kernel)
    exp_i, exp_v, _ = traversal.compact_mask_counted(
        jnp.asarray(np.asarray(full.visited)), 64)
    np.testing.assert_array_equal(np.asarray(comp.leaf_idx),
                                  np.asarray(exp_i))
    np.testing.assert_array_equal(np.asarray(comp.valid), np.asarray(exp_v))
    for f in ("n_visited", "n_true", "n_results", "result_ids", "truncated"):
        np.testing.assert_array_equal(
            np.asarray(getattr(comp, f)), np.asarray(getattr(full, f)),
            err_msg=f"{workload}/{f}")


def test_range_query_compact_never_materializes_mask():
    """On the kernel path the lowered HLO must not contain any [B, L]- or
    [B, L_pad]-shaped tensor: the visited mask exists only tile-by-tile
    inside the kernel. (range_query, by contrast, does materialize it.)"""
    import re
    from repro.core.device_tree import DeviceTree, Level

    rng = np.random.default_rng(0)
    L, B = 1000, 256          # L_pad = 1024; tile_b = 128 < B
    mbrs, parents = synth_levels(L, 4)
    dtree = DeviceTree(
        levels=tuple(Level(mbrs=m, parent=p)
                     for m, p in zip(mbrs, parents)),
        leaf_entries=jnp.zeros((L, 2, 8), jnp.float32),
        leaf_entry_ids=jnp.zeros((L, 8), jnp.int32),
        leaf_counts=jnp.zeros((L,), jnp.int32),
        n_points=0, max_entries=4)
    q = jnp.zeros((B, 4), jnp.float32)

    def lowered(fn):
        return jax.jit(lambda t, qq: fn(t, qq)).lower(dtree, q).as_text()

    txt_c = lowered(lambda t, qq: traversal.range_query_compact(
        t, qq, max_visited=64, use_kernel=True, tile_b=128))
    txt_f = lowered(lambda t, qq: traversal.range_query(
        t, qq, max_visited=64, use_kernel=True))
    full_mask = re.compile(r"<256x(1000|1024)x")
    assert not full_mask.search(txt_c), "compact path materialized the mask"
    assert full_mask.search(txt_f), "oracle should materialize the mask"


def test_visited_leaves_compact_oracle_matches_kernel():
    """visited_leaves_compact: jnp path == kernel path on a real tree."""
    rng = np.random.default_rng(3)
    pts = rng.normal(size=(2000, 2))
    tree = RTree(max_entries=16).insert_all(pts)
    dtree = dt.flatten(tree)
    q = jnp.asarray(mk_rects(23, rng, width=0.6))
    a = traversal.visited_leaves_compact(dtree, q, 32, use_kernel=False)
    b = traversal.visited_leaves_compact(dtree, q, 32, use_kernel=True)
    for f in a._fields:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)), err_msg=f)
