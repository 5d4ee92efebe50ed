"""Jit'd public wrappers around the Pallas kernels.

Responsibilities:
  * pad irregular shapes up to kernel tile multiples and slice results back;
  * transpose rectangles to the planar [4, N] kernel layout;
  * compile the kernels with Mosaic on a TPU, and run them in interpret
    mode on any other backend (the CPU test reference);
  * fall back to the jnp oracle when ``REPRO_KERNELS=off`` (a CPU test
    reference); a VMEM-gate fallback to the oracle exists in interpret
    mode only — on a TPU an over-budget shape raises.
"""
from __future__ import annotations

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels import mbr_intersect as _mbr
from repro.kernels import leaf_refine as _refine
from repro.kernels import forest_infer as _forest
from repro.kernels import traverse_fused as _traverse
from repro.kernels import mlp_infer as _mlp
from repro.kernels import delta_probe as _delta
from repro.kernels import knn_browse as _knn
from repro.kernels import spatial_key as _skey
from repro.kernels import wkv6 as _wkv6


def kernels_enabled() -> bool:
    return os.environ.get("REPRO_KERNELS", "on").lower() not in ("off", "0")


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def mosaic_kernels(compiled_text: str) -> set:
    """Names of the Pallas kernels a compiled program calls as Mosaic
    custom calls (``tpu_custom_call``), read from each call's
    ``op_name`` (every kernel's ``pallas_call`` is named)."""
    names = set()
    for line in compiled_text.splitlines():
        if "tpu_custom_call" in line:
            names.update(re.findall(r'/(\w+)/pallas_call"', line))
    return names


def _pad_to(x: jnp.ndarray, axis: int, mult: int, value) -> jnp.ndarray:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def mbr_intersect(queries: jnp.ndarray, mbrs: jnp.ndarray,
                  tb: int | None = None, tn: int | None = None) -> jnp.ndarray:
    """[B, 4] × [N, 4] → [B, N] bool."""
    if not kernels_enabled():
        return ref.mbr_intersect(queries, mbrs)
    B, N = queries.shape[0], mbrs.shape[0]
    tb = tb or min(_mbr.DEF_TB, max(8, B))
    tn = tn or _mbr.DEF_TN
    # pad with rectangles that can never intersect (inverted infinite rects)
    qp = _pad_to(queries.astype(jnp.float32), 0, tb, 0.0)
    never = jnp.asarray([jnp.inf, jnp.inf, -jnp.inf, -jnp.inf], jnp.float32)
    mp = _pad_to(mbrs.astype(jnp.float32), 0, tn, 0.0)
    if mp.shape[0] != N:
        mp = mp.at[N:].set(never)
    out = _mbr.mbr_intersect_t(qp.T, mp.T, tb=tb, tn=tn,
                               interpret=_interpret())
    return out[:B, :N]


_NEVER_RECT = (float("inf"), float("inf"), float("-inf"), float("-inf"))


def _fused_tiles(B: int, L: int, tb: int | None, tl: int | None,
                 n_levels: int | None = None
                 ) -> tuple[int, int, bool, dict]:
    """Tile choice shared by the fused traversal entry points.

    Resolution order per knob: explicit caller override → autotune cache
    entry for this exact (form, B, L, height) shape (see
    ``traverse_fused.tuned_tiles`` / ``benchmarks/autotune.py``) →
    hand-picked default. The defaults: on TPU, DEF_TB×DEF_TL VMEM tiles
    (grid cells are nearly free and pl.when early exit works per tile); in
    interpret mode fold everything into one tile per query-block —
    emulated grid cells are not free, the walk would rerun per leaf tile,
    and the interpret form early-exits on SUB_TL subtiles *inside* the
    kernel instead. Also returns the cache entry so callers can thread the
    epilogue knobs (``sub_tl``, ``kc``) through to the kernel.
    """
    interp = _interpret()
    tune = _traverse.tuned_tiles(B, L, n_levels, interp) \
        if n_levels is not None else {}
    L128 = (max(128, L) + 127) // 128 * 128
    if tb is None:
        tb = tune.get("tb") or min(1024 if interp else _traverse.DEF_TB,
                                   (max(8, B) + 7) // 8 * 8)
    if tl is None:
        tl = tune.get("tl") or (
            L128 if interp and L128 <= 8192 else
            min(_traverse.DEF_TL, L128))
    return tb, tl, interp, tune


def _fused_operands(queries: jnp.ndarray, level_mbrs, level_parents,
                    tb: int, tl: int):
    """Pad + transpose tree levels to the planar kernel layout."""
    never = jnp.asarray(_NEVER_RECT, jnp.float32)

    def pad_level(mbrs, parent, mult):
        n = mbrs.shape[0]
        mp = _pad_to(mbrs.astype(jnp.float32), 0, mult, 0.0)
        if mp.shape[0] != n:
            mp = mp.at[n:].set(never)
        pp = _pad_to(parent.astype(jnp.int32), 0, mult, 0)
        return mp.T, pp[None, :]

    qp = _pad_to(queries.astype(jnp.float32), 0, tb, 0.0)
    int_mbrs_t, int_parents = [], []
    for lvl in range(len(level_mbrs) - 1):
        mt, pt = pad_level(level_mbrs[lvl], level_parents[lvl],
                           _traverse.LANE)
        int_mbrs_t.append(mt)
        if lvl > 0:
            int_parents.append(pt)
    leaf_mt, leaf_pt = pad_level(level_mbrs[-1], level_parents[-1], tl)
    return qp, tuple(int_mbrs_t), tuple(int_parents), leaf_mt, leaf_pt


def _per_level_kernel_mask(queries: jnp.ndarray, level_mbrs,
                           level_parents) -> jnp.ndarray:
    """Kernel-accelerated per-level fallback (frontier masks round-trip
    HBM, but each level's intersection still runs on the kernel)."""
    mask = mbr_intersect(queries, level_mbrs[0])
    for mbrs, parent in zip(level_mbrs[1:], level_parents[1:]):
        mask = mask[:, parent] & mbr_intersect(queries, mbrs)
    return mask


def _slices_usable(sl, n_levels: int, L: int) -> bool:
    """Does this AncestorTable match the tree shape being dispatched?

    A table built for a different padding/sharding of the same logical tree
    (wrong tile count / level count) must be rejected, not trusted."""
    if sl is None:
        return False
    try:
        st = sl.starts
        return (getattr(st, "ndim", 0) == 2
                and st.shape[0] == n_levels - 1
                and len(sl.widths) == n_levels - 1
                and st.shape[1] == -(-L // sl.tl))
    except (AttributeError, TypeError):
        return False


def _build_slices_if_concrete(level_parents, B: int, L: int,
                              n_levels: int, interp: bool):
    """Build an ancestor table on the fly for callers that passed raw
    level arrays (no ``DeviceTree``) — only possible outside a trace,
    where the parent arrays are concrete."""
    if any(isinstance(p, jax.core.Tracer) for p in level_parents):
        return None
    tune = _traverse.tuned_tiles_for_key(
        _traverse.tune_key_sliced(B, L, n_levels, interp))
    from repro.core.device_tree import build_ancestor_table
    return build_ancestor_table(level_parents,
                                tl=tune.get("tl") or _traverse.DEF_TL)


def _sliced_operands(queries: jnp.ndarray, level_mbrs, level_parents,
                     sl, tb: int):
    """Pad + transpose for the sliced kernels: each internal level to a
    multiple of its window width (BlockSpec windows must tile the padded
    axis), the leaf level to the table's tile granularity. Pad lanes carry
    never-intersecting rects, so whatever window they land in they stay
    dead; the leaf parent pad repeats the last real parent so pad lanes
    index in-window (dead via their never-rects, not via wraparound)."""
    never = jnp.asarray(_NEVER_RECT, jnp.float32)

    def pad_level(mbrs, parent, mult, pfill):
        n = mbrs.shape[0]
        mp = _pad_to(mbrs.astype(jnp.float32), 0, mult, 0.0)
        if mp.shape[0] != n:
            mp = mp.at[n:].set(never)
        pp = parent.astype(jnp.int32)
        pad = (-n) % mult
        if pad:
            pp = jnp.concatenate(
                [pp, jnp.full((pad,), pfill, jnp.int32)])
        return mp.T, pp[None, :]

    qp = _pad_to(queries.astype(jnp.float32), 0, tb, 0.0)
    int_mbrs_t, int_parents = [], []
    for lvl in range(len(level_mbrs) - 1):
        mt, pt = pad_level(level_mbrs[lvl], level_parents[lvl],
                           sl.widths[lvl], 0)
        int_mbrs_t.append(mt)
        if lvl > 0:
            int_parents.append(pt)
    leaf_mt, leaf_pt = pad_level(level_mbrs[-1], level_parents[-1], sl.tl,
                                 level_parents[-1][-1])
    return qp, tuple(int_mbrs_t), tuple(int_parents), leaf_mt, leaf_pt


def _sliced_call(queries: jnp.ndarray, level_mbrs, level_parents, sl,
                 tb: int, interp: bool, *, k: int | None = None):
    """Dispatch to the ancestor-sliced kernel form; ``None`` when the
    table is unusable or even the sliced working set exceeds the budget
    (degenerate tables whose windows capped out at full level width).

    ``k=None`` → dense mask [Bp, Lp]; else → ``(idx [Bp, KP], cnt
    [Bp, 1])`` with ``traverse_compact_t``'s slot contract.
    """
    if sl is None:
        return None
    n_levels = len(level_mbrs)
    B = queries.shape[0]
    L = level_mbrs[-1].shape[0]
    stune = _traverse.tuned_tiles_for_key(
        _traverse.tune_key_sliced(B, L, n_levels, interp))
    tb = stune.get("tb") or tb
    sub_tl = stune.get("sub_tl", _traverse.SUB_TL)
    kc = stune.get("kc", _traverse.COMPACT_KC)
    if k is None:
        est = _traverse.vmem_estimate_sliced(sl.widths, tb, sl.tl,
                                             tpu_form=not interp)
    else:
        kp = k if interp else \
            (k + _traverse.LANE - 1) // _traverse.LANE * _traverse.LANE
        est = _traverse.vmem_estimate_sliced_compact(
            sl.widths, tb, sl.tl, kp, tpu_form=not interp)
    if est > _traverse.VMEM_BUDGET:
        return None
    qp, int_mbrs_t, int_parents, leaf_mt, leaf_pt = _sliced_operands(
        queries, level_mbrs, level_parents, sl, tb)
    if k is None:
        return _traverse.traverse_fused_sliced_t(
            sl.starts, qp.T, int_mbrs_t, int_parents, leaf_mt, leaf_pt,
            widths=sl.widths, tb=tb, tl=sl.tl, sub_tl=sub_tl,
            interpret=interp)
    return _traverse.traverse_compact_sliced_t(
        sl.starts, qp.T, int_mbrs_t, int_parents, leaf_mt, leaf_pt,
        k=k, widths=sl.widths, tb=tb, tl=sl.tl, sub_tl=sub_tl, kc=kc,
        interpret=interp)


def traverse_fused(queries: jnp.ndarray, level_mbrs, level_parents,
                   tb: int | None = None, tl: int | None = None,
                   slices=None) -> jnp.ndarray:
    """Fused root→leaf traversal: [B, 4] → visited-leaf mask [B, L] bool.

    ``level_mbrs``: one [N_l, 4] array per tree level, root first, leaf
    level last. ``level_parents``: matching [N_l] i32 index into the level
    above (entry 0 unused). Single ``pallas_call`` — the internal frontier
    stays in VMEM; only the leaf mask is written to HBM. ``slices`` is the
    tree's ``AncestorTable`` (``DeviceTree.aslices``), if the caller has
    one.

    Falls back to the jnp oracle when kernels are off; when the tree is a
    single level (root == leaves) it is one ``mbr_intersect``. When the
    estimated full-replication VMEM working set (frontier scratch +
    replicated internal operands + largest one-hot expansion) exceeds the
    budget, the **ancestor-sliced** form takes over — same fused walk, but
    each leaf tile stages only its scalar-prefetched ancestor windows, so
    the working set no longer grows with the tree (the table comes from
    ``slices``, or is built on the fly when the parent arrays are
    concrete). Only when even that is impossible (tracing without a table,
    or a degenerate table whose windows capped out at full level width)
    does it run the level-by-level loop with the ``mbr_intersect``
    *kernel* per level — never a silent drop to pure jnp.
    """
    n_levels = len(level_mbrs)
    B = queries.shape[0]
    L = level_mbrs[-1].shape[0]
    if not kernels_enabled():
        return ref.traverse_fused(queries, level_mbrs, level_parents)
    if n_levels == 1:
        return mbr_intersect(queries, level_mbrs[0])

    tb, tl, interp, tune = _fused_tiles(B, L, tb, tl, n_levels)
    sub_tl = tune.get("sub_tl", _traverse.SUB_TL)
    widths = [int(m.shape[0]) for m in level_mbrs[:-1]]
    padded = [n + (-n) % _traverse.LANE for n in widths]
    if _traverse.vmem_estimate(padded, tb, tl) > _traverse.VMEM_BUDGET:
        sl = slices if _slices_usable(slices, n_levels, L) else \
            _build_slices_if_concrete(level_parents, B, L, n_levels,
                                      interp)
        out = _sliced_call(queries, level_mbrs, level_parents, sl, tb,
                           interp)
        if out is not None:
            return out[:B, :L]
        return _per_level_kernel_mask(queries, level_mbrs, level_parents)
    qp, int_mbrs_t, int_parents, leaf_mt, leaf_pt = _fused_operands(
        queries, level_mbrs, level_parents, tb, tl)
    out = _traverse.traverse_fused_t(
        qp.T, int_mbrs_t, int_parents, leaf_mt, leaf_pt,
        tb=tb, tl=tl, sub_tl=sub_tl, interpret=interp)
    return out[:B, :L]


def traverse_compact(queries: jnp.ndarray, level_mbrs, level_parents,
                     k: int, tb: int | None = None, tl: int | None = None,
                     slices=None
                     ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused traversal + compaction: [B, 4] → ``(leaf_idx [B, k] i32,
    valid [B, k] bool, count [B] i32)``.

    Semantically ``compact_mask(traverse_fused(...), k)`` plus the per-row
    visited count, but on the kernel path the ``[B, L]`` visited mask never
    leaves VMEM: the traversal kernel's compaction epilogue ranks set
    leaves by exclusive prefix count per leaf tile and scatters the first
    ``k`` leaf ids (leaf-ID order) straight into the ``[B, K]`` slot table.
    This is the serving-path entry point — training/labels keep the dense
    ``traverse_fused`` mask.

    The fallback ladder mirrors ``traverse_fused`` (jnp oracle when kernels
    are off, one ``mbr_intersect`` for single-level trees, the
    ancestor-sliced kernel when over the full-replication VMEM budget, the
    per-level kernel loop only as last resort); the dense-mask fallbacks
    compact with the jnp ``compact_mask`` scheme, so every path is
    bit-identical.
    """
    from repro.core.traversal import compact_mask_counted

    n_levels = len(level_mbrs)
    B = queries.shape[0]
    if not kernels_enabled():
        return compact_mask_counted(
            ref.traverse_fused(queries, level_mbrs, level_parents), k)
    if n_levels == 1:
        return compact_mask_counted(
            mbr_intersect(queries, level_mbrs[0]), k)

    L = level_mbrs[-1].shape[0]
    tb, tl, interp, tune = _fused_tiles(B, L, tb, tl, n_levels)
    sub_tl = tune.get("sub_tl", _traverse.SUB_TL)
    kc = tune.get("kc", _traverse.COMPACT_KC)
    kp = k if interp else \
        (k + _traverse.LANE - 1) // _traverse.LANE * _traverse.LANE
    widths = [int(m.shape[0]) for m in level_mbrs[:-1]]
    padded = [n + (-n) % _traverse.LANE for n in widths]
    if _traverse.vmem_estimate_compact(padded, tb, tl, kp,
                                       tpu_form=not interp) > \
            _traverse.VMEM_BUDGET:
        sl = slices if _slices_usable(slices, n_levels, L) else \
            _build_slices_if_concrete(level_parents, B, L, n_levels,
                                      interp)
        out = _sliced_call(queries, level_mbrs, level_parents, sl, tb,
                           interp, k=k)
        if out is not None:
            idx, cnt = out
            count = cnt[:B, 0]
            valid = jnp.arange(k, dtype=jnp.int32)[None, :] < \
                count[:, None]
            return jnp.where(valid, idx[:B, :k], 0), valid, count
        return compact_mask_counted(
            _per_level_kernel_mask(queries, level_mbrs, level_parents), k)
    qp, int_mbrs_t, int_parents, leaf_mt, leaf_pt = _fused_operands(
        queries, level_mbrs, level_parents, tb, tl)
    idx, cnt = _traverse.traverse_compact_t(
        qp.T, int_mbrs_t, int_parents, leaf_mt, leaf_pt,
        k=k, tb=tb, tl=tl, sub_tl=sub_tl, kc=kc, interpret=interp)
    count = cnt[:B, 0]
    valid = jnp.arange(k, dtype=jnp.int32)[None, :] < count[:, None]
    return jnp.where(valid, idx[:B, :k], 0), valid, count


def _mlp_vmem(bank, S: int, k: int, interp: bool, tb: int, tl: int,
              n_cells: int | None = None) -> int:
    """``vmem_estimate_mlp`` at the padded cell and label-slot counts the
    kernel really sees (as ``mlp_predict_compact`` pads them)."""
    C, F, H = bank.w1.shape
    C = n_cells or C
    Cl = bank.w2.shape[-1]
    kp = k if interp else \
        (k + _traverse.LANE - 1) // _traverse.LANE * _traverse.LANE
    Cp = C + (-C) % _mlp.CELL_BLOCK
    Clp = Cl if interp else Cl + (-Cl) % _traverse.LANE
    return _mlp.vmem_estimate_mlp(Cp, F, H, Clp, S, tb, tl, kp,
                                  tpu_form=not interp)


def _mlp_tiles(B: int, n_leaves: int, bank, S: int, k: int, interp: bool,
               tb: int | None = None, tl: int | None = None,
               n_cells: int | None = None) -> tuple[int, int, int, int]:
    """Tile resolution for the fused prediction kernel: explicit caller
    override → autotune cache entry (``mlp-`` form keys) → hand-picked
    default. Returns ``(tb, tl, kc, Lp)`` with ``Lp`` the lane-padded
    leaf count (the kernel's scatter axis).

    TPU form: a default query tile over the VMEM budget is halved while
    it stays a multiple of the lane quantum — the inference stage's
    candidate list (``[tb, S·Cl]``) grows with the bank's label slots."""
    C = n_cells or bank.w1.shape[0]
    tune = _mlp.tuned_tiles_mlp(B, n_leaves, C, bank.w2.shape[-1], interp)
    Lp = (max(128, n_leaves) + 127) // 128 * 128
    if tl is None:
        # interpret folds the whole (lane-padded) leaf axis into one tile —
        # emulated grid cells are not free and the walk has no scratch there
        tl = tune.get("tl") or (Lp if interp else min(_mlp.DEF_TL, Lp))
    if tb is None:
        tb = tune.get("tb") or min(1024 if interp else _mlp.DEF_TB,
                                   (max(8, B) + 7) // 8 * 8)
        if not interp and tb < B:
            # the union stage blocks the transposed candidate list
            # [S·Cl, B] by tb lanes
            tb = max(_traverse.LANE, tb // _traverse.LANE * _traverse.LANE)
        while (not interp and tb % (2 * _traverse.LANE) == 0
               and _mlp_vmem(bank, S, k, interp, tb, tl, n_cells)
               > _traverse.VMEM_BUDGET):
            tb //= 2
    kc = tune.get("kc", _traverse.COMPACT_KC)
    return tb, tl, kc, Lp


def _mlp_gate(B: int, bank, S: int, n_leaves: int, k: int,
              tb: int | None = None, tl: int | None = None,
              n_cells: int | None = None) -> bool:
    """True iff the resolved fused-kernel form fits the VMEM budget.
    ``n_cells`` overrides the bank's cell count for callers asking about
    a *shard* of the bank."""
    interp = _interpret()
    tb, tl, _, _ = _mlp_tiles(B, n_leaves, bank, S, k, interp, tb, tl,
                              n_cells)
    return _mlp_vmem(bank, S, k, interp, tb, tl, n_cells) \
        <= _traverse.VMEM_BUDGET


def mlp_fused_active(B: int, bank, S: int, n_leaves: int, k: int,
                     n_cells: int | None = None) -> bool:
    """Would ``mlp_predict_compact`` take the fused kernel path for this
    shape? (False when kernels are off or the VMEM gate routes to the
    dense oracle — callers reporting 'score table eliminated' must check
    the actual dispatch, not just their own flags.) Pass the *per-shard*
    ``B``/``n_cells``/``n_leaves`` when asking about the sharded engine —
    its dispatch sees shard-local shapes."""
    return kernels_enabled() and _mlp_gate(B, bank, S, n_leaves, k,
                                           n_cells=n_cells)


def _oracle_rung(kernel: str) -> None:
    """Gate for the VMEM fallback rungs: in interpret mode the dense
    oracle stands in (bit-identical); on a TPU an over-budget shape is a
    tiling fault to fix, never a silent drop off the device's kernels."""
    if not _interpret():
        raise ValueError(
            f"{kernel}: shape exceeds the kernel's VMEM budget "
            f"({_traverse.VMEM_BUDGET} bytes); refusing the jnp-oracle "
            f"fallback on TPU")


def mlp_predict_compact(queries: jnp.ndarray, bank, cell_ids: jnp.ndarray,
                        slot_ok: jnp.ndarray, *, n_leaves: int, k: int,
                        threshold: float, tb: int | None = None,
                        tl: int | None = None
                        ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused AI-path prediction: queries [B, 4] + cell routing → compact
    predicted-leaf slots ``(leaf_idx [B, k] i32, valid [B, k] bool,
    count [B] i32)``.

    Semantically ``compact_mask_counted(predict_scores(...) > threshold,
    k)``, but on the kernel path the ``[B, n_leaves]`` score table never
    exists: classifier inference, sigmoid+threshold, the ``label_map``
    union and the cumsum-rank compaction all run in ``kernels.mlp_infer``.
    ``bank`` is an ``MLPBank``-shaped object
    (``w1/b1/w2/b2/mu/sd/label_map/lmask`` — duck-typed so this module
    stays core-free); ``cell_ids``/``slot_ok`` [B, S] come from
    ``grid.cells_of_queries``. Requires ``threshold ≥ 0`` (see
    ``mlp_infer`` module docs).

    Fallback ladder mirrors ``traverse_compact``: the jnp dense oracle
    when kernels are off, and in interpret mode when the form-aware VMEM
    estimate exceeds the budget (bit-identical); on a TPU an over-budget
    shape raises. Tile knobs resolve explicit override → autotune cache
    entry for this (form, B, L, C, Cl) shape → hand-picked default.
    """
    assert threshold >= 0, "dense-oracle parity requires threshold >= 0"
    B = queries.shape[0]
    S = cell_ids.shape[1]
    C = bank.w1.shape[0]
    x = (queries.astype(jnp.float32) - bank.mu) / bank.sd
    cid = jnp.clip(cell_ids.astype(jnp.int32), 0, C - 1)

    if not kernels_enabled() or not _mlp_gate(B, bank, S, n_leaves, k,
                                              tb, tl):
        if kernels_enabled():
            _oracle_rung("mlp_predict_compact")
        return ref.mlp_predict_compact(
            x, cid, slot_ok, bank.w1, bank.b1, bank.w2, bank.b2,
            bank.label_map, bank.lmask, n_leaves=n_leaves, k=k,
            threshold=threshold)
    interp = _interpret()
    tb, tl, kc, Lp = _mlp_tiles(B, n_leaves, bank, S, k, interp, tb, tl)
    xp = _pad_to(x, 0, tb, 0.0)
    cidp = _pad_to(cid, 0, tb, 0)
    okp = _pad_to(slot_ok.astype(jnp.int32), 0, tb, 0)
    # label slots that predict nothing fold into the map as -1; padding
    # cells are never routed to (ids are clipped below C), padding label
    # slots carry -1
    lm = jnp.where(bank.lmask, bank.label_map, -1).astype(jnp.int32)
    w1, b1, w2, b2 = bank.w1, bank.b1, bank.w2, bank.b2
    cell_mult = _mlp.CELL_BLOCK
    if not interp:
        w2 = _pad_to(w2, 2, _traverse.LANE, 0.0)
        b2 = _pad_to(b2, 1, _traverse.LANE, 0.0)
        lm = _pad_to(lm, 1, _traverse.LANE, -1)
    w1, b1, w2, b2 = (_pad_to(a, 0, cell_mult, 0.0) for a in (w1, b1, w2,
                                                               b2))
    lm = _pad_to(lm, 0, cell_mult, -1)
    lpt = Lp + (-Lp) % tl
    idx, cnt = _mlp.mlp_predict_compact_t(
        xp, cidp, okp, w1, b1, w2, b2, lm, k=k, lp=lpt,
        thr=float(threshold), tb=tb, tl=tl, kc=kc, interpret=interp)
    count = cnt[:B, 0]
    valid = jnp.arange(k, dtype=jnp.int32)[None, :] < count[:, None]
    return jnp.where(valid, idx[:B, :k], 0), valid, count


def _delta_tiles(B: int, cap: int, interp: bool, tb: int | None = None,
                 tn: int | None = None) -> tuple[int, int, int]:
    """Tile resolution for the delta-probe kernel: explicit caller
    override → autotune cache entry (``delta-`` form keys) → hand-picked
    default. Interpret mode folds the whole (lane-padded) buffer into one
    tile, like the other kernels' leaf-axis folds."""
    tune = _delta.tuned_tiles_delta(B, cap, interp)
    Np = (max(128, cap) + 127) // 128 * 128
    if tb is None:
        tb = tune.get("tb") or min(1024 if interp else _delta.DEF_TB,
                                   (max(8, B) + 7) // 8 * 8)
    if tn is None:
        tn = tune.get("tl") or (Np if interp else min(_delta.DEF_TN, Np))
    kc = tune.get("kc", _traverse.COMPACT_KC)
    return tb, tn, kc


def delta_probe(queries: jnp.ndarray, pts: jnp.ndarray, *, k: int,
                tb: int | None = None, tn: int | None = None
                ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Probe the insert delta buffer: queries [B, 4] × buffer points
    [cap, 2] → compact hit slots ``(slot_idx [B, k] i32, valid [B, k]
    bool, count [B] i32)`` in insertion order.

    Semantically ``compact_mask_counted(contains(queries, pts), k)``, but
    on the kernel path the ``[B, cap]`` containment mask stays in VMEM
    tile-by-tile and never reaches HBM (absent from the lowered HLO — the
    slot-table contract the serving paths share). Unstaged/padding buffer
    slots must hold +inf coordinates (``core.delta`` maintains that);
    ``count`` is the row's full hit total, so overflow (``count > k``)
    survives compaction exactly as the other compact wrappers' counts do.

    Fallback ladder mirrors ``traverse_compact``: the jnp dense oracle
    when kernels are off or the form-aware VMEM estimate exceeds the
    budget — bit-identical either way. Tile knobs resolve explicit
    override → autotune cache entry (``delta-*`` keys) → default.
    """
    B = queries.shape[0]
    cap = pts.shape[0]
    if not kernels_enabled():
        return ref.delta_probe(queries, pts, k)
    interp = _interpret()
    tb, tn, kc = _delta_tiles(B, cap, interp, tb, tn)
    kp = k if interp else \
        (k + _traverse.LANE - 1) // _traverse.LANE * _traverse.LANE
    if _delta.vmem_estimate_delta(tb, tn, kp, tpu_form=not interp) > \
            _traverse.VMEM_BUDGET:
        _oracle_rung("delta_probe")
        return ref.delta_probe(queries, pts, k)
    qp = _pad_to(queries.astype(jnp.float32), 0, tb, 0.0)
    pp = _pad_to(pts.astype(jnp.float32), 0, tn, jnp.inf)
    idx, cnt = _delta.delta_probe_t(qp.T, pp.T, k=k, tb=tb, tn=tn, kc=kc,
                                    interpret=interp)
    count = cnt[:B, 0]
    valid = jnp.arange(k, dtype=jnp.int32)[None, :] < count[:, None]
    return jnp.where(valid, idx[:B, :k], 0), valid, count


def spatial_key(queries: jnp.ndarray, bbox: jnp.ndarray | None = None,
                curve: str = "hilbert", order: int = _skey.DEF_ORDER,
                tb: int | None = None) -> jnp.ndarray:
    """Space-filling-curve keys for query rects: [B, 4] → [B] i32.

    Rect centers are normalized by ``bbox`` ([4] xmin/ymin/xmax/ymax —
    pass the *workload* bounding box so keys are comparable across
    batches; defaults to the batch's own extent) and quantized to
    ``order``-bit coordinates before the bit walk. ``curve`` is
    ``"hilbert"`` (better locality) or ``"morton"`` (cheaper).
    """
    q = queries.astype(jnp.float32)
    cx = (q[:, 0] + q[:, 2]) * 0.5
    cy = (q[:, 1] + q[:, 3]) * 0.5
    if bbox is None:
        bbox = jnp.stack([jnp.min(cx), jnp.min(cy),
                          jnp.max(cx), jnp.max(cy)])
    bbox = jnp.asarray(bbox, jnp.float32)
    span = jnp.maximum(bbox[2:] - bbox[:2], 1e-12)
    cxy = (jnp.stack([cx, cy], axis=1) - bbox[None, :2]) / span[None, :]
    if not kernels_enabled():
        return ref.spatial_key(cxy, curve=curve, order=order)
    B = queries.shape[0]
    tb = tb or min(_skey.DEF_TB, (max(128, B) + 127) // 128 * 128)
    cp = _pad_to(cxy, 0, tb, 0.0)
    out = _skey.spatial_key_t(cp.T, curve=curve, order=order, tb=tb,
                              interpret=_interpret())
    return out[0, :B]


def leaf_refine(queries: jnp.ndarray, leaf_entries: jnp.ndarray,
                leaf_idx: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """queries [B,4], leaf_entries [L,2,M], leaf_idx [B,K], valid [B,K]
    → inside [B, K, M] bool."""
    if not kernels_enabled():
        return ref.leaf_refine(queries, leaf_entries, leaf_idx, valid)
    # clamp padded slots to leaf 0 (masked out by ``valid`` in-kernel)
    safe_idx = jnp.clip(leaf_idx, 0, leaf_entries.shape[0] - 1)
    return _refine.leaf_refine(queries, leaf_entries, safe_idx, valid,
                               interpret=_interpret())


def knn_browse(centers: jnp.ndarray, leaf_entries: jnp.ndarray,
               leaf_idx: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Distance-browse compact visited-leaf slots: centers [B, 3]
    (cx, cy, r²), leaf_entries [L, 2, M], leaf_idx/valid [B, K]
    → d2 [B, K, M] f32 (+inf where masked).

    The kNN serving primitive: only the leaves named in the slot table
    are touched (scalar-prefetched tiles on the TPU form, an XLA gather
    on the folded interpret form — see ``kernels.knn_browse``); the
    caller's top-k over the flat ``[B, K·M]`` view yields the k nearest
    within the probed radius. Fallback ladder mirrors
    ``mlp_predict_compact``: the jnp oracle when kernels are off, and in
    interpret mode when the form-aware VMEM estimate exceeds the budget —
    bit-identical either way; on a TPU an over-budget shape raises. The
    autotune cache is consulted under ``knn-*`` keys for a pinned form
    (``fold_k``).
    """
    if not kernels_enabled():
        return ref.knn_browse(centers, leaf_entries, leaf_idx, valid)
    interp = _interpret()
    B, K = leaf_idx.shape
    M = leaf_entries.shape[2]
    tune = _knn.tuned_tiles_knn(B, K, M, interp)
    fold = tune.get("fold_k")
    fold = interp if fold is None else bool(fold)
    if _knn.vmem_estimate_knn(B, K, M, tpu_form=not fold) > \
            _traverse.VMEM_BUDGET:
        _oracle_rung("knn_browse")
        return ref.knn_browse(centers, leaf_entries, leaf_idx, valid)
    # clamp padded slots to leaf 0 (masked out by ``valid`` in-kernel)
    safe_idx = jnp.clip(leaf_idx, 0, leaf_entries.shape[0] - 1)
    return _knn.knn_browse(centers, leaf_entries, safe_idx, valid,
                           interpret=interp, fold_k=fold)


def forest_infer(features: jnp.ndarray, feat_idx: jnp.ndarray,
                 thresh: jnp.ndarray, tables: jnp.ndarray,
                 tb: int | None = None) -> jnp.ndarray:
    """features [B,F], feat_idx [T,D] i32, thresh [T,D], tables [T,2^D,C]
    → scores [B,C] (summed votes)."""
    B = features.shape[0]
    sel = features[:, feat_idx]                 # [B, T, D] pre-gather
    if not kernels_enabled():
        return ref.forest_infer(sel, thresh, tables)
    tb = tb or min(_forest.DEF_TB, max(8, B))
    selp = _pad_to(sel, 0, tb, 0.0)
    out = _forest.forest_infer(selp, thresh, tables, tb=tb,
                               interpret=_interpret())
    return out[:B]


def forest_infer_cells(features: jnp.ndarray, feat_idx: jnp.ndarray,
                       thresh: jnp.ndarray, tables: jnp.ndarray,
                       n_cells: int, tb: int | None = None) -> jnp.ndarray:
    """Celled variant: feat_idx/thresh [C·T, D], tables [C·T, 2^D, Cl]
    → votes [B, C, Cl] (per-cell tree-vote sums)."""
    B = features.shape[0]
    sel = features[:, feat_idx]                 # [B, C·T, D]
    if not kernels_enabled():
        T = feat_idx.shape[0] // n_cells
        flat = ref.forest_infer_percell(sel, thresh, tables)
        return flat.reshape(B, n_cells, T, -1).sum(axis=2)
    tb = tb or min(_forest.DEF_TB, max(8, B))
    selp = _pad_to(sel, 0, tb, 0.0)
    out = _forest.forest_infer_cells(selp, thresh, tables, n_cells=n_cells,
                                     tb=tb, interpret=_interpret())
    return out[:B]


def _wkv6_kernel_padded(r, k, v, w, u, chunk):
    T = r.shape[1]
    if T % chunk != 0:
        # pad time with identity steps (w=1, k=0 → state & outputs unaffected)
        pad = (-T) % chunk
        r2 = _pad_to(r, 1, chunk, 0.0)
        k2 = _pad_to(k, 1, chunk, 0.0)
        v2 = _pad_to(v, 1, chunk, 0.0)
        w2 = jnp.pad(w, ((0, 0), (0, pad), (0, 0)), constant_values=1.0)
        out = _wkv6.wkv6(r2, k2, v2, w2, u, chunk=chunk,
                         interpret=_interpret())
        return out[:, :T]
    return _wkv6.wkv6(r, k, v, w, u, chunk=chunk, interpret=_interpret())


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _wkv6_ad(r, k, v, w, u, chunk):
    return _wkv6_kernel_padded(r, k, v, w, u, chunk)


def _wkv6_fwd(r, k, v, w, u, chunk):
    return _wkv6_kernel_padded(r, k, v, w, u, chunk), (r, k, v, w, u)


def _wkv6_bwd(chunk, res, ct):
    # Backward through the pure-jnp oracle (recompute); a dedicated backward
    # kernel is a known optimization left on the table — see EXPERIMENTS.md.
    _, vjp = jax.vjp(ref.wkv6, *res)
    return vjp(ct)


_wkv6_ad.defvjp(_wkv6_fwd, _wkv6_bwd)


def wkv6(r: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, w: jnp.ndarray,
         u: jnp.ndarray, chunk: int = _wkv6.DEF_CHUNK) -> jnp.ndarray:
    """RWKV-6 scan: r/k/w [BH,T,dk], v [BH,T,dv], u [BH,dk] → y [BH,T,dv].

    Differentiable: forward runs the chunked Pallas kernel; the VJP
    recomputes through the sequential reference (checkpoint-style).
    """
    if not kernels_enabled():
        return ref.wkv6(r, k, v, w, u)
    return _wkv6_ad(r, k, v, w, u, chunk)
