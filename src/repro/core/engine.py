"""Distributed batched serving engine for the "AI+R"-tree.

Sharding layout on the production mesh (pod, data, model):

  * queries                 → split over (pod, data)  — traffic parallelism
  * leaf entries / leaf MBRs→ split over model        — the tree's "pages"
  * grid-cell experts       → split over model        — expert parallelism
  * internal levels, router → replicated              — tiny, read-only

Per-batch collectives (all over ``model``):
  1. ``pmax`` of the AI-path per-leaf score union  (experts live apart)
  2. ``psum`` of per-query refine counts           (leaves live apart)

The R path and AI path both touch only the local leaf shard, so the paper's
"skip extraneous leaf accesses" becomes "skip extraneous HBM traffic on
every shard" — the AI-tree's benefit scales with the mesh.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.device_tree import DeviceTree, Level
from repro.core.hybrid import HybridTree
from repro.core import traversal
from repro.core.grid import cells_of_queries
from repro.core.classifiers.knn import KNNBank
from repro.core.classifiers.mlp import MLPBank
from repro.core.classifiers.forest import Forest


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    max_visited: int = 64        # per-shard compact bound (R path)
    max_pred: int = 16           # per-shard compact bound (AI path)
    max_cells: int = 4
    threshold: float = 0.5
    use_kernel: bool = False
    # AI-path score-union collective:
    #  "pmax"  — paper-faithful dense union: pmax over the full [B, L]
    #            per-leaf score table (simple, collective-heavy);
    #  "topk"  — beyond-paper: each expert shard reduces its local scores to
    #            (leaf id, score) top-k per query, the union runs over the
    #            all-gathered [B, shards·k] candidate lists. Exact whenever
    #            a query's true leaf set per shard ≤ k (guaranteed here by
    #            k = max_pred, since >max_pred predictions fall back anyway).
    # Default "topk": O(B·shards·k) payload vs pmax's O(B·L_glob) table —
    # 2-3.4× faster at every swept shard count and scaling away from pmax
    # past 4 shards (benchmarks/union_scaling.py, union_* rows).
    score_union: str = "topk"
    # Freshness guard: demote queries overlapping a not-ok cell
    # (``AITree.cell_ok`` — under-fit at build time or stale since inserts
    # landed there) to the exact R path before prediction. Default ON: a
    # sub-1.0-fit bank on the ungated AI path silently drops results (the
    # under-prediction blind spot); exact-fit, fresh banks are unaffected
    # (their cell_ok is all-True and the guard never fires).
    guard: bool = True
    # Delta-probe compact slot bound (the insert buffer's per-query hit
    # table). The engine only consumes the exact per-query hit *count*, so
    # this bounds kernel-side slot work, never correctness.
    delta_k: int = 64


def pad_tree_for_sharding(h: HybridTree, n_shards: int) -> HybridTree:
    """Pad leaf-level arrays (and expert cells) to multiples of ``n_shards``.

    Padding leaves get never-intersecting MBRs and +inf entries; padding
    cells get -1 label maps. Semantics are unchanged.
    """
    t = h.tree
    L = t.n_leaves
    Lp = int(np.ceil(L / n_shards) * n_shards)
    if Lp != L:
        pad = Lp - L
        never = jnp.asarray([np.inf, np.inf, -np.inf, -np.inf], jnp.float32)
        leaf = t.levels[-1]
        # padding leaves repeat the last real parent (not 0): their
        # never-rect MBRs keep them dead either way, but the repeat keeps
        # the rebuilt ancestor windows tight (a 0 parent in the last leaf
        # tile would stretch that tile's window back to the level start)
        new_leaf = Level(
            mbrs=jnp.concatenate(
                [leaf.mbrs, jnp.tile(never[None], (pad, 1))]),
            parent=jnp.concatenate(
                [leaf.parent,
                 jnp.broadcast_to(leaf.parent[-1], (pad,))]))
        t = dataclasses.replace(
            t,
            levels=t.levels[:-1] + (new_leaf,),
            leaf_entries=jnp.concatenate(
                [t.leaf_entries,
                 jnp.full((pad,) + t.leaf_entries.shape[1:], jnp.inf,
                          t.leaf_entries.dtype)]),
            leaf_entry_ids=jnp.concatenate(
                [t.leaf_entry_ids,
                 jnp.full((pad,) + t.leaf_entry_ids.shape[1:], -1,
                          jnp.int32)]),
            leaf_counts=jnp.concatenate(
                [t.leaf_counts, jnp.zeros((pad,), jnp.int32)]),
        )
    # Re-anchor the ancestor-window table to the padded leaf axis. Inside
    # shard_map each shard keeps its contiguous run of leaf tiles (starts
    # columns shard with them — ``tree_shardings_p``) while internal
    # levels stay replicated, so the table stays valid *iff* the tile
    # grid divides evenly across shards; otherwise drop it and let
    # dispatch fall back.
    if t.aslices is not None:
        tl_s = t.aslices.tl
        if Lp % tl_s == 0 and (Lp // tl_s) % n_shards == 0:
            from repro.core.device_tree import build_ancestor_table
            t = dataclasses.replace(t, aslices=build_ancestor_table(
                [np.asarray(lv.parent) for lv in t.levels], tl=tl_s))
        else:
            t = dataclasses.replace(t, aslices=None)
    from repro.core.aitree import bank_n_cells
    bank = h.ait.bank
    C = bank_n_cells(bank)
    Cp = int(np.ceil(C / n_shards) * n_shards)
    cell_ok = h.ait.cell_ok
    if Cp != C:
        padc = Cp - C

        def _pad0(a, fill=0):
            return jnp.concatenate(
                [a, jnp.full((padc,) + a.shape[1:], fill, a.dtype)])

        # padding cells are never routed to (cell ids < C), but guard them
        # anyway — False is the safe fill for an eligibility mask
        cell_ok = _pad0(cell_ok, False)
        if isinstance(bank, KNNBank):
            bank = dataclasses.replace(
                bank, feats=_pad0(bank.feats, np.inf),
                labels=_pad0(bank.labels), label_map=_pad0(bank.label_map, -1),
                lmask=_pad0(bank.lmask, False))
        elif isinstance(bank, MLPBank):
            bank = dataclasses.replace(
                bank, w1=_pad0(bank.w1), b1=_pad0(bank.b1), w2=_pad0(bank.w2),
                b2=_pad0(bank.b2), label_map=_pad0(bank.label_map, -1),
                lmask=_pad0(bank.lmask, False))
        else:
            bank = dataclasses.replace(
                bank, feat_idx=_pad0(bank.feat_idx),
                thresh=_pad0(bank.thresh, np.inf), tables=_pad0(bank.tables),
                label_map=_pad0(bank.label_map, -1),
                lmask=_pad0(bank.lmask, False))
    ait = dataclasses.replace(h.ait, bank=bank, cell_ok=cell_ok)
    return dataclasses.replace(h, tree=t, ait=ait)


def tree_shardings(h: HybridTree, mesh, model_axis: str = "model"):
    """NamedSharding pytree matching ``HybridTree`` (for jit in_shardings)."""
    spec = tree_shardings_p(h, model_axis)
    return jax.tree.map(lambda s: jax.sharding.NamedSharding(mesh, s), spec,
                        is_leaf=lambda x: isinstance(x, P))


class ServeStats(NamedTuple):
    n_results: jnp.ndarray      # [B]
    leaf_accesses: jnp.ndarray  # [B]
    routed_high: jnp.ndarray    # [B]
    used_ai: jnp.ndarray        # [B]
    r_truncated: jnp.ndarray    # [B] R-path refine bound overflow — the
    #                             caller re-serves these on the wide-bound
    #                             tier (two-tier serving; keeps max_visited
    #                             small for the common case)
    guarded: jnp.ndarray        # [B] routed-high but demoted to the R path
    #                             by the cell guard (fit < 1 / stale cell)
    delta_hits: jnp.ndarray     # [B] qualifying points found in the insert
    #                             delta buffer (already folded into
    #                             n_results; zeros when no delta store)
    mispredict: jnp.ndarray     # [B] AI-path attempt hit the misprediction
    #                             signal (predicted leaf, zero qualifiers) —
    #                             per-cell drift evidence for the policy
    cell_id: jnp.ndarray        # [B] i32 anchor grid cell (-1 on window
    #                             overflow) — the monitor's aggregation key


class RPathOut(NamedTuple):
    """Per-query R-path stage output (collectives already reduced)."""
    r_counts: jnp.ndarray    # [B] qualifying points via the classical path
    n_visited: jnp.ndarray   # [B] classical visit count (global)
    n_true: jnp.ndarray      # [B] true-leaf count (global)
    r_truncated: jnp.ndarray  # [B] max_visited overflow on any shard


class AIPathOut(NamedTuple):
    """Per-query AI-path stage output (collectives already reduced)."""
    ai_counts: jnp.ndarray   # [B] qualifying points via predicted leaves
    n_pred: jnp.ndarray      # [B] predicted leaf accesses (global)
    fallback: jnp.ndarray    # [B] prediction unusable → R answer
    guarded: jnp.ndarray     # [B] query overlaps a not-ok cell → demoted
    #                          to the R path before prediction
    mispredict: jnp.ndarray  # [B] the misprediction component of fallback
    #                          (a predicted leaf held no qualifying entry)
    cell_id: jnp.ndarray     # [B] i32 anchor cell (-1 on window overflow)


class SlotRefineOut(NamedTuple):
    """Shared refine-stage output over one [B, K] slot table (psum'd)."""
    n_results: jnp.ndarray   # [B] qualifying points across valid slots
    n_hit: jnp.ndarray       # [B] valid slots with ≥ 1 qualifying point
    n_valid: jnp.ndarray     # [B] valid slots


def _refine_slots(h: HybridTree, queries: jnp.ndarray, leaf_idx: jnp.ndarray,
                  valid: jnp.ndarray, cfg: EngineConfig,
                  model_axis: str) -> SlotRefineOut:
    """Shared refine stage: a compact ``[B, K]`` slot table of local leaf
    ids in, globally-reduced per-query counts out.

    Both paths feed this — the slot table is the single inter-path
    contract: the R path's ``visited_leaves_compact`` slots and the AI
    path's predicted slots (fused kernel or oracle, either union mode)
    land here identically. The three reductions cover every downstream
    need: ``n_results`` (answers), ``n_hit`` (the R path's true-leaf
    count), and ``n_valid`` − ``n_hit`` > 0 (the paper's misprediction
    signal — some predicted leaf held no qualifying entry).
    """
    ref = traversal.refine_leaves(h.tree, queries, leaf_idx, valid,
                                  use_kernel=cfg.use_kernel)
    vi = valid.astype(jnp.int32)
    n_results = jax.lax.psum(jnp.sum(ref.counts * vi, -1), model_axis)
    n_hit = jax.lax.psum(
        jnp.sum(((ref.counts > 0) & valid).astype(jnp.int32), -1),
        model_axis)
    n_valid = jax.lax.psum(jnp.sum(vi, -1), model_axis)
    return SlotRefineOut(n_results=n_results, n_hit=n_hit, n_valid=n_valid)


def _r_path(h: HybridTree, queries: jnp.ndarray, cfg: EngineConfig,
            model_axis: str) -> RPathOut:
    """Classical stage over the local leaf shard.

    Fused traverse+compact (with use_kernel, the [B, L_loc] visited
    mask stays in VMEM; only the [B, max_visited] slots + counts
    reach HBM — the jnp path materializes the mask but compacts with
    the identical scheme). Internal levels are replicated, so the
    traversal applies unchanged per shard: the local leaf level's
    parent indices point into the replicated last internal level, and
    the sharding pad's never-intersecting leaf MBRs stay dead
    regardless of their parent slot. Single-level (root == leaf)
    shards are handled downstream — the former engine-local loop
    self-gathered the root mask there.
    """
    cv = traversal.visited_leaves_compact(
        h.tree, queries, cfg.max_visited, use_kernel=cfg.use_kernel)
    r_trunc = jax.lax.psum(cv.overflow.astype(jnp.int32), model_axis) > 0
    ro = _refine_slots(h, queries, cv.leaf_idx, cv.valid, cfg, model_axis)
    n_visited = jax.lax.psum(cv.n_visited, model_axis)    # [B]
    return RPathOut(r_counts=ro.n_results, n_visited=n_visited,
                    n_true=ro.n_hit, r_truncated=r_trunc)


def _ai_slots_topk(h: HybridTree, queries: jnp.ndarray, cfg: EngineConfig,
                   kind: str, loc_ids: jnp.ndarray, local: jnp.ndarray,
                   model_axis: str, n_model: int, L_loc: int, L_glob: int):
    """Per-shard compact prediction slots + shard union (``topk`` mode).

    Beyond-paper: each expert shard compacts its local cells' predictions
    to the first ``max_pred`` **distinct** global leaf ids (leaf-ID
    order) — with an MLP bank under ``use_kernel`` that is the fused
    prediction kernel writing the slot table straight from VMEM; the
    oracle rung runs ``compact_candidates`` over the small [B, S·Cl]
    candidate list. The union reads the all-gathered ``[B, shards·k]``
    slot lists directly (the previous implementation re-top-k'd dense
    per-leaf scores): single-shard meshes need no union at all — the
    shard's slots *are* the answer, so no per-leaf tensor of any size
    exists; multi-shard meshes scatter the gathered ids into the
    ``[B, L_loc]`` local-range mask, which *shrinks* with the mesh (a
    pairwise ``compact_candidates`` dedup here would grow O((shards·k)²)
    transients on the hot path instead). Exact whenever no shard
    overflows its k distinct predictions (guaranteed complete lists);
    overflow falls back — a fallback is never wrong, only slower.

    Returns ``(p_idx, p_valid, n_pred, overflow)`` with ``p_idx`` local
    leaf ids for the shared refine stage and ``n_pred`` the
    globally-deduped predicted-leaf count (sibling cells on *different*
    shards can predict the same leaf, but each distinct leaf lands in
    exactly one shard's range — the psum of local mask counts dedups).
    """
    B = queries.shape[0]
    k = cfg.max_pred
    midx = jax.lax.axis_index(model_axis)
    if kind == "mlp" and cfg.use_kernel:
        from repro.kernels import ops as kops
        g_idx, g_valid, g_cnt = kops.mlp_predict_compact(
            queries, h.ait.bank, loc_ids, local, n_leaves=L_glob, k=k,
            threshold=cfg.threshold)
    else:
        from repro.core.aitree import cell_slot_probs
        probs = cell_slot_probs(h.ait, queries, loc_ids)
        lm = h.ait.bank.label_map[loc_ids]                # [B, S, Cl]
        lok = local[:, :, None] & h.ait.bank.lmask[loc_ids] \
            & (probs > cfg.threshold)
        g_idx, g_valid, g_cnt = traversal.compact_candidates(
            lm.reshape(B, -1), lok.reshape(B, -1), k)
    if n_model == 1:
        return g_idx, g_valid, g_cnt, g_cnt > k
    trunc = jax.lax.psum((g_cnt > k).astype(jnp.int32), model_axis) > 0
    ag_i = jax.lax.all_gather(g_idx, model_axis, axis=1, tiled=True)
    ag_v = jax.lax.all_gather(g_valid, model_axis, axis=1, tiled=True)
    keep = ag_v & (ag_i >= midx * L_loc) & (ag_i < (midx + 1) * L_loc)
    li = jnp.clip(ag_i - midx * L_loc, 0, L_loc - 1)
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    pred_loc = jnp.zeros((B, L_loc), jnp.int32).at[rows, li].max(
        keep.astype(jnp.int32)) > 0
    n_pred = jax.lax.psum(
        jnp.sum(pred_loc.astype(jnp.int32), -1), model_axis)
    p_idx, p_valid, _ = traversal.compact_mask_counted(pred_loc, k)
    return p_idx, p_valid, n_pred, (n_pred > k) | trunc


def _ai_path(h: HybridTree, queries: jnp.ndarray, cfg: EngineConfig,
             kind: str, model_axis: str, n_model: int) -> AIPathOut:
    """Learned stage: per-cell experts → score union → shared refine.

    Both ``score_union`` modes end in the same compact ``[B, max_pred]``
    slot table handed to ``_refine_slots``: ``topk`` builds it without
    ever materializing per-leaf scores (``_ai_slots_topk``); ``pmax``
    keeps the paper-faithful dense ``[B, L_glob]`` union and compacts the
    local slice. ``n_model`` is the static model-axis size.
    """
    B = queries.shape[0]
    L_loc = h.tree.levels[-1].mbrs.shape[0]
    midx = jax.lax.axis_index(model_axis)
    # global cell ids per query; translate to local expert slots
    cell_ids, cvalid, cell_over = cells_of_queries(
        h.ait.grid, queries, cfg.max_cells)
    from repro.core.aitree import bank_n_cells
    C_loc = bank_n_cells(h.ait.bank)
    c0 = midx * C_loc
    local = (cell_ids >= c0) & (cell_ids < c0 + C_loc) & cvalid
    loc_ids = jnp.clip(cell_ids - c0, 0, C_loc - 1)
    if cfg.guard:
        # freshness/fit guard over the local expert shard: any overlapped
        # cell with cell_ok False demotes the query (each valid cell is
        # local to exactly one shard, so the psum unions the verdicts)
        bad = jnp.any(local & ~h.ait.cell_ok[loc_ids], axis=-1)
        guarded = jax.lax.psum(bad.astype(jnp.int32), model_axis) > 0
    else:
        guarded = jnp.zeros((B,), bool)
    L_glob = L_loc * n_model
    if cfg.score_union == "pmax":
        # paper-faithful dense union: one pmax over the full score table
        from repro.core.aitree import cell_slot_probs
        from repro.core.classifiers.mlp import global_scores
        probs = cell_slot_probs(h.ait, queries, loc_ids)
        scores = global_scores(h.ait.bank, probs, local, loc_ids, L_glob)
        scores = jax.lax.pmax(scores, model_axis)         # [B, L_glob]
        pred = scores > cfg.threshold
        pred_loc = jax.lax.dynamic_slice_in_dim(
            pred, midx * L_loc, L_loc, 1)
        n_pred = jnp.sum(pred.astype(jnp.int32), -1)      # replicated
        p_idx, p_valid, p_cnt = traversal.compact_mask_counted(
            pred_loc, cfg.max_pred)
        over = (p_cnt > cfg.max_pred) | (n_pred > cfg.max_pred)
        over = jax.lax.psum(over.astype(jnp.int32), model_axis) > 0
    else:
        p_idx, p_valid, n_pred, over = _ai_slots_topk(
            h, queries, cfg, kind, loc_ids, local, model_axis, n_model,
            L_loc, L_glob)
    ro = _refine_slots(h, queries, p_idx, p_valid, cfg, model_axis)
    empty = n_pred == 0
    mis = ro.n_valid > ro.n_hit   # some predicted leaf had no qualifier
    fallback = empty | mis | cell_over | over
    # anchor-cell attribution: global ids on replicated queries, identical
    # on every shard (no collective needed)
    cell_id = jnp.where(cvalid[:, 0], cell_ids[:, 0], -1).astype(jnp.int32)
    return AIPathOut(ai_counts=ro.n_results, n_pred=n_pred,
                     fallback=fallback, guarded=guarded, mispredict=mis,
                     cell_id=cell_id)


def _delta_path(queries: jnp.ndarray, delta_xy: jnp.ndarray,
                cfg: EngineConfig) -> jnp.ndarray:
    """Freshness stage: probe the (replicated) insert delta buffer.

    Returns the per-query exact hit count [B] i32 — staged points are
    invisible to both tree paths, so the count is *added* to whichever
    path answered. With ``use_kernel`` the probe is the Pallas kernel
    (``ops.delta_probe``): the ``[B, cap]`` containment mask stays in
    VMEM and only the compact slot table + counts reach HBM; the jnp
    oracle rung is bit-identical. The buffer is replicated (it is small
    and write-staged on the host), so no collective is needed.
    """
    if cfg.use_kernel:
        from repro.kernels import ops as kops
        _, _, cnt = kops.delta_probe(queries, delta_xy, k=cfg.delta_k)
    else:
        from repro.kernels import ref as kref
        _, _, cnt = kref.delta_probe(queries, delta_xy, cfg.delta_k)
    return cnt


def _route_combine(h: HybridTree, queries: jnp.ndarray, rp: RPathOut,
                   ap: AIPathOut,
                   d_hits: Optional[jnp.ndarray] = None) -> ServeStats:
    """Router dispatch + paper cost accounting over the stage outputs.

    Guard-demoted rows (``ap.guarded``) take the R answer and pay only
    the classical cost — the guard fires before prediction. Delta hits
    (``d_hits``, the freshness stage) add to the chosen path's count:
    staged inserts are invisible to both tree paths by construction.
    """
    from repro.core.classifiers.router import route_high
    high = route_high(h.router, queries)
    demoted = high & ap.guarded
    eligible = high & ~demoted
    used_ai = eligible & ~ap.fallback
    if d_hits is None:
        d_hits = jnp.zeros_like(rp.r_counts)
    n_results = jnp.where(used_ai, ap.ai_counts, rp.r_counts) + d_hits
    leaf_accesses = jnp.where(
        eligible, ap.n_pred + jnp.where(ap.fallback, rp.n_visited, 0),
        rp.n_visited)
    # overflow only matters when the R path supplied the answer: used_ai
    # rows report exact AI-path stats (n_visited stays exact regardless —
    # the compaction count is not truncated), so flagging them would send
    # already-exact rows through the wide tier for bit-identical results
    return ServeStats(n_results=n_results, leaf_accesses=leaf_accesses,
                      routed_high=high, used_ai=used_ai,
                      r_truncated=rp.r_truncated & ~used_ai,
                      guarded=demoted, delta_hits=d_hits,
                      # only rows that attempted the AI path can mispredict
                      mispredict=eligible & ap.mispredict,
                      cell_id=ap.cell_id)


def make_serve_step(mesh, cfg: EngineConfig, *, kind: str,
                    batch_axes=("pod", "data"), model_axis: str = "model"):
    """Build the shard_map'd hybrid serve step for ``mesh``.

    Returned fn: ``(hybrid, queries [B,4], delta_xy=None) → ServeStats``
    with B split over ``batch_axes`` and tree/experts split over
    ``model_axis``. ``delta_xy`` ([cap, 2] f32, +inf on unstaged slots —
    ``core.delta.DeltaStore.xy``) is the replicated insert buffer; when
    passed, the ``_delta_path`` stage probes it and its hits fold into
    ``n_results``. The body is a composition of the stage functions above
    — ``_r_path`` / ``_ai_path`` / ``_delta_path`` / ``_route_combine`` —
    so alternative drivers (the spatial batch scheduler, the two-tier
    wide re-serve, future partial pipelines) can restage them without
    re-deriving the collective layout.
    """
    batch_axes = tuple(a for a in batch_axes if a in mesh.axis_names)
    n_model = mesh.shape[model_axis]

    def body(h: HybridTree, queries):
        rp = _r_path(h, queries, cfg, model_axis)
        ap = _ai_path(h, queries, cfg, kind, model_axis, n_model)
        return _route_combine(h, queries, rp, ap)

    def body_delta(h: HybridTree, queries, delta_xy):
        rp = _r_path(h, queries, cfg, model_axis)
        ap = _ai_path(h, queries, cfg, kind, model_axis, n_model)
        d = _delta_path(queries, delta_xy, cfg)
        return _route_combine(h, queries, rp, ap, d)

    baxes = batch_axes if len(batch_axes) > 1 else batch_axes[0]
    qspec = P(baxes, None)
    ospec = ServeStats(n_results=P(baxes), leaf_accesses=P(baxes),
                       routed_high=P(baxes), used_ai=P(baxes),
                       r_truncated=P(baxes), guarded=P(baxes),
                       delta_hits=P(baxes), mispredict=P(baxes),
                       cell_id=P(baxes))

    def serve_step(h: HybridTree, queries: jnp.ndarray,
                   delta_xy: Optional[jnp.ndarray] = None) -> ServeStats:
        if delta_xy is None:
            shard = jax.shard_map(
                body, mesh=mesh,
                in_specs=(tree_shardings_p(h, model_axis), qspec),
                out_specs=ospec, check_vma=False)
            return shard(h, queries)
        shard = jax.shard_map(
            body_delta, mesh=mesh,
            in_specs=(tree_shardings_p(h, model_axis), qspec, P(None, None)),
            out_specs=ospec, check_vma=False)
        return shard(h, queries, delta_xy)

    return serve_step


def wide_config(cfg: EngineConfig, factor: int = 8) -> EngineConfig:
    """The wide-bound tier's config: ``max_visited`` scaled by ``factor``."""
    return dataclasses.replace(cfg, max_visited=cfg.max_visited * factor)


def point_config(cfg: EngineConfig, max_visited: int = 32) -> EngineConfig:
    """The point-query fast path's config: single-cell AI routing (a
    degenerate rect overlaps exactly one grid cell, so the cell window
    collapses with no overflow) and a traversal narrowed to point-sized
    bounds. No wide tier pairs with this — the driver asserts
    ``r_truncated`` stays empty instead of re-serving."""
    return dataclasses.replace(cfg, max_cells=1,
                               max_visited=min(cfg.max_visited, max_visited))


def make_point_serve_step(mesh, cfg: EngineConfig, *, kind: str,
                          max_visited: int = 32,
                          batch_axes=("pod", "data"),
                          model_axis: str = "model"):
    """``make_serve_step`` specialized for degenerate-rect point queries
    (see ``point_config``). Same ``(hybrid, queries, delta_xy=None) →
    ServeStats`` closure shape as the range step, so the scheduler and
    the open-loop runtime drive it unchanged."""
    return make_serve_step(mesh, point_config(cfg, max_visited), kind=kind,
                           batch_axes=batch_axes, model_axis=model_axis)


def make_two_tier_steps(mesh, cfg: EngineConfig, *, kind: str,
                        wide_factor: int = 8, batch_axes=("pod", "data"),
                        model_axis: str = "model"):
    """Narrow + wide serve steps realizing the ``r_truncated`` contract.

    The narrow step keeps ``max_visited`` small for the common case;
    queries that overflow it (``ServeStats.r_truncated`` — their
    ``n_results`` undercounts) are collected by the scheduler
    (``core.schedule.serve_workload``) and re-served through the wide
    step, whose bound is ``wide_factor``× larger. Returns
    ``(narrow_step, wide_step)``; both are ``(hybrid, queries) →
    ServeStats`` closures over the same mesh layout.
    """
    narrow = make_serve_step(mesh, cfg, kind=kind, batch_axes=batch_axes,
                             model_axis=model_axis)
    wide = make_serve_step(mesh, wide_config(cfg, wide_factor), kind=kind,
                           batch_axes=batch_axes, model_axis=model_axis)
    return narrow, wide


def tree_shardings_p(h: HybridTree, model_axis: str = "model"):
    """PartitionSpec pytree (not NamedSharding) for shard_map in_specs."""
    rep = P()
    t = h.tree
    lvl_specs = []
    for i, lv in enumerate(t.levels):
        if i == len(t.levels) - 1:
            lvl_specs.append(Level(mbrs=P(model_axis, None),
                                   parent=P(model_axis)))
        else:
            lvl_specs.append(Level(mbrs=rep, parent=rep))
    tree_spec = DeviceTree(
        levels=tuple(lvl_specs),
        leaf_entries=P(model_axis, None, None),
        leaf_entry_ids=P(model_axis, None),
        leaf_counts=P(model_axis),
        n_points=t.n_points, max_entries=t.max_entries,
        # window starts shard along the tile axis with the leaf chunks
        # they describe (internal levels stay replicated, so each shard's
        # columns still hold valid global window indices)
        aslices=None if t.aslices is None else dataclasses.replace(
            t.aslices, starts=P(None, model_axis)))
    bank = h.ait.bank
    if isinstance(bank, KNNBank):
        bank_spec = dataclasses.replace(
            bank, feats=P(model_axis, None, None),
            labels=P(model_axis, None, None), label_map=P(model_axis, None),
            lmask=P(model_axis, None))
    elif isinstance(bank, MLPBank):
        bank_spec = dataclasses.replace(
            bank, w1=P(model_axis, None, None), b1=P(model_axis, None),
            w2=P(model_axis, None, None), b2=P(model_axis, None),
            mu=rep, sd=rep, label_map=P(model_axis, None),
            lmask=P(model_axis, None))
    else:
        bank_spec = dataclasses.replace(
            bank, feat_idx=P(model_axis, None, None),
            thresh=P(model_axis, None, None),
            tables=P(model_axis, None, None, None),
            label_map=P(model_axis, None), lmask=P(model_axis, None))
    ait_spec = dataclasses.replace(
        h.ait, bank=bank_spec, cell_ok=P(model_axis),
        grid=dataclasses.replace(h.ait.grid, bbox=rep))
    router_spec = dataclasses.replace(
        h.router, feat_idx=rep, thresh=rep, tables=rep)
    return HybridTree(tree=tree_spec, ait=ait_spec, router=router_spec)
