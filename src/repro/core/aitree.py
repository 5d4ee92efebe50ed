"""The AI-tree (paper §III): predict true leaves, access only those, refine.

Query path (Fig. 5/6):
  1. grid-route the query to its overlapped cells (≤ ``max_cells``);
  2. run those cells' models, union their per-leaf scores (max-combine);
  3. threshold → predicted leaf set (≤ ``max_pred``);
  4. fetch ONLY predicted leaves and refine entries exactly (never a false
     positive, §III-C);
  5. raise the fallback flag when the prediction is unusable — empty set,
     a predicted leaf with zero qualifying entries (the paper's
     misprediction signal), grid/prediction overflow — the caller then runs
     the classical R-path for those queries, keeping results exact.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Union

import jax
import jax.numpy as jnp

from repro.core.device_tree import DeviceTree
from repro.core.grid import Grid, cells_of_queries
from repro.core.classifiers.mlp import (MLPBank, cell_logits_for,
                                        global_scores)
from repro.core.classifiers.forest import Forest, cell_probs_for
from repro.core.classifiers.knn import KNNBank, cell_probs_for as knn_probs
from repro.core import traversal


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class AITree:
    grid: Grid
    bank: Union[MLPBank, Forest, KNNBank]
    # Per-cell serve-eligibility guard: cell ``c``'s model may answer on
    # the AI path iff ``cell_ok[c]``. ``build.fit_airtree`` sets it from
    # the per-cell exact-fit flags (a cell whose training queries were
    # not all answered exactly can under-predict *silently* — the
    # blind-spot ROADMAP documented); the freshness monitor further
    # clears cells that received inserts since the bank was fit. Queries
    # overlapping any not-ok cell are demoted to the exact R path by the
    # hybrid/engine routing (see ``hybrid_query`` / ``engine._ai_path``).
    cell_ok: jnp.ndarray
    # ``kind`` names the bank family and selects the inference path:
    # "mlp" (MLPBank, the TPU-native stacked experts — the only kind with a
    # fused prediction kernel), "forest" (Forest, paper-faithful oblivious
    # trees) or "knn" (KNNBank, memorization-complete nearest-stored-query).
    kind: str = dataclasses.field(metadata=dict(static=True))
    max_cells: int = dataclasses.field(metadata=dict(static=True))
    max_pred: int = dataclasses.field(metadata=dict(static=True))
    threshold: float = dataclasses.field(metadata=dict(static=True))


def bank_n_cells(bank) -> int:
    """Cell count of any bank family (the guard/label leading axis)."""
    if isinstance(bank, KNNBank):
        return bank.feats.shape[0]
    if isinstance(bank, MLPBank):
        return bank.w1.shape[0]
    return bank.feat_idx.shape[0]


def update_bank_cells(bank, cells, **rows):
    """Functional per-cell-slot splice: return a new bank whose rows at
    ``cells`` ([Csub] i32 global cell ids) are replaced by the given
    ``[Csub, ...]`` arrays, all other cells' buffers untouched.

    The write side of the cell-granular refit pipeline
    (``build.refit_cells``): a sub-stack trained on just the changed
    cells lands in the live bank with one scatter per buffer — no full
    retrain, no bank reallocation. Field names must be per-cell buffers
    of the bank family (leading axis C); globals like ``mu``/``sd`` are
    rejected since splicing them would silently retarget *every* cell.
    """
    cells = jnp.asarray(cells, jnp.int32)
    per_cell = {
        MLPBank: ("w1", "b1", "w2", "b2", "label_map", "lmask"),
        KNNBank: ("feats", "labels", "label_map", "lmask"),
    }.get(type(bank))
    if per_cell is None:
        raise NotImplementedError(
            f"update_bank_cells: {type(bank).__name__} has no per-cell "
            "splice (forest banks refit whole)")
    updates = {}
    for name, val in rows.items():
        if name not in per_cell:
            raise ValueError(f"{name!r} is not a per-cell buffer of "
                             f"{type(bank).__name__} (allowed: {per_cell})")
        cur = getattr(bank, name)
        val = jnp.asarray(val, cur.dtype)
        if val.shape != (cells.shape[0],) + cur.shape[1:]:
            raise ValueError(f"{name}: row shape {val.shape} does not match "
                             f"({cells.shape[0]},) + {cur.shape[1:]}")
        updates[name] = cur.at[cells].set(val)
    return dataclasses.replace(bank, **updates)


def make_aitree(grid: Grid, bank, *, max_cells: int = 4, max_pred: int = 64,
                threshold: float = 0.5, cell_ok=None) -> AITree:
    kind = {MLPBank: "mlp", Forest: "forest", KNNBank: "knn"}[type(bank)]
    if cell_ok is None:
        # all-eligible default keeps hand-built trees' dispatch unchanged;
        # fit_airtree installs the real per-cell fit flags
        cell_ok = jnp.ones((bank_n_cells(bank),), jnp.bool_)
    return AITree(grid=grid, bank=bank, cell_ok=jnp.asarray(cell_ok),
                  kind=kind, max_cells=max_cells, max_pred=max_pred,
                  threshold=threshold)


def cell_slot_probs(ait: AITree, queries: jnp.ndarray,
                    cell_ids: jnp.ndarray) -> jnp.ndarray:
    """Per-(query, cell-slot) classifier scores: [B, S] ids → [B, S, Cl]."""
    if ait.kind == "mlp":
        return jax.nn.sigmoid(cell_logits_for(ait.bank, queries, cell_ids))
    if ait.kind == "knn":
        return knn_probs(ait.bank, queries, cell_ids)
    return cell_probs_for(ait.bank, queries, cell_ids)


def predict_scores(ait: AITree, queries: jnp.ndarray, n_leaves: int
                   ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """[B, 4] → (leaf scores [B, L], cell_overflow [B]).

    The dense prediction path — kept as the fused kernel's oracle and for
    consumers that need the full score table (labels, α, training,
    ``pred_mask``). The serving path uses ``predict_compact``.
    """
    cell_ids, valid, overflow = cells_of_queries(
        ait.grid, queries, ait.max_cells)
    probs = cell_slot_probs(ait, queries, cell_ids)
    scores = global_scores(ait.bank, probs, valid, cell_ids, n_leaves)
    return scores, overflow


def predict_compact(ait: AITree, queries: jnp.ndarray, n_leaves: int, *,
                    use_kernel: bool = False,
                    tile_b=None, tile_l=None
                    ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                               jnp.ndarray]:
    """Prediction straight to the compact slot table: [B, 4] →
    ``(leaf_idx [B, max_pred] i32, valid [B, max_pred] bool, n_pred [B]
    i32, cell_overflow [B] bool)``.

    Semantically ``compact_mask_counted(predict_scores > threshold,
    max_pred)`` plus the cell-routing overflow flag. With ``use_kernel``
    and an MLP bank the whole pipeline runs inside the fused Pallas
    kernel (``kernels.mlp_infer``) and the dense ``[B, L]`` score table
    is never materialized — absent from the lowered HLO. kNN/forest banks
    and the no-kernel path run the dense oracle and compact it with the
    identical scheme (bit-identical, next rung of the fallback ladder).
    ``tile_b``/``tile_l`` override the kernel's tile choice
    (testing/tuning only).
    """
    if ait.kind == "mlp" and use_kernel:
        cell_ids, valid, overflow = cells_of_queries(
            ait.grid, queries, ait.max_cells)
        from repro.kernels import ops as kops
        idx, v, cnt = kops.mlp_predict_compact(
            queries, ait.bank, cell_ids, valid, n_leaves=n_leaves,
            k=ait.max_pred, threshold=ait.threshold, tb=tile_b, tl=tile_l)
        return idx, v, cnt, overflow
    scores, overflow = predict_scores(ait, queries, n_leaves)
    idx, v, cnt = traversal.compact_mask_counted(
        scores > ait.threshold, ait.max_pred)
    return idx, v, cnt, overflow


def _refine_and_flag(ait: AITree, tree: DeviceTree, queries: jnp.ndarray,
                     leaf_idx: jnp.ndarray, valid: jnp.ndarray,
                     n_pred: jnp.ndarray, cell_over: jnp.ndarray,
                     max_results: int, use_kernel: bool):
    """Shared tail of the AI query pipelines: refine the predicted slot
    table, gather result ids, and assemble the paper's fallback signals
    (empty prediction, mispredicted zero-count leaf, cell/prediction
    overflow, result truncation). One implementation so ``ai_query`` and
    ``ai_query_compact`` cannot drift apart on the fallback convention.
    Returns ``(counts, n_pred_clamped, n_results, result_ids, fallback,
    mispredict)`` — the misprediction signal rides along separately so the
    maintenance policy can tell "model predicted a dead leaf" (drift
    evidence against the query's cell) apart from the structural fallbacks.
    """
    pred_over = n_pred > ait.max_pred
    with jax.named_scope("refine"):
        ref = traversal.refine_leaves(tree, queries, leaf_idx, valid,
                                      use_kernel=use_kernel)
    empty = n_pred == 0
    # paper's misprediction signal: a predicted leaf with no qualifying entry
    mispredict = jnp.any((ref.counts == 0) & valid, axis=-1)
    with jax.named_scope("gather_ids"):
        result_ids, trunc = traversal.gather_result_ids(tree, ref,
                                                        max_results)
    fallback = empty | mispredict | cell_over | pred_over | trunc
    n_results = jnp.sum(ref.counts * valid.astype(jnp.int32), axis=-1)
    return (ref.counts, jnp.minimum(n_pred, ait.max_pred), n_results,
            result_ids, fallback, mispredict)


def primary_cell_ids(ait: AITree, queries: jnp.ndarray) -> jnp.ndarray:
    """[B] i32 — each query's anchor grid cell (its lower-left corner's
    cell), or -1 for cell-window overflow. The per-query attribution key
    the serving stats carry so the freshness monitor can aggregate guard/
    mispredict/delta-hit evidence *per cell* and target maintenance
    (refit/demote/promote) at cell granularity.
    """
    cell_ids, valid, _ = cells_of_queries(ait.grid, queries, ait.max_cells)
    return jnp.where(valid[:, 0], cell_ids[:, 0], -1).astype(jnp.int32)


class AIQueryResult(NamedTuple):
    pred_mask: jnp.ndarray     # [B, L] predicted leaves
    counts: jnp.ndarray        # [B, K] qualifying entries per accessed leaf
    n_pred: jnp.ndarray        # [B] leaves accessed by the AI path
    n_results: jnp.ndarray     # [B] qualifying points found
    result_ids: jnp.ndarray    # [B, max_results] i32, -1 pad
    fallback: jnp.ndarray      # [B] bool — run the exact R-path instead
    mispredict: jnp.ndarray    # [B] bool — fallback specifically because a
    #                            predicted leaf held no qualifying entry
    cell_id: jnp.ndarray       # [B] i32 anchor cell (-1 on window overflow)


@functools.partial(jax.jit, static_argnames=("max_results", "use_kernel"))
def ai_query(ait: AITree, tree: DeviceTree, queries: jnp.ndarray, *,
             max_results: int = 512, use_kernel: bool = False
             ) -> AIQueryResult:
    queries = queries.astype(jnp.float32)
    L = tree.n_leaves
    scores, cell_over = predict_scores(ait, queries, L)
    pred = scores > ait.threshold                           # [B, L]
    # counted compaction: one scan yields slots, validity, and the row
    # count that feeds n_pred / the empty and overflow fallback signals
    leaf_idx, valid, n_pred = traversal.compact_mask_counted(
        pred, ait.max_pred)
    counts, n_pred_c, n_results, result_ids, fallback, mis = \
        _refine_and_flag(ait, tree, queries, leaf_idx, valid, n_pred,
                         cell_over, max_results, use_kernel)
    return AIQueryResult(
        pred_mask=pred,
        counts=counts,
        n_pred=n_pred_c,
        n_results=n_results,
        result_ids=result_ids,
        fallback=fallback,
        mispredict=mis,
        cell_id=primary_cell_ids(ait, queries),
    )


class AICompactResult(NamedTuple):
    leaf_idx: jnp.ndarray      # [B, max_pred] predicted leaves (ID order)
    valid: jnp.ndarray         # [B, max_pred] slot validity
    counts: jnp.ndarray        # [B, max_pred] qualifying entries per slot
    n_pred: jnp.ndarray        # [B] leaves accessed by the AI path
    n_results: jnp.ndarray     # [B] qualifying points found
    result_ids: jnp.ndarray    # [B, max_results] i32, -1 pad
    fallback: jnp.ndarray      # [B] bool — run the exact R-path instead
    mispredict: jnp.ndarray    # [B] bool — fallback specifically because a
    #                            predicted leaf held no qualifying entry
    cell_id: jnp.ndarray       # [B] i32 anchor cell (-1 on window overflow)


@functools.partial(jax.jit, static_argnames=("max_results", "use_kernel",
                                             "tile_b", "tile_l"))
def ai_query_compact(ait: AITree, tree: DeviceTree, queries: jnp.ndarray, *,
                     max_results: int = 512, use_kernel: bool = False,
                     tile_b=None, tile_l=None) -> AICompactResult:
    """Serving-path AI query: fused predict+compact → refine.

    The ``ai_query`` variant for the hot path, mirroring what
    ``range_query_compact`` is to ``range_query``: prediction lands
    directly in the ``[B, max_pred]`` slot table that feeds the
    scalar-prefetch refine kernel, so with ``use_kernel`` (MLP banks) the
    dense ``[B, L]`` score table never round-trips through HBM and is
    absent from the lowered HLO. Per-field bit-identical to ``ai_query``
    on every shared field — including the fallback convention: *empty*
    prediction, the paper's misprediction signal (a predicted leaf with
    zero qualifying entries), cell/prediction overflow, and result
    truncation. Use ``ai_query`` when ``pred_mask`` itself is needed
    (exact-fit evaluation, labels).
    """
    queries = queries.astype(jnp.float32)
    with jax.named_scope("predict"):
        leaf_idx, valid, n_pred, cell_over = predict_compact(
            ait, queries, tree.n_leaves, use_kernel=use_kernel,
            tile_b=tile_b, tile_l=tile_l)
    counts, n_pred_c, n_results, result_ids, fallback, mis = \
        _refine_and_flag(ait, tree, queries, leaf_idx, valid, n_pred,
                         cell_over, max_results, use_kernel)
    return AICompactResult(
        leaf_idx=leaf_idx,
        valid=valid,
        counts=counts,
        n_pred=n_pred_c,
        n_results=n_results,
        result_ids=result_ids,
        fallback=fallback,
        mispredict=mis,
        cell_id=primary_cell_ids(ait, queries),
    )
