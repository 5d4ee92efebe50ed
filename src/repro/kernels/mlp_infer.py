"""Pallas TPU kernel: fused AI-path prediction → compact slot table.

The AI path of the "AI+R"-tree turns a range query into multi-label
classification: run the ≤ ``max_cells`` cell experts a query overlaps,
union their per-leaf scores, threshold, and access only the predicted
leaves. Before this kernel the learned side materialized the dense
``[B, L]`` score table in HBM (``predict_scores`` → ``global_scores`` →
threshold → ``compact_mask_counted``) — the paper's *fast* path was the
memory-heavy half of the engine. This kernel fuses the prediction
pipeline into two ``pallas_call``s that emit the same ``[B, K]`` slot
table + per-row count contract as ``traverse_compact_t``; the ``[B, L]``
scores never exist outside VMEM tiles.

TPU form, two stages:

* **Cell-routed MLP-bank inference** (grid: query tile × block of
  ``CELL_BLOCK`` cells). The bank streams through VMEM block by block, so
  its size is bounded by HBM, not VMEM. For each cell some query of the
  tile routes to, the first layer runs as ``F`` broadcast multiply-adds
  and the second as an MXU matmul over the whole tile (rows the cell does
  not serve are masked afterwards); sigmoid + threshold + the cell's
  ``label_map`` give the routed rows' candidate leaf targets, written to
  the segment of a per-query ``[S·Cl]`` candidate list that the query's
  cell slot owns. That list is the only inter-stage HBM traffic —
  ``[B, S·Cl]`` vs the dense ``[B, L]``; one XLA sort between the
  stages moves each row's candidates to its front.

* **Union + compaction** (grid: query tile × leaf tile). A leaf tile
  outside the query tile's [min, max] candidate-target range is skipped
  outright — predictions are spatially tight, so most tiles are dead.
  A live tile ORs each candidate into a ``[TL, TB]`` mask (one dynamic
  sublane row of the transposed list per candidate rank, looped up to the
  tile's longest list; union across a query's cells and dedup of
  sibling-cell duplicates come free from the OR), transposes it once and
  runs the compaction epilogue shared with ``traverse_compact_t``: first
  ``k`` predicted leaf ids in leaf-ID order plus the per-row distinct
  count, from which the caller derives ``valid``, the *empty* and
  *overflow* fallback signals, bit-identical to ``compact_mask_counted``
  of the dense path.

The interpret form is one ``pallas_call`` of value-level gathers with the
dense oracle's contraction order, scatter and searchsorted epilogue.

Threshold convention: requires ``threshold ≥ 0`` (the dense oracle's
zero-initialized score scatter predicts *every* leaf under a negative
threshold; the candidate union cannot). ``ops.py`` asserts this.

Layout: queries/cell ids arrive row-major (``[B, F]``, ``[B, S]``).
``ops.py`` pads B to the query tile, the leaf axis to the leaf tile, C to
``CELL_BLOCK`` and (TPU form) Cl to the lane quantum; padding cells and
label slots carry ``label_map = -1``, and clipped ids never select a
padding cell.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.epilogue import (
    compact_epilogue_interp as _compact_epilogue_interp,
    compact_epilogue_tpu as _compact_epilogue_tpu,
    vmem_bytes as epilogue_vmem,
)
from repro.kernels.traverse_fused import (COMPACT_KC, LANE,
                                          tuned_tiles_for_key)

DEF_TB = 256    # query-tile (sublane axis)
DEF_TL = 512    # leaf-tile (lane axis, multiple of 128)
CELL_BLOCK = 8  # cells per grid step of the inference stage (sublane quantum)
# the bank's matmuls run at full f32 precision on every path (as
# ``core.classifiers.mlp.F32``)
F32 = jax.lax.Precision.HIGHEST


def tune_key_mlp(B: int, L: int, C: int, Cl: int, interp: bool) -> str:
    """Autotune-cache key for the fused prediction kernel's form space
    (same cache file as the traversal forms; see ``benchmarks/autotune``)."""
    return f"mlp-{'interp' if interp else 'tpu'}:B{B}:L{L}:C{C}:Cl{Cl}"


def tuned_tiles_mlp(B: int, L: int, C: int, Cl: int, interp: bool) -> dict:
    return tuned_tiles_for_key(tune_key_mlp(B, L, C, Cl, interp))


def vmem_estimate_mlp(C: int, F: int, H: int, Cl: int, S: int, tb: int,
                      tl: int, kp: int, tpu_form: bool = True) -> int:
    """Rough VMEM working-set bytes for the fused prediction kernel.

    TPU form: the larger of its two stages. The inference stage streams
    the bank ``CELL_BLOCK`` cells per grid step (double-buffered blocks)
    and writes the ``[tb, S·Cl]`` candidate list; the union stage
    holds the transposed candidate list, the ``[tl, tb]`` union
    accumulator, the leaf-tile mask and the compaction transients. The
    interpret form runs the whole bank as values per query tile, as the
    dense oracle does.
    """
    kcp = S * Cl
    if tpu_form:
        cb = CELL_BLOCK
        bank = 2 * cb * (F * H + H + H * Cl + 2 * Cl) * 4
        infer = bank + 2 * tb * kcp * 4 + tb * (F + 2 * S + H) * 4
        infer += 2 * tb * Cl * 4                        # logits, targets
        union = 2 * kcp * tb * 4 + 2 * tl * tb * 4      # list, accumulator
        union += tb * tl * 4 + epilogue_vmem(tb, tl)    # mask, epilogue
        union += tb * (kp + 1) * 4
        return max(infer, union)
    est = C * (F * H + H + H * Cl + 2 * Cl) * 4
    est += tb * S * (F * H + H + H * Cl + 2 * Cl) * 4   # gathered params
    est += tb * tl * 4 + epilogue_vmem(tb, tl, tpu_form=False)
    est += tb * (kp + 1) * 4
    return est


def _make_infer_kernel(F: int, Cl: int, S: int, cb: int, tb: int,
                       kcp: int, thr: float):
    """Inference stage (TPU form): one grid step per block of ``cb``
    cells. For each cell some query of the tile routes to, the cell's
    two layers run on the tile (the first as ``F`` broadcast
    multiply-adds, the second on the MXU), and the cell's label targets
    (1-based, 0 = below threshold or no label) are written to segment
    ``[s·Cl, (s+1)·Cl)`` of the candidate list of every row routing the
    cell through its slot ``s`` — static, lane-aligned slices, no
    compaction in the kernel."""

    def kernel(x_ref, cid_ref, ok_ref, w1_ref, b1_ref, w2_ref, b2_ref,
               lm_ref, list_ref):
        blk = pl.program_id(1)

        @pl.when(blk == 0)
        def _init():
            list_ref[:, :] = jnp.zeros((tb, kcp), jnp.int32)

        x = x_ref[:, :]                                   # [TB, F]
        cid = jnp.where(ok_ref[:, :] > 0, cid_ref[:, :], -1)   # [TB, S]
        for u in range(cb):
            hit = cid == blk * cb + u                     # [TB, S]

            @pl.when(jnp.max(jnp.where(hit, 1, 0)) > 0)
            def _cell(u=u, hit=hit):
                w1 = w1_ref[u]                            # [F, H]
                h = b1_ref[u:u + 1, :] + x[:, 0:1] * w1[0:1, :]
                for f in range(1, F):
                    h = h + x[:, f:f + 1] * w1[f:f + 1, :]
                h = jnp.maximum(h, 0.0)                   # [TB, H]
                logits = jax.lax.dot(
                    h, w2_ref[u], precision=F32,
                    preferred_element_type=jnp.float32) + b2_ref[u:u + 1, :]
                lm = lm_ref[u:u + 1, :]                   # [1, Cl] (-1 pad)
                tgt = jnp.where((lm >= 0) & (jax.nn.sigmoid(logits) > thr),
                                lm + 1, 0)                # [TB, Cl]
                for s in range(S):
                    seg = slice(s * Cl, (s + 1) * Cl)
                    list_ref[:, seg] = jnp.where(hit[:, s:s + 1], tgt,
                                                 list_ref[:, seg])

    return kernel


def _make_union_kernel(tb: int, tl: int, kp: int, kc: int):
    """Union stage (TPU form): per leaf tile, OR every candidate target
    of each query into the tile's prediction mask, then compact it. The
    candidate list arrives transposed (``[S·Cl, TB]``), so candidate
    ``t`` of every query is one dynamic sublane row compared against the
    tile's leaf ids laid along sublanes; the ``[TL, TB]`` union is
    transposed once on the XLU. Tiles outside the tile's candidate-target
    range skip everything."""

    def kernel(list_ref, n_ref, idx_ref, cnt_ref):
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            idx_ref[:, :] = jnp.zeros((tb, kp), jnp.int32)
            cnt_ref[:, :] = jnp.zeros((tb, 1), jnp.int32)

        cl = list_ref[:, :]                               # [S·Cl, TB]
        lo = jnp.min(jnp.where(cl > 0, cl, jnp.int32(2 ** 30))) - 1
        hi = jnp.max(cl) - 1
        t0 = j * tl

        @pl.when((lo < t0 + tl) & (hi >= t0))
        def _live_tile():
            tgt = t0 + 1 + jax.lax.broadcasted_iota(jnp.int32, (tl, tb), 0)

            def add(t, acc):
                return jnp.where(list_ref[pl.ds(t, 1), :] == tgt, 1.0, acc)

            acc = jax.lax.fori_loop(0, jnp.max(n_ref[:, :]), add,
                                    jnp.zeros((tl, tb), jnp.float32))
            mask = jnp.transpose(acc) > 0.0               # [TB, TL]
            col = t0 + jax.lax.broadcasted_iota(jnp.int32, (tb, tl), 1)
            _compact_epilogue_tpu(mask, col, idx_ref, cnt_ref, kp, kc)

    return kernel


def _make_interp_kernel(S: int, Cl: int, tb: int, tl: int, kp: int,
                        thr: float):
    """Interpret form: value-level parameter gathers + the same einsum
    contraction order as the dense oracle (``cell_logits_for``),
    value-level scatter into the leaf tile, and the searchsorted epilogue
    — interpret mode functionalizes ref-touching conds, so everything is
    unconditional value work (the interpret default folds the leaf axis
    into one tile)."""
    SCl = S * Cl

    def kernel(x_ref, cid_ref, ok_ref, w1_ref, b1_ref, w2_ref, b2_ref,
               lm_ref, idx_ref, cnt_ref):
        j = pl.program_id(1)
        x = x_ref[:, :]                                  # [TB, F]
        cid = cid_ref[:, :]                              # [TB, S]
        okr = ok_ref[:, :] > 0
        w1 = w1_ref[:, :, :][cid]                        # [TB, S, F, H]
        b1 = b1_ref[:, :][cid]
        w2 = w2_ref[:, :, :][cid]
        b2 = b2_ref[:, :][cid]
        h = jnp.maximum(
            jnp.einsum("bf,bsfh->bsh", x, w1, precision=F32) + b1, 0.0)
        logits = jnp.einsum("bsh,bshl->bsl", h, w2, precision=F32) + b2
        prob = jax.nn.sigmoid(logits)                    # [TB, S, Cl]
        lm = lm_ref[:, :][cid]                           # [TB, S, Cl]
        cand = okr[:, :, None] & (lm >= 0) & (prob > thr)
        trel = lm - j * tl
        intile = cand & (trel >= 0) & (trel < tl)
        ti = jnp.where(intile, trel, tl).reshape(tb, SCl)
        rows = jnp.arange(tb, dtype=jnp.int32)[:, None]
        mask = jnp.zeros((tb, tl + 1), jnp.int32).at[rows, ti].max(
            intile.reshape(tb, SCl).astype(jnp.int32))[:, :tl] > 0
        _compact_epilogue_interp(mask, j, tl, kp, idx_ref, cnt_ref)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("k", "lp", "thr", "tb", "tl", "kc",
                                    "interpret", "tpu_form"))
def mlp_predict_compact_t(x: jnp.ndarray, cell_ids: jnp.ndarray,
                          slot_ok: jnp.ndarray, w1: jnp.ndarray,
                          b1: jnp.ndarray, w2: jnp.ndarray,
                          b2: jnp.ndarray, lm: jnp.ndarray, *, k: int,
                          lp: int, thr: float, tb: int = DEF_TB,
                          tl: int = DEF_TL, kc: int = COMPACT_KC,
                          interpret: bool = False,
                          tpu_form: bool | None = None
                          ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused prediction entry point.

    ``x`` [B, F] normalized features; ``cell_ids``/``slot_ok`` [B, S]
    (ids clipped into [0, C)); ``w1`` [C, F, H], ``b1`` [C, H], ``w2``
    [C, H, Cl], ``b2`` [C, Cl]; ``lm`` [C, Cl] i32 global leaf ids of
    each cell's label slots, -1 on slots that predict nothing. ``lp`` is
    the lane-padded leaf count (the scatter axis); B must be a multiple
    of ``tb``, ``lp`` of ``tl``, C of ``CELL_BLOCK`` and Cl of LANE in
    the TPU form (ops.py pads). Returns ``(leaf_idx [B, KP] i32, count
    [B, 1] i32)`` with the ``traverse_compact_t`` slot contract: KP =
    ``k`` lane-rounded in the TPU form, exactly ``k`` in the interpret
    form; row ``b``'s first ``min(count[b], KP)`` slots hold its
    predicted leaf ids in leaf-ID order, slots past the count are 0.

    The TPU form is two ``pallas_call``s: the inference stage writes each
    query's ``[S·Cl]`` candidate-target list (the only inter-stage HBM
    traffic, vs the ``[B, L]`` score table), the union stage sweeps the
    leaf tiles. ``tpu_form`` defaults to ``not interpret``; pass
    ``tpu_form=True`` with ``interpret=True`` to validate the exact
    hardware graph off-TPU.
    """
    if tpu_form is None:
        tpu_form = not interpret
    B, F = x.shape
    S = cell_ids.shape[1]
    C, _, H = w1.shape
    Cl = b2.shape[1]
    assert B % tb == 0 and lp % tl == 0, (B, lp, tb, tl)
    kp = (k + LANE - 1) // LANE * LANE if tpu_form else k
    assert kp % kc == 0 or not tpu_form, (kp, kc)
    n_i, n_j = B // tb, lp // tl
    args = (x.astype(jnp.float32), cell_ids.astype(jnp.int32),
            slot_ok.astype(jnp.int32), w1.astype(jnp.float32),
            b1.astype(jnp.float32), w2.astype(jnp.float32),
            b2.astype(jnp.float32), lm.astype(jnp.int32))
    out_specs = [pl.BlockSpec((tb, kp), lambda i, j: (i, 0)),
                 pl.BlockSpec((tb, 1), lambda i, j: (i, 0))]
    out_shape = [jax.ShapeDtypeStruct((B, kp), jnp.int32),
                 jax.ShapeDtypeStruct((B, 1), jnp.int32)]
    if not tpu_form:
        rep = lambda shape: pl.BlockSpec(  # noqa: E731
            shape, lambda i, j: (0,) * len(shape))
        return pl.pallas_call(
            _make_interp_kernel(S, Cl, tb, tl, kp, thr),
            grid=(n_i, n_j),
            in_specs=[pl.BlockSpec((tb, F), lambda i, j: (i, 0)),
                      pl.BlockSpec((tb, S), lambda i, j: (i, 0)),
                      pl.BlockSpec((tb, S), lambda i, j: (i, 0)),
                      rep((C, F, H)), rep((C, H)), rep((C, H, Cl)),
                      rep((C, Cl)), rep((C, Cl))],
            out_specs=out_specs, out_shape=out_shape,
            interpret=interpret,
            name="mlp_infer",
        )(*args)

    cb = CELL_BLOCK
    assert C % cb == 0 and Cl % LANE == 0, (C, Cl)
    kcp = S * Cl
    cands = pl.pallas_call(
        _make_infer_kernel(F, Cl, S, cb, tb, kcp, thr),
        grid=(n_i, C // cb),
        in_specs=[pl.BlockSpec((tb, F), lambda i, c: (i, 0)),
                  pl.BlockSpec((tb, S), lambda i, c: (i, 0)),
                  pl.BlockSpec((tb, S), lambda i, c: (i, 0)),
                  pl.BlockSpec((cb, F, H), lambda i, c: (c, 0, 0)),
                  pl.BlockSpec((cb, H), lambda i, c: (c, 0)),
                  pl.BlockSpec((cb, H, Cl), lambda i, c: (c, 0, 0)),
                  pl.BlockSpec((cb, Cl), lambda i, c: (c, 0)),
                  pl.BlockSpec((cb, Cl), lambda i, c: (c, 0))],
        out_specs=pl.BlockSpec((tb, kcp), lambda i, c: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, kcp), jnp.int32),
        interpret=interpret,
        name="mlp_infer",
    )(*args)
    # each row's candidates to its front (targets are >= 1, empties 0):
    # the union stage loops over the first max-count rows of the list
    cands = jnp.sort(cands, axis=1, descending=True)
    n_c = jnp.sum(cands > 0, axis=1, keepdims=True, dtype=jnp.int32)
    return pl.pallas_call(
        _make_union_kernel(tb, tl, kp, kc),
        grid=(n_i, n_j),
        in_specs=[pl.BlockSpec((kcp, tb), lambda i, j: (0, i)),
                  pl.BlockSpec((tb, 1), lambda i, j: (i, 0))],
        out_specs=out_specs, out_shape=out_shape,
        interpret=interpret,
        name="mlp_union",
    )(cands.T, n_c)
