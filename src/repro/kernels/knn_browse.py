"""Pallas TPU kernel: distance browsing over compact visited-leaf slots.

The kNN path reuses the range path's I/O discipline: the fused traversal
(probed with the query's ``center ± radius`` box) names at most ``K``
candidate leaves per query in a compact ``[B, K]`` slot table, and this
kernel browses exactly those leaves — only the named ``[1, M]`` entry
tiles move HBM→VMEM (scalar-prefetch BlockSpec index maps), extraneous
leaves generate no memory traffic. Per fetched entry it emits the
squared Euclidean distance to the query center, masked to +inf outside
the probed radius (or on invalid slots / +inf-padded entries), so the
caller's top-k over the ``[B, K·M]`` flat view yields the k nearest
among all points within the radius. The dense ``[B, L]`` visited mask
never exists on this path — the slot table is the only interchange.

Two grid forms, one semantics (the ``leaf_refine`` split):

* ``fold_k=False`` (the TPU form): a ``(B, K)`` grid, one cell per
  (query, leaf slot), each DMA-ing one named leaf's ``[2, M]`` entry
  tile — ``leaf_refine``'s ``slot_grid_call``, with the centers in SMEM.
* ``fold_k=True`` (the interpret form): the grid folds away — an XLA
  gather stages the ``[B, K, M]`` slab and the kernel body runs once.
  Bit-identical outputs; the right trade when the "DMA" is an emulated
  memcpy anyway.

Inputs (planar entry layout):
  ``centers``  [B, 3] f32   — query center x, center y, radius²
  ``entries``  [L, 2, M] f32 — x row over y row per leaf, +inf padded
  ``leaf_idx`` [B, K] i32   — leaves to browse (scalar-prefetched)
  ``valid``    [B, K] i32   — slot validity
Output:
  ``d2``       [B, K, M] f32 — squared distance, +inf where masked

+inf-padded entries are safe by arithmetic, not by branch: their
``dx``/``dy`` are +inf (finite center), so ``d2`` is +inf and the
radius test fails — the same convention the delta-probe buffer uses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.leaf_refine import slot_grid_call
from repro.kernels.traverse_fused import tuned_tiles_for_key


def tune_key_knn(B: int, K: int, M: int, interp: bool) -> str:
    """Autotune-cache key for the kNN-browse form space (same cache file
    as the traversal/mlp/delta forms; see ``benchmarks/autotune``)."""
    return f"knn-{'interp' if interp else 'tpu'}:B{B}:K{K}:M{M}"


def tuned_tiles_knn(B: int, K: int, M: int, interp: bool) -> dict:
    return tuned_tiles_for_key(tune_key_knn(B, K, M, interp))


def vmem_estimate_knn(B: int, K: int, M: int, tpu_form: bool = True) -> int:
    """Rough VMEM working-set bytes for one browse dispatch.

    The TPU form's cell working set is one double-buffered entry tile +
    the query's double-buffered ``[K, M]`` output row; the folded form
    stages the whole gathered ``[B, K, M]`` slab (gx, gy, out) plus the
    query/valid blocks.
    """
    if tpu_form:
        return 2 * (2 * M * 4) + 2 * K * M * 4
    return B * (3 + K) * 4 + 3 * B * K * M * 4


def _kernel(idx_ref, q_ref, e_ref, o_ref, *, K: int, Q: int):
    # q_ref: flat [B·3] (cx, cy, r²) in SMEM; e_ref: [2, M]; o_ref: [K, M]
    b = pl.program_id(0)
    k = pl.program_id(1)
    cx = q_ref[Q * b]
    cy = q_ref[Q * b + 1]
    r2 = q_ref[Q * b + 2]
    e = e_ref[:, :]
    c = jnp.where(jax.lax.broadcasted_iota(jnp.int32, e.shape, 0) == 0,
                  cx, cy)
    sq = (e - c) * (e - c)             # rows dx², dy²
    d2 = sq[0:1, :] + sq[1:2, :]
    ok = (d2 <= r2) & (idx_ref[b * K + k] >= 0)
    o_ref[pl.ds(k, 1), :] = jnp.where(ok, d2, jnp.inf)


def _kernel_folded(q_ref, valid_ref, gx_ref, gy_ref, o_ref):
    # whole-array blocks: q [B, 3]; valid [B, K]; gx/gy/o [B, K, M]
    q = q_ref[:, :]
    cx = q[:, 0][:, None, None]
    cy = q[:, 1][:, None, None]
    r2 = q[:, 2][:, None, None]
    dx = gx_ref[:, :, :] - cx
    dy = gy_ref[:, :, :] - cy
    d2 = dx * dx + dy * dy
    ok = (d2 <= r2) & (valid_ref[:, :][:, :, None] > 0)
    o_ref[:, :, :] = jnp.where(ok, d2, jnp.inf)


@functools.partial(jax.jit, static_argnames=("interpret", "fold_k"))
def knn_browse(centers: jnp.ndarray, entries: jnp.ndarray,
               leaf_idx: jnp.ndarray, valid: jnp.ndarray, *,
               interpret: bool = False,
               fold_k: bool | None = None) -> jnp.ndarray:
    """centers [B,3] (cx,cy,r²), entries [L,2,M], leaf_idx/valid [B,K]
    → d2 [B,K,M] f32 (+inf where masked).

    ``fold_k`` defaults to ``interpret``: the (B, K) scalar-prefetch grid
    on hardware, the folded form when emulating. Both forms are
    bit-identical (tested); pass ``fold_k`` explicitly to pin a form.
    """
    if fold_k is None:
        fold_k = interpret
    B, K = leaf_idx.shape
    M = entries.shape[2]
    if fold_k:
        g = entries[leaf_idx]                   # [B, K, 2, M] XLA gather
        gx, gy = g[:, :, 0], g[:, :, 1]
        return pl.pallas_call(
            _kernel_folded,
            out_shape=jax.ShapeDtypeStruct((B, K, M), jnp.float32),
            interpret=interpret,
        )(centers.astype(jnp.float32), valid.astype(jnp.int32),
          gx.astype(jnp.float32), gy.astype(jnp.float32))
    return slot_grid_call(_kernel, "knn_browse", centers, entries,
                          leaf_idx, valid, jnp.float32, interpret)
