"""Pallas TPU kernel: refinement of predicted/visited leaves.

This kernel embodies the paper's core I/O saving on TPU: only the leaf tiles
named in ``leaf_idx`` are pulled HBM→VMEM (via scalar-prefetch BlockSpec
index maps); extraneous leaves generate **no memory traffic at all**. The
per-entry containment test then runs on the VPU over the fetched tile.

Two grid forms, one semantics:

* ``fold_k=False`` (the TPU form): a ``(B, K)`` grid, one cell per
  (query, leaf slot), each DMA-ing exactly one named leaf's ``[2, M]``
  entry tile. That per-slot DMA *is* the paper's saving on hardware — but
  interpret mode emulates every grid cell in sequence, so B·K cells cost
  seconds on CPU for what is microseconds of VPU work. The slot table
  (valid folded in as -1) rides in SMEM through scalar prefetch and the
  query rects sit in SMEM whole, so no per-query operand needs a
  tile-aligned VMEM block; the output block is the query's ``[K, M]``
  row, revisited across its K cells. SMEM holds 1 MiB on a v5e, so the
  batch is cut into calls of at most ``PREFETCH_SLOTS`` slots.
* ``fold_k=True`` (the interpret form): the grid folds away entirely — one
  kernel invocation over the whole ``[B, K, M]`` slab, gathered at the XLA
  level outside the kernel. Same outputs bit for bit; the gather trades
  the targeted DMA for an O(B·K·M) HBM gather, which is exactly the right
  trade when the "DMA" is an emulated memcpy anyway.

Inputs (planar entry layout — see mbr_intersect.py for rationale):
  ``leaf_idx`` [B, K] i32   — leaves to refine per query (scalar-prefetched)
  ``queries``  [B, 4] f32
  ``entries``  [L, 2, M] f32 — x row over y row per leaf, +inf padded
                              (``DeviceTree.leaf_entries``)
  ``valid``    [B, K] i32   — slot validity
Output:
  ``inside``   [B, K, M] bool — exact containment per fetched entry
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Slots per call of the (B, K) grid form: its flattened slot table rides
# in SMEM (1 MiB on a v5e) through scalar prefetch.
PREFETCH_SLOTS = 1 << 16


def slot_grid_call(kernel, name: str, per_query: jnp.ndarray,
                   entries: jnp.ndarray, leaf_idx: jnp.ndarray,
                   valid: jnp.ndarray, out_dtype, interpret: bool
                   ) -> jnp.ndarray:
    """The ``(B, K)`` scalar-prefetch grid shared by ``leaf_refine`` and
    ``knn_browse``: ``kernel(idx_ref, q_ref, e_ref, o_ref, *, K, Q)`` runs
    once per (query, slot), with ``idx_ref`` the flat slot table (-1 on
    invalid slots), ``q_ref`` the flat ``[B·Q]`` per-query scalars in
    SMEM, ``e_ref`` the slot's ``[2, M]`` tile of the planar ``[L, 2,
    M]`` entries and ``o_ref`` the query's ``[K, M]`` output row. Returns
    ``[B, K, M]``."""
    B, K = leaf_idx.shape
    M = entries.shape[2]
    Q = per_query.shape[1]
    idx = jnp.where(valid, leaf_idx, -1).astype(jnp.int32)
    bc = max(1, min(B, PREFETCH_SLOTS // K))
    outs = []
    for lo in range(0, B, bc):
        n = min(bc, B - lo)
        spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n, K),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((None, 2, M), lambda b, k, idx: (
                    jnp.maximum(idx[b * K + k], 0), 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, K, M), lambda b, k, idx: (b, 0, 0)),
        )
        outs.append(pl.pallas_call(
            functools.partial(kernel, K=K, Q=Q),
            grid_spec=spec,
            out_shape=jax.ShapeDtypeStruct((n, K, M), out_dtype),
            interpret=interpret,
            name=name,
        )(idx[lo:lo + n].reshape(-1),
          per_query[lo:lo + n].astype(jnp.float32).reshape(-1),
          entries.astype(jnp.float32)))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)


def _kernel(idx_ref, q_ref, e_ref, o_ref, *, K: int, Q: int):
    # q_ref: flat [B·4] rects (SMEM); e_ref: [2, M]; o_ref: [K, M]
    b = pl.program_id(0)
    k = pl.program_id(1)
    x0 = q_ref[Q * b]
    y0 = q_ref[Q * b + 1]
    x1 = q_ref[Q * b + 2]
    y1 = q_ref[Q * b + 3]
    e = e_ref[:, :]
    ex = e[0:1, :]
    ey = e[1:2, :]
    ok = (ex >= x0) & (ex <= x1) & (ey >= y0) & (ey <= y1)
    o_ref[pl.ds(k, 1), :] = ok & (idx_ref[b * K + k] >= 0)


def _kernel_folded(q_ref, valid_ref, gx_ref, gy_ref, o_ref):
    # whole-array blocks: q [B, 4]; valid [B, K]; gx/gy/o [B, K, M]
    q = q_ref[:, :]
    gx = gx_ref[:, :, :]
    gy = gy_ref[:, :, :]
    v = valid_ref[:, :]
    x0 = q[:, 0][:, None, None]
    y0 = q[:, 1][:, None, None]
    x1 = q[:, 2][:, None, None]
    y1 = q[:, 3][:, None, None]
    ok = (gx >= x0) & (gx <= x1) & (gy >= y0) & (gy <= y1)
    o_ref[:, :, :] = ok & (v[:, :, None] > 0)


@functools.partial(jax.jit, static_argnames=("interpret", "fold_k"))
def leaf_refine(queries: jnp.ndarray, entries: jnp.ndarray,
                leaf_idx: jnp.ndarray, valid: jnp.ndarray, *,
                interpret: bool = False,
                fold_k: bool | None = None) -> jnp.ndarray:
    """queries [B,4], entries [L,2,M], leaf_idx [B,K], valid [B,K]
    → [B,K,M].

    ``fold_k`` defaults to ``interpret``: the (B, K) scalar-prefetch grid on
    hardware, the folded (B,) grid when emulating. Both forms are
    bit-identical (tested); pass ``fold_k`` explicitly to pin a form.
    """
    if fold_k is None:
        fold_k = interpret
    B, K = leaf_idx.shape
    M = entries.shape[2]
    if fold_k:
        g = entries[leaf_idx]                   # [B, K, 2, M] XLA gather
        gx, gy = g[:, :, 0], g[:, :, 1]
        # Whole-array blocks, no grid: the emulated grid loop is pure
        # overhead off-TPU, so the folded form runs the kernel body once.
        return pl.pallas_call(
            _kernel_folded,
            out_shape=jax.ShapeDtypeStruct((B, K, M), jnp.bool_),
            interpret=interpret,
        )(queries.astype(jnp.float32), valid.astype(jnp.int32),
          gx.astype(jnp.float32), gy.astype(jnp.float32))
    return slot_grid_call(_kernel, "leaf_refine", queries, entries,
                          leaf_idx, valid, jnp.bool_, interpret)
