"""Pallas TPU kernel: oblivious-decision-forest inference.

The AI-tree's paper-faithful classifier family is decision trees. Pointer
trees do not vectorize, so we use **oblivious** trees (one (feature,
threshold) pair per depth level): evaluating a tree is

    bit_d  = x[feat_d] > thresh_d                (VPU compares)
    leaf   = Σ_d bit_d · 2^(D-1-d)               (integer dot)
    scores = onehot(leaf) @ leaf_table           (MXU matmul)

The [TB, 2^D] one-hot × [2^D, C] table matmul is the hot op and maps straight
onto the MXU. ``forest_infer`` (the router's forest) runs every tree inside
one grid step per query tile, accumulating votes in the VMEM output tile;
``forest_infer_cells`` grids over (B-tiles, cells, trees).

Inputs:
  ``sel``    [B, T, D] f32 — pre-gathered feature values per tree/depth
  ``thresh`` [T, D]   f32
  ``tables`` [T, 2^D, C] f32 — per-leaf label votes
Output:
  ``scores`` [B, C] f32 — summed votes (caller normalizes by T)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEF_TB = 256


def _leaf_ids(sel, th):
    """[TB, D] features vs [1, D] thresholds → [TB, 1] i32 leaf ids
    (bit d weighs 2^(D-1-d))."""
    D = sel.shape[-1]
    powers = jnp.left_shift(
        1, D - 1 - jax.lax.broadcasted_iota(jnp.int32, (1, D), 1))
    return jnp.sum(jnp.where(sel > th, powers, 0), axis=-1, keepdims=True)


def _kernel(sel_ref, th_ref, tbl_ref, o_ref):
    # sel_ref [T, TB, D] (trees outermost, so each tree's [TB, D] slab is
    # a tile-aligned block); th_ref [T, D]; tbl_ref [T, 2^D, C]
    T, tb, D = sel_ref.shape
    n_leaves = tbl_ref.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (tb, n_leaves), 1)
    for t in range(T):
        leaf = _leaf_ids(sel_ref[t], th_ref[t:t + 1, :])     # [TB, 1]
        onehot = (iota == leaf).astype(jnp.float32)          # [TB, 2^D]
        votes = jnp.dot(onehot, tbl_ref[t],
                        preferred_element_type=jnp.float32)  # [TB, C]
        if t == 0:
            o_ref[:, :] = votes
        else:
            o_ref[:, :] += votes


def _kernel_cells(sel_ref, th_ref, tbl_ref, o_ref):
    """Per-cell accumulation variant: grid (B-tiles, C, T), output [TB, 1, Cl]
    per cell — tree votes accumulate within a cell, not across cells."""
    t = pl.program_id(2)
    sel = sel_ref[:, 0, :]
    leaf = _leaf_ids(sel, th_ref[0:1, :])
    n_leaves = tbl_ref.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (sel.shape[0], n_leaves), 1)
    onehot = (iota == leaf).astype(jnp.float32)
    votes = jnp.dot(onehot, tbl_ref[0, :, :],
                    preferred_element_type=jnp.float32)

    @pl.when(t == 0)
    def _init():
        o_ref[:, 0, :] = votes

    @pl.when(t > 0)
    def _acc():
        o_ref[:, 0, :] += votes


@functools.partial(jax.jit, static_argnames=("n_cells", "tb", "interpret"))
def forest_infer_cells(sel: jnp.ndarray, thresh: jnp.ndarray,
                       tables: jnp.ndarray, *, n_cells: int, tb: int = DEF_TB,
                       interpret: bool = False) -> jnp.ndarray:
    """Celled forests: sel [B, C·T, D], thresh [C·T, D], tables [C·T, 2^D, Cl]
    → votes [B, C, Cl] (summed over each cell's T trees)."""
    B, CT, D = sel.shape
    n_leaves, Cl = tables.shape[1], tables.shape[2]
    assert CT % n_cells == 0, (CT, n_cells)
    T = CT // n_cells
    assert B % tb == 0, (B, tb)
    grid = (B // tb, n_cells, T)
    return pl.pallas_call(
        _kernel_cells,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tb, 1, D), lambda b, c, t: (b, c * T + t, 0)),
            pl.BlockSpec((1, D), lambda b, c, t: (c * T + t, 0)),
            pl.BlockSpec((1, n_leaves, Cl), lambda b, c, t: (c * T + t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((tb, 1, Cl), lambda b, c, t: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, n_cells, Cl), jnp.float32),
        interpret=interpret,
    )(sel.astype(jnp.float32), thresh.astype(jnp.float32),
      tables.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("tb", "interpret"))
def forest_infer(sel: jnp.ndarray, thresh: jnp.ndarray, tables: jnp.ndarray,
                 *, tb: int = DEF_TB, interpret: bool = False) -> jnp.ndarray:
    """sel [B,T,D], thresh [T,D], tables [T,2^D,C] → scores [B,C].

    One grid step per query tile runs every tree (forests are small: the
    router's is 16 trees of depth 6), so the thresholds and vote tables
    are whole blocks and the features arrive tree-major.
    """
    B, T, D = sel.shape
    T2, n_leaves, C = tables.shape
    assert T2 == T and n_leaves == 2 ** D, (tables.shape, D)
    assert B % tb == 0, (B, tb)
    return pl.pallas_call(
        _kernel,
        grid=(B // tb,),
        in_specs=[
            pl.BlockSpec((T, tb, D), lambda b: (0, b, 0)),
            pl.BlockSpec((T, D), lambda b: (0, 0)),
            pl.BlockSpec((T, n_leaves, C), lambda b: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((tb, C), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((B, C), jnp.float32),
        interpret=interpret,
        name="forest_infer",
    )(jnp.transpose(sel.astype(jnp.float32), (1, 0, 2)),
      thresh.astype(jnp.float32), tables.astype(jnp.float32))
