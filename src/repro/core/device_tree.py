"""Flattened, device-resident form of the R-tree.

The host ``RTree`` (pointer style) is converted to a structure-of-arrays
suitable for batched TPU traversal:

* one ``Level`` per tree depth, nodes ordered so that every parent's children
  are **contiguous** and leaf order equals the paper's DFS leaf-ID order
  (§III-A1 — sibling leaves get consecutive IDs);
* each level stores node MBRs ``[N_l, 4]`` and a ``parent`` index into the
  level above, so frontier expansion is one gather + one rect-intersection;
* the leaf level additionally stores a padded planar entry tensor
  ``[L, 2, M_pad]`` (x row over y row; pad = +inf, so containment tests
  fail on padding) and the corresponding
  point ids ``[L, M_pad]`` (pad = -1).

All device arrays are float32/int32 — the f64 host build is only a builder.
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.rtree import RTree


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Level:
    mbrs: jnp.ndarray    # [N_l, 4] f32
    parent: jnp.ndarray  # [N_l] i32 index into previous level


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class AncestorTable:
    """Per-(internal level, leaf tile) ancestor windows for the sliced walk.

    The level-order flatten gives every parent's children contiguous ids,
    so each ``tl``-wide leaf tile's ancestor set at internal level ``l`` is
    a contiguous index range. ``starts[l, t]`` is the *block index* of the
    ``widths[l]``-wide aligned window containing that range (window element
    offset = ``starts[l, t] * widths[l]`` — Pallas block-spec index maps
    address whole blocks, so windows are block-aligned and ``widths[l]`` is
    the smallest lane-quantum power-of-two width that block-aligns every
    tile's range, capped at the lane-padded level width). The sliced fused
    traversal (``kernels.traverse_fused.traverse_fused_sliced_t``) feeds
    ``starts`` through scalar prefetch and stages only each tile's window
    of every internal level into VMEM — the walk fits the VMEM budget at
    any tree size.
    """
    starts: jnp.ndarray  # [n_int, n_tiles] i32 block-index window starts
    widths: Tuple[int, ...] = dataclasses.field(metadata=dict(static=True))
    tl: int = dataclasses.field(metadata=dict(static=True))

    @property
    def n_tiles(self) -> int:
        return int(self.starts.shape[1])


def build_ancestor_table(level_parents, *, tl: int | None = None
                         ) -> "AncestorTable | None":
    """Host-side ancestor-window table for the sliced fused traversal.

    ``level_parents``: one ``[N_l]`` int parent array per tree level, root
    first, leaf level last (``DeviceTree``'s layout — entry 0 of the root's
    array is unused). ``tl`` is the leaf-tile granularity (defaults to the
    kernel's ``DEF_TL``). Returns ``None`` for single-level trees (root ==
    leaves — no internal levels to slice).

    Ranges are computed bottom-up by min/max over each tile's slice (no
    monotonicity assumption on the parent arrays, though the level-order
    flatten produces non-decreasing ones); widths double from the lane
    quantum until every tile's range fits one aligned window, capped at the
    lane-padded level width (cap ⇒ the window degenerates to the whole
    level — full replication, still correct).
    """
    from repro.kernels.traverse_fused import DEF_TL, LANE
    tl = int(tl or DEF_TL)
    parents = [np.asarray(p) for p in level_parents]
    n_int = len(parents) - 1
    if n_int < 1:
        return None
    L = parents[-1].shape[0]
    n_tiles = -(-L // tl)
    los = np.empty((n_int, n_tiles), np.int64)
    his = np.empty((n_int, n_tiles), np.int64)
    lp = parents[-1]
    edges = np.arange(0, L, tl)
    los[n_int - 1] = np.minimum.reduceat(lp, edges)
    his[n_int - 1] = np.maximum.reduceat(lp, edges)
    for l in range(n_int - 1, 0, -1):
        p = parents[l]
        for t in range(n_tiles):
            seg = p[los[l, t]:his[l, t] + 1]
            los[l - 1, t] = seg.min()
            his[l - 1, t] = seg.max()
    widths = []
    starts = np.zeros((n_int, n_tiles), np.int32)
    for l in range(n_int):
        n_l = parents[l].shape[0]
        cap = -(-max(n_l, 1) // LANE) * LANE
        w = LANE
        while w < cap and not np.all(los[l] // w == his[l] // w):
            w *= 2
        if w >= cap:
            w = cap          # whole (lane-padded) level in one window
        else:
            starts[l] = (los[l] // w).astype(np.int32)
        widths.append(int(w))
    return AncestorTable(starts=jnp.asarray(starts), widths=tuple(widths),
                         tl=tl)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DeviceTree:
    levels: Tuple[Level, ...]        # levels[0] has exactly 1 node (the root)
    # [L, 2, M_pad] f32, +inf padded: planar x row over y row per leaf,
    # the [2, M] tile the refine kernels read with the entries on lanes
    leaf_entries: jnp.ndarray
    leaf_entry_ids: jnp.ndarray      # [L, M_pad] i32, -1 padded
    leaf_counts: jnp.ndarray         # [L] i32
    n_points: int = dataclasses.field(metadata=dict(static=True))
    max_entries: int = dataclasses.field(metadata=dict(static=True))
    # Ancestor-window table for the sliced fused traversal (None on trees
    # built before/without one — dispatch then falls back; ``flatten``
    # always attaches it, ``engine.pad_tree_for_sharding`` rebuilds or
    # drops it to match the padded/sharded leaf axis).
    aslices: "AncestorTable | None" = None

    @property
    def n_leaves(self) -> int:
        return int(self.levels[-1].mbrs.shape[0])

    @property
    def leaf_mbrs(self) -> jnp.ndarray:
        return self.levels[-1].mbrs

    @property
    def height(self) -> int:
        return len(self.levels)

    def byte_size(self) -> int:
        total = 0
        for lv in self.levels:
            total += lv.mbrs.size * 4 + lv.parent.size * 4
        total += self.leaf_entries.size * 4 + self.leaf_entry_ids.size * 4
        total += self.leaf_counts.size * 4
        return total


def flatten(tree: RTree, pad_to: int | None = None,
            slice_tl: int | None = None) -> DeviceTree:
    """Flatten a host ``RTree`` to a ``DeviceTree``.

    ``pad_to`` overrides the per-leaf entry padding (defaults to ``tree.M``,
    rounded up to a multiple of 8 for clean vector lanes). ``slice_tl``
    overrides the ancestor-window table's leaf-tile granularity (defaults
    to the fused kernel's ``DEF_TL``); the table itself is always attached
    (``None`` only for root==leaf trees).
    """
    assert tree.points is not None, "flatten() needs a built tree"
    M_pad = pad_to if pad_to is not None else tree.M
    M_pad = int(np.ceil(M_pad / 8) * 8)

    # ---- level-order walk with parent-ordered children (== DFS leaf order)
    level_nodes: List[List[int]] = [[tree.root]]
    while not all(tree.is_leaf[n] for n in level_nodes[-1]):
        nxt: List[int] = []
        for n in level_nodes[-1]:
            assert not tree.is_leaf[n], "unbalanced host tree"
            nxt.extend(tree.children[n])
        level_nodes.append(nxt)

    levels: List[Level] = []
    np_parents: List[np.ndarray] = []
    for depth, nodes in enumerate(level_nodes):
        mbrs = tree.mbrs[nodes].astype(np.float32)
        if depth == 0:
            parent = np.zeros((1,), dtype=np.int32)
        else:
            pos_above = {n: i for i, n in enumerate(level_nodes[depth - 1])}
            parent = np.array(
                [pos_above[tree.parent[n]] for n in nodes], dtype=np.int32)
        np_parents.append(parent)
        levels.append(Level(mbrs=jnp.asarray(mbrs), parent=jnp.asarray(parent)))

    # ---- leaf entries, padded
    leaves = level_nodes[-1]
    L = len(leaves)
    entries = np.full((L, 2, M_pad), np.inf, dtype=np.float32)
    entry_ids = np.full((L, M_pad), -1, dtype=np.int32)
    counts = np.zeros((L,), dtype=np.int32)
    for i, n in enumerate(leaves):
        ids = tree.children[n]
        k = len(ids)
        assert k <= M_pad, f"leaf fill {k} exceeds pad {M_pad}"
        if k:
            entries[i, :, :k] = tree.points[ids].astype(np.float32).T
            entry_ids[i, :k] = np.asarray(ids, dtype=np.int32)
        counts[i] = k

    return DeviceTree(
        levels=tuple(levels),
        leaf_entries=jnp.asarray(entries),
        leaf_entry_ids=jnp.asarray(entry_ids),
        leaf_counts=jnp.asarray(counts),
        n_points=int(tree.points.shape[0]),
        max_entries=tree.M,
        aslices=build_ancestor_table(np_parents, tl=slice_tl),
    )


def dfs_leaf_index(tree: RTree) -> dict:
    """host-node-id → DFS leaf id (the class label space of the paper)."""
    return {n: i for i, n in enumerate(tree.leaves_dfs())}
