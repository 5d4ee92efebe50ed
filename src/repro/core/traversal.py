"""Batched, level-synchronous R-tree range queries on device.

This is the TPU-native replacement for root-to-leaf pointer chasing: the
frontier at each level is a ``[B, N_l]`` boolean mask; expansion to the next
level is one gather (child → parent) plus one batched rectangle-intersection.

With ``use_kernel=True`` the whole root→leaf walk runs as one fused Pallas
kernel (``repro.kernels.traverse_fused``) — the frontier stays in VMEM across
levels instead of round-tripping [B, N_l] masks through HBM per level. The
pure-jnp per-level path doubles as its oracle. Mask→index compaction is
sort-free (prefix-count ranks + rowwise scatter), replacing the former
``top_k``-shaped implementations, which are kept as ``*_topk`` oracles.

Also implements the *refinement* step (exact point-in-rect filtering of the
visited/predicted leaves) and the overlap ratio α = TN/VN (§III-A2).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import geometry as geo
from repro.core.device_tree import DeviceTree


def _cross_intersect(queries: jnp.ndarray, mbrs: jnp.ndarray,
                     use_kernel: bool) -> jnp.ndarray:
    """[B,4] × [N,4] → [B,N] bool."""
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.mbr_intersect(queries, mbrs)
    return geo.jnp_cross_intersects(queries, mbrs)


def visited_leaf_mask(tree: DeviceTree, queries: jnp.ndarray,
                      use_kernel: bool = False) -> jnp.ndarray:
    """Leaves the classical R-tree would visit for each query: [B, L] bool.

    Exactly reproduces the recursive traversal's visited set: a leaf is
    visited iff every ancestor MBR (and its own) intersects the query.

    With ``use_kernel`` the whole walk runs as a single fused ``pallas_call``
    (``repro.kernels.traverse_fused``): the internal frontier never leaves
    VMEM and only the final [B, L] mask is materialized. Without it, the
    level-by-level jnp path below doubles as the oracle.
    """
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.traverse_fused(
            queries, [lv.mbrs for lv in tree.levels],
            [lv.parent for lv in tree.levels],
            slices=getattr(tree, "aslices", None))
    return visited_leaf_mask_per_level(tree, queries, use_kernel=False)


def visited_leaf_mask_per_level(tree: DeviceTree, queries: jnp.ndarray,
                                use_kernel: bool = False) -> jnp.ndarray:
    """Level-synchronous traversal: one [B, N_l] intersection per level.

    The pre-fusion hot path, kept as the fused kernel's benchmark baseline
    and oracle (``ops.traverse_fused`` falls back to this same loop shape,
    kernel-accelerated, when a tree's working set exceeds the VMEM
    budget). ``use_kernel`` here only accelerates each level's
    cross-intersection; frontier masks still round-trip through HBM.
    """
    mask = _cross_intersect(queries, tree.levels[0].mbrs, use_kernel)  # [B, 1]
    for level in tree.levels[1:]:
        parent_alive = mask[:, level.parent]                 # [B, N_l]
        hit = _cross_intersect(queries, level.mbrs, use_kernel)
        mask = parent_alive & hit
    return mask


class RefineResult(NamedTuple):
    counts: jnp.ndarray      # [B, K] qualifying points per (query, leaf slot)
    inside: jnp.ndarray      # [B, K, M_pad] bool, per-entry containment
    leaf_idx: jnp.ndarray    # [B, K] leaf ids refined (padding slots arbitrary)
    valid: jnp.ndarray       # [B, K] slot validity


def compact_mask_counted(mask: jnp.ndarray, k: int
                         ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """[B, L] bool → (indices [B, k] i32, valid [B, k] bool, count [B] i32).

    Takes the first ``k`` set leaves per row (leaf-ID order). Sort-free:
    the ``j``-th set bit's column is the first position where the row's
    inclusive prefix count reaches ``j + 1``, i.e. a rowwise binary search
    of ``1..k`` over the cumsum — O(B·(L + k·log L)), no sort and no
    scatter (a rowwise scatter is equivalent but an order of magnitude
    slower under XLA:CPU; see EXPERIMENTS.md). ``count`` is the row's
    total set bits, so overflow (``count > k``) and validity come for free
    from the same scan — callers no longer re-reduce the mask.

    This is the canonical compaction scheme; the fused traversal kernel's
    epilogue (``kernels.traverse_fused.traverse_compact_t``) implements the
    identical rank semantics inside VMEM and is tested bit-identical.
    """
    m = mask.astype(jnp.int32)
    cs = jnp.cumsum(m, axis=-1)                          # inclusive prefix
    count = cs[:, -1]                                    # = sum, one pass
    targets = jnp.arange(1, k + 1, dtype=jnp.int32)
    idx = jax.vmap(
        lambda c: jnp.searchsorted(c, targets, side="left"))(cs)
    valid = jnp.arange(k, dtype=jnp.int32)[None, :] < count[:, None]
    return jnp.where(valid, idx.astype(jnp.int32), 0), valid, count


def compact_mask(mask: jnp.ndarray, k: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """[B, L] bool → (indices [B, k] i32, valid [B, k] bool).

    Thin wrapper over ``compact_mask_counted`` for callers that don't need
    the per-row count; overflow is reported via ``overflowed()`` and
    handled by the exact fallback path.
    """
    idx, valid, _ = compact_mask_counted(mask, k)
    return idx, valid


def compact_candidates(ids: jnp.ndarray, ok: jnp.ndarray, k: int
                       ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """First ``k`` **distinct** ids (ascending) among masked candidates.

    ``ids`` [B, N] i32 (≥ 0 where ``ok``), ``ok`` [B, N] bool →
    ``(slots [B, k] i32, valid [B, k] bool, count [B] i32)`` with
    ``count`` the distinct-id total. Bit-compatible with
    ``compact_mask_counted(scatter(ids into [B, L]), k)`` — same slot
    order, zero-filled invalid slots, same count — but without ever
    materializing a ``[B, L]`` table: dedup and ranking are O(N²)
    pairwise compares, the right trade when the candidate list is small
    (N ≪ L — per-cell label slots, gathered shard top-k lists). This is
    what lets the engine's AI path keep the compact slot table as the
    only inter-stage format.
    """
    B, N = ids.shape
    ids = ids.astype(jnp.int32)
    eq = ids[:, :, None] == ids[:, None, :]          # [B, i, j]
    earlier = jnp.tril(jnp.ones((N, N), jnp.bool_), -1)  # j < i
    dup = jnp.any(eq & earlier[None] & ok[:, None, :], axis=-1)
    rep = ok & ~dup                                  # first occurrence per id
    count = jnp.sum(rep.astype(jnp.int32), axis=-1)
    less = ids[:, None, :] < ids[:, :, None]         # id_j < id_i
    rank = jnp.sum((rep[:, None, :] & less).astype(jnp.int32), axis=-1)
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    slot = jnp.where(rep & (rank < k), rank, k)      # park the rest at k
    slots = jnp.zeros((B, k + 1), jnp.int32).at[rows, slot].max(
        jnp.where(rep, ids, 0))[:, :k]
    valid = jnp.arange(k, dtype=jnp.int32)[None, :] < count[:, None]
    return jnp.where(valid, slots, 0), valid, count


def compact_mask_topk(mask: jnp.ndarray, k: int
                      ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Pre-optimization ``top_k``-based compaction (equivalence oracle)."""
    k_eff = min(k, mask.shape[-1])
    vals, idx = jax.lax.top_k(mask.astype(jnp.int32), k_eff)
    if k_eff < k:  # pad slots so callers keep a static [B, k] shape
        idx = jnp.pad(idx, ((0, 0), (0, k - k_eff)))
        vals = jnp.pad(vals, ((0, 0), (0, k - k_eff)))
    return idx.astype(jnp.int32), vals > 0


def overflowed(mask: jnp.ndarray, k: int) -> jnp.ndarray:
    """[B, L] → [B] bool: more than ``k`` leaves set (compact would truncate)."""
    return jnp.sum(mask.astype(jnp.int32), axis=-1) > k


def refine_leaves(tree: DeviceTree, queries: jnp.ndarray, leaf_idx: jnp.ndarray,
                  valid: jnp.ndarray, use_kernel: bool = False) -> RefineResult:
    """Exact containment test over the entries of selected leaves.

    ``queries``: [B, 4]; ``leaf_idx``: [B, K]; ``valid``: [B, K].
    Guarantees no false positives (paper §III-C): every reported entry is
    re-checked against the query rectangle.
    """
    if use_kernel:
        from repro.kernels import ops as kops
        inside = kops.leaf_refine(queries, tree.leaf_entries, leaf_idx, valid)
    else:
        pts = tree.leaf_entries[leaf_idx]                   # [B, K, 2, M]
        inside = geo.jnp_contains_point(queries[:, None, None, :],
                                        jnp.swapaxes(pts, -1, -2))
        inside = inside & valid[:, :, None]
    counts = jnp.sum(inside.astype(jnp.int32), axis=-1)     # [B, K]
    return RefineResult(counts=counts, inside=inside, leaf_idx=leaf_idx,
                        valid=valid)


class CompactVisit(NamedTuple):
    leaf_idx: jnp.ndarray    # [B, k] i32 — first k visited leaves, ID order
    valid: jnp.ndarray       # [B, k] bool slot validity
    n_visited: jnp.ndarray   # [B] i32 total visited leaves (may exceed k)
    overflow: jnp.ndarray    # [B] bool — more than k leaves visited


def visited_leaves_compact(tree: DeviceTree, queries: jnp.ndarray, k: int,
                           use_kernel: bool = False,
                           tile_b: Optional[int] = None,
                           tile_l: Optional[int] = None) -> CompactVisit:
    """Classical visited set, compacted: first ``k`` visited leaves per row.

    With ``use_kernel`` this runs the fused traversal kernel's compaction
    epilogue (``kernels.ops.traverse_compact``): the ``[B, L]`` visited
    mask stays in VMEM and only the ``[B, k]`` slot table plus per-row
    counts reach HBM — the serving-path form. Without it, the jnp oracle
    materializes the mask and compacts it with the identical cumsum-rank
    scheme. ``tile_b``/``tile_l`` override the kernel's tile choice
    (testing/tuning only).
    """
    if use_kernel:
        from repro.kernels import ops as kops
        idx, valid, count = kops.traverse_compact(
            queries, [lv.mbrs for lv in tree.levels],
            [lv.parent for lv in tree.levels], k, tb=tile_b, tl=tile_l,
            slices=getattr(tree, "aslices", None))
    else:
        mask = visited_leaf_mask_per_level(tree, queries, use_kernel=False)
        idx, valid, count = compact_mask_counted(mask, k)
    return CompactVisit(leaf_idx=idx, valid=valid, n_visited=count,
                        overflow=count > k)


class QueryResult(NamedTuple):
    visited: jnp.ndarray        # [B, L] bool — classical visited set
    true_leaves: jnp.ndarray    # [B, L] bool — leaves with qualifying points
    n_visited: jnp.ndarray      # [B] i32
    n_true: jnp.ndarray         # [B] i32
    n_results: jnp.ndarray      # [B] i32 total qualifying points
    result_ids: jnp.ndarray     # [B, max_results] i32, -1 padded
    truncated: jnp.ndarray      # [B] bool — static bounds overflowed


def scatter_rows(base: jnp.ndarray, idx: jnp.ndarray,
                 vals: jnp.ndarray) -> jnp.ndarray:
    """Rowwise scatter: base [B, L], idx [B, K], vals [B, K] → [B, L]."""
    B = base.shape[0]
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    return base.at[rows, idx].max(vals)


# target slots one step of ``gather_result_ids``'s loop searches: one lane
# width
GATHER_CHUNK = 128


def gather_result_ids(tree: DeviceTree, refine: RefineResult,
                      max_results: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Flatten qualifying entry ids to [B, max_results] (padded with -1).

    Sort-free, same scheme as ``compact_mask``: the ``j``-th qualifying
    entry's flat (leaf-slot, entry) position is a rowwise binary search of
    ``j + 1`` over the inclusive prefix count ``cs``. Only the slots some
    row of the batch can fill are searched: a ``lax.while_loop`` walks
    chunks of ``GATHER_CHUNK`` target slots and stops once the chunk start
    passes ``min(max(n_in), max_results)``, the batch's largest hit count;
    slots past it keep the -1 the output starts from. A last chunk that
    would run past ``max_results`` starts at ``max_results − C'``
    (``C' = min(GATHER_CHUNK, max_results)``), so the slots it shares with
    the chunk before are written again with the same values. Each slot's
    value is the one a search of every slot would give: the ids, the -1
    padding and the truncation flag are bit-identical to the
    ``gather_result_ids_topk`` oracle's, whatever the batch's hit counts.
    """
    B, K, M = refine.inside.shape
    flat_in = refine.inside.reshape(B, -1).astype(jnp.int32)
    cs = jnp.cumsum(flat_in, axis=-1)
    n_in = cs[:, -1]
    width = min(GATHER_CHUNK, max_results)
    stop = jnp.minimum(jnp.max(n_in, initial=0), max_results)
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    search = jax.vmap(lambda c, t: jnp.searchsorted(c, t, side="left"),
                      in_axes=(0, None))

    def chunk(carry):
        c, out = carry
        start = jnp.minimum(c * GATHER_CHUNK, max_results - width)
        targets = start + 1 + jnp.arange(width, dtype=jnp.int32)
        pos = search(cs, targets)
        safe = jnp.minimum(pos, K * M - 1).astype(jnp.int32)
        ids = tree.leaf_entry_ids[refine.leaf_idx[rows, safe // M],
                                  safe % M]
        vals = jnp.where(targets[None, :] <= n_in[:, None], ids, -1)
        return c + 1, jax.lax.dynamic_update_slice(out, vals, (0, start))

    out0 = jnp.full((B, max_results), -1, tree.leaf_entry_ids.dtype)
    _, out = jax.lax.while_loop(lambda carry: carry[0] * GATHER_CHUNK < stop,
                                chunk, (jnp.int32(0), out0))
    trunc = n_in > max_results
    return out, trunc


def gather_result_ids_topk(tree: DeviceTree, refine: RefineResult,
                           max_results: int
                           ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Pre-optimization ``top_k``-based gather (equivalence oracle)."""
    ids = tree.leaf_entry_ids[refine.leaf_idx]              # [B, K, M]
    B = ids.shape[0]
    flat_ids = ids.reshape(B, -1)
    flat_in = refine.inside.reshape(B, -1)
    key = flat_in.astype(jnp.int32)
    take, slot = jax.lax.top_k(key, max_results)            # first hits
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    out = jnp.where(take > 0, flat_ids[rows, slot], -1)
    trunc = jnp.sum(flat_in.astype(jnp.int32), axis=-1) > max_results
    return out, trunc


@functools.partial(jax.jit, static_argnames=("max_visited", "max_results",
                                             "use_kernel"))
def range_query(tree: DeviceTree, queries: jnp.ndarray, *,
                max_visited: int = 256, max_results: int = 512,
                use_kernel: bool = False) -> QueryResult:
    """Full classical batched range query: traverse → compact → refine.

    This is the **R** path of the "AI+R"-tree. It also produces the
    (visited, true) leaf sets that define α and the training labels.
    """
    queries = queries.astype(jnp.float32)
    visited = visited_leaf_mask(tree, queries, use_kernel)   # [B, L]
    leaf_idx, valid, n_vis = compact_mask_counted(visited, max_visited)
    ref = refine_leaves(tree, queries, leaf_idx, valid, use_kernel)
    B, L = visited.shape
    true_rows = scatter_rows(
        jnp.zeros((B, L), dtype=jnp.int32), leaf_idx,
        (ref.counts > 0).astype(jnp.int32) * valid.astype(jnp.int32))
    true_leaves = true_rows > 0
    result_ids, trunc_r = gather_result_ids(tree, ref, max_results)
    trunc_v = n_vis > max_visited
    return QueryResult(
        visited=visited,
        true_leaves=true_leaves,
        n_visited=n_vis,
        n_true=jnp.sum(true_leaves.astype(jnp.int32), axis=-1),
        n_results=jnp.sum(ref.counts * valid.astype(jnp.int32), axis=-1),
        result_ids=result_ids,
        truncated=trunc_v | trunc_r,
    )


class CompactQueryResult(NamedTuple):
    leaf_idx: jnp.ndarray       # [B, max_visited] i32 compacted visited set
    valid: jnp.ndarray          # [B, max_visited] bool slot validity
    n_visited: jnp.ndarray      # [B] i32
    n_true: jnp.ndarray         # [B] i32
    n_results: jnp.ndarray      # [B] i32 total qualifying points
    result_ids: jnp.ndarray     # [B, max_results] i32, -1 padded
    truncated: jnp.ndarray      # [B] bool — static bounds overflowed


@functools.partial(jax.jit, static_argnames=("max_visited", "max_results",
                                             "use_kernel", "tile_b",
                                             "tile_l"))
def range_query_compact(tree: DeviceTree, queries: jnp.ndarray, *,
                        max_visited: int = 256, max_results: int = 512,
                        use_kernel: bool = True,
                        tile_b: Optional[int] = None,
                        tile_l: Optional[int] = None) -> CompactQueryResult:
    """Serving-path classical range query: traverse+compact → refine.

    The ``range_query`` variant for the hot path: the traversal kernel's
    compaction epilogue hands the first ``max_visited`` visited leaf ids
    straight to the scalar-prefetch refine kernel, so the ``[B, L]``
    visited mask never round-trips through HBM (and is absent from the
    lowered HLO on the kernel path). Use ``range_query`` when the dense
    visited/true masks themselves are needed — labels, α, training.

    Per-field bit-identical to ``range_query`` (``n_visited``/``n_true``/
    ``n_results``/``result_ids``/``truncated`` and the compacted slots).
    """
    queries = queries.astype(jnp.float32)
    with jax.named_scope("traverse"):
        cv = visited_leaves_compact(tree, queries, max_visited,
                                    use_kernel=use_kernel,
                                    tile_b=tile_b, tile_l=tile_l)
    with jax.named_scope("refine"):
        ref = refine_leaves(tree, queries, cv.leaf_idx, cv.valid, use_kernel)
    with jax.named_scope("gather_ids"):
        result_ids, trunc_r = gather_result_ids(tree, ref, max_results)
    validi = cv.valid.astype(jnp.int32)
    return CompactQueryResult(
        leaf_idx=cv.leaf_idx,
        valid=cv.valid,
        n_visited=cv.n_visited,
        # compacted slots hold distinct leaves, so the slot-level count is
        # the leaf-level count — no [B, L] scatter needed
        n_true=jnp.sum((ref.counts > 0).astype(jnp.int32) * validi, axis=-1),
        n_results=jnp.sum(ref.counts * validi, axis=-1),
        result_ids=result_ids,
        truncated=cv.overflow | trunc_r,
    )


def alpha(n_true: jnp.ndarray, n_visited: jnp.ndarray) -> jnp.ndarray:
    """Overlap ratio α = TN(Q)/VN(Q) ∈ [0, 1] (§III-A2).

    Queries that visit no leaves (empty region) get α = 1 — nothing was
    extraneous, so they are maximally low-overlap.
    """
    return jnp.where(n_visited > 0, n_true / jnp.maximum(n_visited, 1), 1.0)
