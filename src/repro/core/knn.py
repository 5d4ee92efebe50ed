"""Batched k-nearest-neighbor serving on the slot-table contract.

The kNN path is *distance browsing over the range machinery*: probe the
tree with the query's ``center ± radius`` box through the fused
traversal's compaction epilogue (``visited_leaves_compact`` — the
``[B, L]`` visited mask never reaches HBM on the kernel path), then
distance-browse exactly the named leaf slots (``kernels.knn_browse`` —
only those entry tiles move HBM→VMEM) and take the k smallest in-radius
distances over the flat ``[B, K·M]`` candidate view.

Exactness argument: every point within distance ``r`` of the center
lies inside the probe box, so it sits in a visited leaf. If the visited
set did not overflow its slot table **and** at least ``k`` candidates
fell within ``r``, the k smallest in-radius distances are the global
k nearest — anything outside ``r`` is farther than all of them. Rows
where either condition fails are flagged ``truncated`` and re-served by
the wide tier of ``make_knn_steps``: the radius **doubles** (and the
slot table widens) instead of a rect widening — the same two-tier
``serve_workload`` machinery the range path uses, with the re-serve
geometry swapped. Residual truncation stays flagged, never silently
approximate.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.device_tree import DeviceTree
from repro.core.traversal import visited_leaves_compact


class KnnResult(NamedTuple):
    neighbor_ids: jnp.ndarray   # [B, k] i32 entry ids, -1 padded
    neighbor_d2: jnp.ndarray    # [B, k] f32 squared distances, +inf padded
    n_within: jnp.ndarray       # [B] i32 candidates within the radius
    n_visited: jnp.ndarray      # [B] i32 leaves the probe box visited
    leaf_accesses: jnp.ndarray  # [B] i32 leaf tiles actually browsed
    truncated: jnp.ndarray      # [B] bool — result not provably exact


def query_centers(queries: jnp.ndarray) -> jnp.ndarray:
    """[B, 4] rects (or [B, 2] points) → [B, 2] f32 centers."""
    q = queries.astype(jnp.float32)
    if q.shape[-1] == 2:
        return q
    return jnp.stack([(q[:, 0] + q[:, 2]) * 0.5,
                      (q[:, 1] + q[:, 3]) * 0.5], axis=1)


@functools.partial(jax.jit, static_argnames=("k", "max_visited",
                                             "use_kernel", "tile_b",
                                             "tile_l"))
def knn_query(tree: DeviceTree, queries: jnp.ndarray, *, k: int,
              radius: float, max_visited: int = 64,
              use_kernel: bool = False, tile_b: int | None = None,
              tile_l: int | None = None) -> KnnResult:
    """Radius-probed exact kNN: queries [B, 4] rects (centers taken) or
    [B, 2] points → ``KnnResult``.

    ``radius`` is the probe radius (data units). A row is exact unless
    ``truncated`` — the visited set overflowed ``max_visited`` slots or
    fewer than ``k`` candidates fell within the radius (see module doc).
    """
    centers = query_centers(queries)
    r = jnp.float32(radius)
    box = jnp.concatenate([centers - r, centers + r], axis=1)
    cv = visited_leaves_compact(tree, box, max_visited,
                                use_kernel=use_kernel, tile_b=tile_b,
                                tile_l=tile_l)
    c3 = jnp.concatenate([centers, jnp.full_like(centers[:, :1], r * r)],
                         axis=1)
    if use_kernel:
        from repro.kernels import ops as kops
        d2 = kops.knn_browse(c3, tree.leaf_entries, cv.leaf_idx, cv.valid)
    else:
        from repro.kernels import ref as kref
        safe_idx = jnp.clip(cv.leaf_idx, 0,
                            tree.leaf_entries.shape[0] - 1)
        d2 = kref.knn_browse(c3, tree.leaf_entries, safe_idx, cv.valid)
    B = centers.shape[0]
    flat_d2 = d2.reshape(B, -1)                         # [B, K·M]
    safe_idx = jnp.clip(cv.leaf_idx, 0, tree.leaf_entry_ids.shape[0] - 1)
    flat_ids = tree.leaf_entry_ids[safe_idx].reshape(B, -1)
    n_within = jnp.sum(jnp.isfinite(flat_d2).astype(jnp.int32), axis=-1)
    # top-k smallest: negate and lax.top_k (ties break to the lower flat
    # position, so slot order — hence ids — is deterministic per form)
    kk = min(k, flat_d2.shape[-1])
    neg, pos = jax.lax.top_k(-flat_d2, kk)
    d2k = -neg
    idk = jnp.take_along_axis(flat_ids, pos, axis=-1)
    if kk < k:          # degenerate tiny trees: keep the static [B, k]
        d2k = jnp.pad(d2k, ((0, 0), (0, k - kk)),
                      constant_values=jnp.inf)
        idk = jnp.pad(idk, ((0, 0), (0, k - kk)), constant_values=0)
    hit = jnp.isfinite(d2k)
    return KnnResult(
        neighbor_ids=jnp.where(hit, idk, -1),
        neighbor_d2=jnp.where(hit, d2k, jnp.inf),
        n_within=n_within,
        n_visited=cv.n_visited,
        leaf_accesses=jnp.minimum(cv.n_visited, max_visited),
        truncated=cv.overflow | (n_within < k),
    )


def make_knn_steps(tree: DeviceTree, *, k: int, radius: float,
                   max_visited: int = 64, wide_factor: int = 8,
                   use_kernel: bool = False):
    """Two-tier kNN serve steps for ``schedule.serve_workload``.

    The narrow tier probes at ``radius``; the wide tier doubles the
    radius and widens the slot table by ``wide_factor`` — the kNN
    analogue of ``engine.make_two_tier_steps``'s width widening, wired
    to the same re-serve loop (``trunc_field="truncated"``). Both tiers
    share the static ``[B, k]`` result width, so the merge keeps wide
    rows whole.
    """
    narrow = jax.jit(lambda q: knn_query(
        tree, q, k=k, radius=radius, max_visited=max_visited,
        use_kernel=use_kernel))
    wide = jax.jit(lambda q: knn_query(
        tree, q, k=k, radius=radius * 2.0,
        max_visited=max_visited * wide_factor, use_kernel=use_kernel))
    return narrow, wide


def default_radius(tree: DeviceTree, k: int, margin: float = 2.0) -> float:
    """Density-derived probe radius: for ~uniform data, a disc holding
    ``k`` points has radius ``sqrt(k·A / (π·n))``; ``margin`` buys
    slack so the narrow tier usually resolves in one pass."""
    root = np.asarray(tree.levels[0].mbrs, np.float64)
    area = float(max((root[:, 2].max() - root[:, 0].min())
                     * (root[:, 3].max() - root[:, 1].min()), 1e-12))
    n = max(int(tree.n_points), 1)
    return float(margin * math.sqrt(max(k, 1) * area / (math.pi * n)))


def knn_brute(points: np.ndarray, centers: np.ndarray, k: int
              ) -> tuple[np.ndarray, np.ndarray]:
    """Brute-force oracle: all-pairs f32 distances → ``(d2 [B, k],
    ids [B, k])`` ascending. The arithmetic (dx·dx + dy·dy in f32) is
    evaluated through jnp so XLA applies the identical FMA contraction
    it applies on the serving path — a numpy evaluation of the same
    expression differs by 1 ulp wherever XLA fuses the multiply-add.
    Distances then compare bit-exactly; ids are compared only where
    distances are distinct.
    """
    pts = jnp.asarray(np.asarray(points, np.float32))
    c = jnp.asarray(np.asarray(centers, np.float32))
    kk = min(k, pts.shape[0])

    @functools.partial(jax.jit, static_argnames=("n",))
    def _topk(pts, c, n):
        dx = pts[None, :, 0] - c[:, None, 0]
        dy = pts[None, :, 1] - c[:, None, 1]
        d2 = dx * dx + dy * dy
        return jax.lax.top_k(-d2, n)

    neg, idx = _topk(pts, c, kk)
    out_d2 = np.asarray(-neg)
    idx = np.asarray(idx)
    if kk < k:
        pad = ((0, 0), (0, k - kk))
        out_d2 = np.pad(out_d2, pad, constant_values=np.inf)
        idx = np.pad(idx, pad, constant_values=-1)
    return out_d2, idx.astype(np.int64)
