"""The "AI+R"-tree (paper §IV): router-dispatched hybrid of AI- and R-paths.

For each query the binary router predicts high-/low-overlap; high-overlap
queries take the AI path (predicted leaves only), low-overlap queries take
the classical R path. AI-path queries whose prediction is unusable fall back
to the R path (exactness). Per-query *leaf access* counts are tracked the
way the paper costs them: the AI path pays its predicted accesses, plus the
full R-tree visit set if it had to fall back.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.aitree import AITree, ai_query_compact
from repro.core.classifiers.router import Router, route_high
from repro.core.device_tree import DeviceTree
from repro.core.grid import cells_of_queries
from repro.core import traversal


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class HybridTree:
    tree: DeviceTree
    ait: AITree
    router: Router


class HybridResult(NamedTuple):
    routed_high: jnp.ndarray    # [B] router verdict (True → AI path)
    used_ai: jnp.ndarray        # [B] answered by the AI path (no fallback)
    n_results: jnp.ndarray      # [B] qualifying points
    result_ids: jnp.ndarray     # [B, max_results]
    leaf_accesses: jnp.ndarray  # [B] paper cost unit (leaf I/Os)
    n_visited_r: jnp.ndarray    # [B] classical visit count (for α / reporting)
    n_true: jnp.ndarray         # [B] true leaf count
    truncated: jnp.ndarray      # [B] R-path static bounds overflowed — the
    #                             scheduler re-serves these on a wide-bound
    #                             tier (mirrors ServeStats.r_truncated)
    guarded: jnp.ndarray        # [B] routed-high but demoted to the R path
    #                             by the cell guard (fit < 1 or stale cell —
    #                             mirrors ServeStats.guarded)
    mispredict: jnp.ndarray     # [B] AI-path attempt hit the paper's
    #                             misprediction signal (a predicted leaf
    #                             with zero qualifying entries) — per-cell
    #                             drift evidence for the maintenance policy
    cell_id: jnp.ndarray        # [B] i32 anchor grid cell of the query
    #                             (-1 on cell-window overflow) — the key
    #                             the monitor aggregates signals under


def guard_demoted(ait: AITree, queries: jnp.ndarray) -> jnp.ndarray:
    """[B] bool: query overlaps a cell the guard holds back from the AI
    path (``cell_ok`` False — under-fit at build time, or stale since the
    freshness monitor saw inserts land there). Shared by ``hybrid_query``
    and (shard-local + psum) the engine's ``_ai_path``.
    """
    cell_ids, valid, _ = cells_of_queries(ait.grid, queries, ait.max_cells)
    return jnp.any(valid & ~ait.cell_ok[cell_ids], axis=-1)


def is_point_query(queries: jnp.ndarray) -> jnp.ndarray:
    """[B, 4] → [B] bool: degenerate rects (zero extent on both axes).

    Device-side twin of ``schedule.point_query_mask`` — the detection
    that dispatches the point-query fast path.
    """
    q = queries.astype(jnp.float32)
    return (q[:, 0] == q[:, 2]) & (q[:, 1] == q[:, 3])


@functools.partial(jax.jit, static_argnames=("max_visited", "max_results",
                                             "use_kernel", "force_path",
                                             "guard"))
def point_query(h: HybridTree, queries: jnp.ndarray, *,
                max_visited: int = 32, max_results: int = 64,
                use_kernel: bool = False, force_path: str = "auto",
                guard: bool = True) -> HybridResult:
    """Point-query fast path: degenerate rects served with single-cell
    AI routing and a narrowed traversal.

    A zero-extent query overlaps exactly one grid cell, so the AI path's
    cell window collapses to ``max_cells=1`` — no window overflow, one
    bank gather instead of ``max_cells`` — and the classical visit set is
    a root-to-leaf containment stack, so ``max_visited``/``max_results``
    shrink to point-sized bounds. No wide tier: the narrowed bounds must
    cover every row (callers assert ``truncated`` stays empty — the
    launch driver and the smoke gate both do) instead of re-serving.
    Everything else — router, guard, fallback, cost accounting — is
    ``hybrid_query`` exactly; the result is a plain ``HybridResult``.
    """
    ait1 = dataclasses.replace(h.ait, max_cells=1)
    h1 = dataclasses.replace(h, ait=ait1)
    return hybrid_query(h1, queries, max_visited=max_visited,
                        max_results=max_results, use_kernel=use_kernel,
                        force_path=force_path, guard=guard)


@functools.partial(jax.jit, static_argnames=("max_visited", "max_results",
                                             "use_kernel", "force_path",
                                             "guard"))
def hybrid_query(h: HybridTree, queries: jnp.ndarray, *,
                 max_visited: int = 256, max_results: int = 512,
                 use_kernel: bool = False, force_path: str = "auto",
                 guard: bool = True) -> HybridResult:
    """Masked single-dispatch execution of both paths.

    ``force_path``: "auto" (router), "ai" (AI-tree only + fallback), or "r"
    (classical only) — the latter two give the paper's standalone baselines.

    ``guard`` (auto routing only): demote queries overlapping a not-ok
    cell (``AITree.cell_ok``) to the exact R path *before* prediction.
    This closes the under-prediction blind spot: a bank with
    ``exact_fit < 1`` can predict a strict subset of the true leaves with
    every predicted leaf still yielding hits — no fallback signal fires
    and results are silently dropped. The forced baselines bypass the
    guard (they measure the raw paths).
    """
    queries = queries.astype(jnp.float32)
    B = queries.shape[0]

    # device phases (``jax.named_scope``: op-name metadata only, so the
    # profiler names each op's phase and no output bit changes): route,
    # guard, ai, r, select; ai and r hold their own sub-phases
    with jax.named_scope("route"):
        if force_path == "r":
            high = jnp.zeros((B,), bool)
        elif force_path == "ai":
            high = jnp.ones((B,), bool)
        else:
            high = route_high(h.router, queries)

    with jax.named_scope("guard"):
        if guard and force_path == "auto":
            demoted = high & guard_demoted(h.ait, queries)
        else:
            demoted = jnp.zeros((B,), bool)
        eligible = high & ~demoted

    # serving-path compact AI query: prediction lands in the [B, max_pred]
    # slot table (bit-identical to the dense ai_query on all shared fields;
    # the [B, L] score table exists only on the kernel-free oracle rung)
    with jax.named_scope("ai"):
        ai = ai_query_compact(h.ait, h.tree, queries,
                              max_results=max_results, use_kernel=use_kernel)
    # serving-path R query: the traversal kernel's compaction epilogue
    # hands the visited slots to refinement (per-field bit-identical to
    # the dense-mask range_query)
    with jax.named_scope("r"):
        r = traversal.range_query_compact(h.tree, queries,
                                          max_visited=max_visited,
                                          max_results=max_results,
                                          use_kernel=use_kernel)
    with jax.named_scope("select"):
        used_ai = eligible & ~ai.fallback
        n_results = jnp.where(used_ai, ai.n_results, r.n_results)
        result_ids = jnp.where(used_ai[:, None], ai.result_ids,
                               r.result_ids)
        # cost accounting (paper §IV-A): AI path pays prediction + its
        # accesses; a fallback additionally pays the classical visit set.
        # Guard-demoted rows never reach prediction, so they pay the
        # classical cost only.
        leaf_accesses = jnp.where(
            eligible,
            ai.n_pred + jnp.where(ai.fallback, r.n_visited, 0),
            r.n_visited,
        )
        return HybridResult(
            routed_high=high,
            used_ai=used_ai,
            n_results=n_results,
            result_ids=result_ids,
            leaf_accesses=leaf_accesses,
            n_visited_r=r.n_visited,
            n_true=r.n_true,
            # only flag rows the R path answered — used_ai rows are exact
            # (AI-side truncation already forces fallback)
            truncated=r.truncated & ~used_ai,
            guarded=demoted,
            # only rows that actually attempted the AI path can mispredict
            # — drift evidence must not be charged to guarded/low-overlap
            # rows
            mispredict=eligible & ai.mispredict,
            cell_id=ai.cell_id,
        )
