"""Pallas TPU kernel: fused single-pass root→leaf R-tree traversal.

The level-synchronous traversal in ``repro.core.traversal`` launches one
dense ``[B, N_level]`` cross-intersection *per tree level* and round-trips
full boolean frontier masks through HBM between launches. This kernel walks
**all** levels in a single ``pallas_call``:

* Internal levels are tiny (they shrink geometrically from the leaves) and
  replicated, so their MBRs fit in VMEM whole. The frontier mask for a
  query-tile is computed once per query-tile (``j == 0``) and kept resident
  in a VMEM scratch buffer across all leaf tiles of that query-tile — it
  never touches HBM.

* Frontier expansion (``mask[:, parent]``) is rewritten as a one-hot matmul
  so it runs on the MXU instead of a lane-dimension gather (which Mosaic
  does not vectorize): ``alive = mask_f32 @ onehot(parent)``. The one-hot is
  built *inside* the kernel from the ``[1, N]`` int32 parent row with a
  broadcasted-iota compare, so no O(N_prev·N) matrix ever crosses HBM.

* The leaf level is tiled over the grid's minor axis. A ``pl.when`` guard
  skips the per-leaf-tile rectangle-intersection entirely when the one-hot
  expansion shows the whole tile is dead (every parent of every leaf in the
  tile failed), so dead subtrees generate no VPU work — the paper's "skip
  extraneous node accesses", applied to the traversal itself.

Two epilogues share that walk:

* ``traverse_fused_t`` writes the final ``[B, L]`` visited-leaf mask (the
  labels/α/training form — downstream consumers need the dense mask).

* ``traverse_compact_t`` never writes the mask at all: a compaction
  epilogue ranks each query-tile's set leaves by exclusive prefix count
  (the same cumsum-rank scheme as ``core.traversal.compact_mask``, with the
  running per-row rank base carried across leaf tiles in the revisited
  output block) and scatters the first ``k`` leaf ids into a ``[B, K]``
  slot table plus a ``[B, 1]`` per-row count. The serving path feeds those
  slots straight into the scalar-prefetch ``leaf_refine`` kernel, so the
  ``[B, L]`` mask never round-trips through HBM between traversal and
  refinement.

Layout: rectangles arrive transposed/planar (``[4, N]``) as in
``mbr_intersect.py``; parent index rows are ``[1, N]`` int32. ``ops.py``
handles padding (never-intersecting rects; parent = 0) and transposition.
Padding-lane parents point at real (or padding) nodes, which is harmless:
a padding rect can never intersect, so its mask lane is always dead.
"""
from __future__ import annotations

import functools
import json
import os
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The compaction epilogues live in ``kernels.epilogue`` (shared with
# ``mlp_infer`` and ``delta_probe``); the private names stay importable
# here.
from repro.kernels.epilogue import (
    compact_epilogue_interp as _compact_epilogue_interp,
    compact_epilogue_tpu as _compact_epilogue_tpu,
    vmem_bytes as epilogue_vmem,
)


DEF_TB = 256    # query-tile (sublane axis)
DEF_TL = 512    # leaf-tile (lane axis, multiple of 128)
SUB_TL = 512    # interpret-form early-exit subtile within the leaf tile
LANE = 128      # internal-level width quantum
# In-tile ranks per loop step of the TPU-form compaction epilogue (the
# loop's unroll; ``kernels.epilogue``).
COMPACT_KC = 8
# VMEM budget (bytes) for the TPU-form kernel's resident working set —
# frontier scratch, replicated internal-level operands, and the largest
# one-hot expansion matrix. Real VMEM is ~16 MiB/core; leave headroom for
# double buffering. ops.py estimates the working set per tree and routes
# over-budget trees to the ancestor-sliced form (per-level kernel loop as
# the last resort). Overridable via the REPRO_VMEM_BUDGET env var (bytes;
# read once at import) so the gate can be tuned per platform — and so
# tests can force every dispatch rung deterministically.
VMEM_BUDGET_ENV = "REPRO_VMEM_BUDGET"
DEF_VMEM_BUDGET = 8 * 1024 * 1024


def _read_vmem_budget(env: dict | None = None) -> int:
    """Parse the budget override (invalid / non-positive values fall back
    to the default — a typo'd env var must not disable every kernel)."""
    raw = (env if env is not None else os.environ).get(VMEM_BUDGET_ENV, "")
    try:
        v = int(raw)
    except (TypeError, ValueError):
        return DEF_VMEM_BUDGET
    return v if v > 0 else DEF_VMEM_BUDGET


VMEM_BUDGET = _read_vmem_budget()

# ---------------------------------------------------------------------------
# Autotune cache: the constants above are hand-picked fallbacks; a sweep
# (``benchmarks/autotune.py``) measures real tree shapes and caches the
# winning tiles per (form, B, L, height) key. ``ops.py`` consults the cache
# before every fused dispatch and only then falls back to the defaults.
# ---------------------------------------------------------------------------
AUTOTUNE_CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
DEF_AUTOTUNE_CACHE = os.path.join(os.path.dirname(__file__),
                                  "autotune_cache.json")
_TUNABLE_KEYS = ("tb", "tl", "sub_tl", "kc")


def autotune_cache_path() -> str:
    return os.environ.get(AUTOTUNE_CACHE_ENV, DEF_AUTOTUNE_CACHE)


@functools.lru_cache(maxsize=8)
def _load_autotune(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def tune_key(B: int, L: int, n_levels: int, interp: bool) -> str:
    """Cache key for one dispatch shape (exact match, no interpolation)."""
    return f"{'interp' if interp else 'tpu'}:B{B}:L{L}:H{n_levels}"


def tune_key_sliced(B: int, L: int, n_levels: int, interp: bool) -> str:
    """Cache key for the ancestor-sliced form (own knob space: its ``tl``
    is the slice granularity baked into the table, not a block choice)."""
    return f"sliced-{'interp' if interp else 'tpu'}:B{B}:L{L}:H{n_levels}"


def tuned_tiles(B: int, L: int, n_levels: int, interp: bool) -> dict:
    """Cached tile choice for a shape: subset of {tb, tl, sub_tl, kc}.

    Empty dict when the shape was never swept (or the cache is absent) —
    callers then use the hand-picked defaults. Values are sanitized to the
    kernels' alignment contracts so a stale or hand-edited cache can only
    cost performance, never correctness.
    """
    return tuned_tiles_for_key(tune_key(B, L, n_levels, interp))


def tuned_tiles_for_key(key: str) -> dict:
    """Sanitized cache lookup by explicit key (shared with other kernel
    families — ``mlp_infer`` keys its own form space into the same cache)."""
    ent = _load_autotune(autotune_cache_path()).get(key, {})
    out = {}
    for k in _TUNABLE_KEYS:
        if k in ent:
            v = int(ent[k])
            if k == "tb":
                v = max(8, v // 8 * 8)      # sublane multiple
            if k in ("tl", "sub_tl"):
                v = max(LANE, v // LANE * LANE)
            if k == "kc" and (v < 1 or LANE % v != 0):
                continue   # kc must divide the lane-padded slot width
            out[k] = max(1, v)
    return out


def vmem_estimate(int_widths_padded: Sequence[int], tb: int, tl: int) -> int:
    """Rough VMEM working-set bytes for the fused kernel.

    ``int_widths_padded``: lane-padded internal level widths, root first.
    Counts the frontier scratch, all replicated internal operands (MBRs +
    parent rows), the query/leaf/output tiles, and the largest transient
    one-hot matmul operand (consecutive internal pairs and the leaf
    expansion) — the term the frontier width alone does not bound.
    """
    n_last = int_widths_padded[-1]
    est = tb * n_last * 4                                   # scratch
    est += sum(4 * n * 4 + n * 4 for n in int_widths_padded)  # mbrs+parents
    est += 4 * tb * 4 + 4 * tl * 4 + 1 * tl * 4 + tb * tl   # q, leaf, out
    onehots = [a * b for a, b in zip(int_widths_padded[:-1],
                                     int_widths_padded[1:])]
    onehots.append(n_last * tl)
    est += max(onehots) * 4
    return est


def vmem_estimate_compact(int_widths_padded: Sequence[int], tb: int, tl: int,
                          kp: int, tpu_form: bool = True) -> int:
    """VMEM working-set bytes for the fused traversal+compaction kernel.

    The walk terms match ``vmem_estimate``; the compaction epilogue swaps
    the [tb, tl] mask output tile for the [tb, kp] slot table + [tb, 1]
    count, and adds the epilogue transient. That transient is
    form-dependent (``epilogue.vmem_bytes``): the TPU form's prefix
    matmul stages a [tl, tl] triangle, while the interpret form's binary
    search only needs the [tb, tl] prefix count — gating the interpret
    run (whose ``tl`` is the whole folded leaf axis) on the TPU transient
    would spuriously push CPU runs onto the per-level fallback.
    """
    est = vmem_estimate(int_widths_padded, tb, tl)
    est -= tb * tl                          # no [tb, tl] bool output tile
    est += tb * (kp + 1) * 4                # slot table + count accumulators
    est += epilogue_vmem(tb, tl, tpu_form)
    return est


def vmem_estimate_sliced(widths: Sequence[int], tb: int, tl: int,
                         tpu_form: bool = True) -> int:
    """VMEM working-set bytes for the ancestor-sliced fused traversal.

    ``widths``: per-internal-level *window* widths (the AncestorTable's,
    root first) — the sliced kernel stages one window per level instead of
    the whole level, and recomputes the walk per (query, leaf) tile, so
    there is no persistent frontier scratch; the frontier exists only as a
    ``[tb, widths[-1]]`` transient. The one-hot expansion operands shrink
    to window×window; the interpret form gathers instead (its transient is
    the ``[tb, tl]`` mask), mirroring ``vmem_estimate_compact``'s
    form-awareness so CPU runs aren't gated on MXU-only transients.
    """
    w_last = widths[-1]
    est = sum(4 * w * 4 + w * 4 for w in widths)     # window mbrs + parents
    est += 4 * tb * 4 + 4 * tl * 4 + tl * 4 + tb * tl  # q, leaf, out
    est += tb * w_last * 4                            # frontier transient
    if tpu_form:
        onehots = [a * b for a, b in zip(widths[:-1], widths[1:])]
        onehots.append(w_last * tl)
        est += max(onehots) * 4
    else:
        est += tb * tl * 4
    return est


def vmem_estimate_sliced_compact(widths: Sequence[int], tb: int, tl: int,
                                 kp: int, tpu_form: bool = True) -> int:
    """Sliced-walk analogue of ``vmem_estimate_compact``: same window
    terms as ``vmem_estimate_sliced``, the mask output tile swapped for
    the slot table + count accumulators plus the epilogue transient."""
    est = vmem_estimate_sliced(widths, tb, tl, tpu_form=tpu_form)
    est -= tb * tl                          # no [tb, tl] bool output tile
    est += tb * (kp + 1) * 4                # slot table + count accumulators
    est += epilogue_vmem(tb, tl, tpu_form)
    return est


def _tile_intersect(q, m):
    """q [4, TB] × m [4, TN] values → [TB, TN] bool (closed rectangles).

    Takes materialized values, not refs: each ref index-read costs a masked
    load (emulated one-by-one in interpret mode) — callers read each block
    once and slice the value.
    """
    qx0 = q[0, :][:, None]
    qy0 = q[1, :][:, None]
    qx1 = q[2, :][:, None]
    qy1 = q[3, :][:, None]
    mx0 = m[0, :][None, :]
    my0 = m[1, :][None, :]
    mx1 = m[2, :][None, :]
    my1 = m[3, :][None, :]
    return (qx0 <= mx1) & (mx0 <= qx1) & (qy0 <= my1) & (my0 <= qy1)


def _expand_mxu(mask_f32, parent_row, n_prev):
    """Frontier expansion: alive[b, c] > 0 iff mask[b, parent_row[c]] set.

    mask_f32 [TB, n_prev] (0/1), parent_row [n] i32 → alive [TB, n] f32.
    A gather along the lane dimension is what this *means*, but Mosaic does
    not vectorize lane gathers — so the hardware form is a one-hot matmul on
    the MXU. The one-hot is built in VMEM from the int32 parent row with a
    broadcasted-iota compare; no O(n_prev·n) matrix ever crosses HBM.
    """
    n = parent_row.shape[0]
    onehot = (parent_row[None, :] ==
              jax.lax.broadcasted_iota(jnp.int32, (n_prev, n), 0)
              ).astype(jnp.float32)
    return jax.lax.dot(mask_f32, onehot, preferred_element_type=jnp.float32)


def _walk_internal_tpu(q, int_m, int_p, frontier_ref, n_int: int):
    """TPU-form internal walk: root→last internal level, one-hot MXU
    expansion per level, final frontier written to the VMEM scratch."""
    # Root level: plain intersection (no parent).
    mask = _tile_intersect(q, int_m[0][:, :]).astype(jnp.float32)
    for l in range(1, n_int):
        alive = _expand_mxu(mask, int_p[l - 1][0, :],
                            int_m[l - 1].shape[1])
        hit = _tile_intersect(q, int_m[l][:, :])
        mask = jnp.where((alive > 0.0) & hit, 1.0, 0.0)
    frontier_ref[:, :] = mask


def _leaf_mask_interp(q, int_m, int_p, lm_v, leaf_par, n_int: int,
                      tb: int, tl: int, sub_tl: int = SUB_TL):
    """Interpret-form leaf mask as a *value* (no ref writes).

    Same semantics as the TPU form, restructured for the emulated grid
    loop, which materializes every intermediate and turns any ref-touching
    ``pl.when`` into full-buffer functionalization copies:

    * early exit runs as *value-level* ``lax.cond``s (branches return
      values, touch no refs) — an outer cond over the whole tile, then one
      per SUB-wide leaf subtile, each gated on a bounding box of the
      subtile's leaf MBRs computed in-kernel, so dead subtrees skip their
      intersection entirely;
    * the internal walk runs inside the outer live branch — one
      concatenated intersection over all internal levels, boolean masks end
      to end, lane gathers instead of one-hot matmuls.
    """

    def subtile_hit(sm):
        return jnp.any((q[0, :] <= jnp.max(sm[2, :]))
                       & (jnp.min(sm[0, :]) <= q[2, :])
                       & (q[1, :] <= jnp.max(sm[3, :]))
                       & (jnp.min(sm[1, :]) <= q[3, :]))

    def live():
        int_all = jnp.concatenate([m[:, :] for m in int_m], axis=1)
        hit_all = _tile_intersect(q, int_all)        # [TB, ΣN_l]
        off = int_m[0].shape[1]
        mask = hit_all[:, :off]
        for l in range(1, n_int):
            n = int_m[l].shape[1]
            mask = mask[:, int_p[l - 1][0, :]] & \
                hit_all[:, off:off + n]
            off += n
        outs = []
        for s in range(0, tl, sub_tl):
            e = min(s + sub_tl, tl)
            sm = lm_v[:, s:e]
            outs.append(jax.lax.cond(
                subtile_hit(sm),
                lambda sm=sm, s=s, e=e: mask[:, leaf_par[s:e]]
                & _tile_intersect(q, sm),
                lambda e=e, s=s: jnp.zeros((tb, e - s), jnp.bool_)))
        return outs[0] if len(outs) == 1 else \
            jnp.concatenate(outs, axis=1)

    tile_live = subtile_hit(lm_v)     # O(TB·4) bbox check, reused by callers
    mask = jax.lax.cond(tile_live, live,
                        lambda: jnp.zeros((tb, tl), jnp.bool_))
    return mask, tile_live


def _make_kernel(n_int: int, tb: int, tl: int, tpu_form: bool,
                 sub_tl: int = SUB_TL):
    """Build the mask-output kernel body for ``n_int`` internal levels.

    ``tpu_form=True`` is the hardware graph: one-hot-matmul expansion on the
    MXU, the internal walk run once per query-tile under ``pl.when(j == 0)``
    with the frontier persisted in VMEM scratch, and a ``pl.when`` tile-level
    early exit so leaf tiles under a dead frontier skip the intersection
    (predication is ~free on TPU).

    ``tpu_form=False`` is the branch-free interpret form: same semantics via
    ``_leaf_mask_interp`` — in interpret mode every ``pl.when`` lowers to a
    ``lax.cond`` that functionalizes the output/scratch refs (full-array
    copies per branch), so predication there *costs* rather than saves.
    Tests validate both forms.
    """

    def kernel(*refs):
        q_ref = refs[0]
        int_m = refs[1:1 + n_int]                       # [4, N_l] each
        int_p = refs[1 + n_int:2 * n_int]               # [1, N_l], levels 1..
        leaf_m = refs[2 * n_int]                        # [4, TL]
        leaf_p = refs[2 * n_int + 1]                    # [1, TL]
        o_ref = refs[2 * n_int + 2]                     # [TB, TL] bool
        frontier_ref = refs[2 * n_int + 3]              # [TB, N_last] f32

        q = q_ref[:, :]                                  # [4, TB]

        if tpu_form:
            j = pl.program_id(1)

            @pl.when(j == 0)
            def _walk_internal():
                _walk_internal_tpu(q, int_m, int_p, frontier_ref, n_int)

            frontier = frontier_ref[:, :]                # [TB, N_last]
            alive = _expand_mxu(frontier, leaf_p[0, :], frontier.shape[1])
            any_live = jnp.max(alive) > 0.0

            @pl.when(jnp.logical_not(any_live))
            def _dead_tile():
                o_ref[:, :] = jnp.zeros((tb, tl), jnp.bool_)

            @pl.when(any_live)
            def _live_tile():
                o_ref[:, :] = (alive > 0.0) & _tile_intersect(
                    q, leaf_m[:, :])
        else:
            o_ref[:, :] = _leaf_mask_interp(
                q, int_m, int_p, leaf_m[:, :], leaf_p[0, :], n_int, tb,
                tl, sub_tl)[0]

    return kernel


def _make_compact_kernel(n_int: int, tb: int, tl: int, kp: int, n_j: int,
                         tpu_form: bool, sub_tl: int = SUB_TL,
                         kc: int = COMPACT_KC):
    """Kernel body: fused traversal + compaction epilogue.

    Instead of writing the ``[TB, TL]`` visited mask, each leaf tile ranks
    its set leaves by exclusive prefix count — continued across tiles via a
    running per-row total in the revisited ``[TB, 1]`` count block — and
    scatters the global leaf ids of ranks ``< kp`` into the revisited
    ``[TB, KP]`` slot block (leaf-ID order, exactly ``compact_mask``'s
    cumsum-rank scheme). Both output blocks map to ``(i, 0)`` so they stay
    VMEM-resident across the whole leaf-tile sweep of a query tile: the
    mask never exists outside registers/VMEM.

    ``tpu_form=True`` realizes the scatter with ``epilogue``'s TPU form
    (prefix count on the MXU, then one rank-equality compare + lane-sum
    per in-tile rank — ranks are unique per row, so sum == select, and
    Mosaic vectorizes dense compare/reduce where it would not a lane
    scatter); the whole epilogue is skipped for dead tiles.
    ``tpu_form=False`` fills slots by value-level rowwise binary search of
    each slot's rank over the tile's inclusive prefix count — the same
    searchsorted scheme as ``compact_mask_counted``, unconditional value
    ops (interpret mode functionalizes ref-touching conds).
    """

    def kernel(*refs):
        q_ref = refs[0]
        int_m = refs[1:1 + n_int]                       # [4, N_l] each
        int_p = refs[1 + n_int:2 * n_int]               # [1, N_l], levels 1..
        leaf_m = refs[2 * n_int]                        # [4, TL]
        leaf_p = refs[2 * n_int + 1]                    # [1, TL]
        idx_ref = refs[2 * n_int + 2]                   # [TB, KP] i32 (i, 0)
        cnt_ref = refs[2 * n_int + 3]                   # [TB, 1] i32 (i, 0)
        frontier_ref = refs[2 * n_int + 4]              # [TB, N_last] f32

        q = q_ref[:, :]                                  # [4, TB]
        j = pl.program_id(1)

        if tpu_form:
            col = j * tl + jax.lax.broadcasted_iota(jnp.int32, (tb, tl), 1)

            @pl.when(j == 0)
            def _init():
                idx_ref[:, :] = jnp.zeros((tb, kp), jnp.int32)
                cnt_ref[:, :] = jnp.zeros((tb, 1), jnp.int32)
                _walk_internal_tpu(q, int_m, int_p, frontier_ref, n_int)

            frontier = frontier_ref[:, :]                # [TB, N_last]
            alive = _expand_mxu(frontier, leaf_p[0, :], frontier.shape[1])
            any_live = jnp.max(alive) > 0.0

            @pl.when(any_live)
            def _live_tile():
                mask = (alive > 0.0) & _tile_intersect(q, leaf_m[:, :])
                _compact_epilogue_tpu(mask, col, idx_ref, cnt_ref, kp, kc)
        else:
            mask, tile_live = _leaf_mask_interp(
                q, int_m, int_p, leaf_m[:, :], leaf_p[0, :], n_int, tb, tl,
                sub_tl)
            if n_j == 1:
                # Whole leaf axis in one tile (the usual interpret fold):
                # no rank base to carry — the epilogue is exactly
                # ``compact_mask_counted``, with a value-level early exit
                # on the traversal's own bbox liveness (information the
                # out-of-kernel compact never has; coarser than
                # ``jnp.any(mask)`` but free — the any() reduction would
                # itself scan the whole tile).
                def live():
                    m = mask.astype(jnp.int32)
                    cs = jnp.cumsum(m, axis=1)
                    targets = 1 + jax.lax.iota(jnp.int32, kp)
                    pos = jax.vmap(lambda c: jnp.searchsorted(
                        c, targets, side="left"))(cs)
                    idx = jnp.where(targets[None, :] <= cs[:, -1][:, None],
                                    pos.astype(jnp.int32), 0)
                    return idx, cs[:, -1][:, None]

                idx, cnt = jax.lax.cond(
                    tile_live, live,
                    lambda: (jnp.zeros((tb, kp), jnp.int32),
                             jnp.zeros((tb, 1), jnp.int32)))
                idx_ref[:, :] = idx
                cnt_ref[:, :] = cnt
            else:
                _compact_epilogue_interp(mask, j, tl, kp, idx_ref, cnt_ref)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("tb", "tl", "sub_tl", "interpret",
                                    "tpu_form"))
def traverse_fused_t(q_t: jnp.ndarray,
                     int_mbrs_t: Sequence[jnp.ndarray],
                     int_parents: Sequence[jnp.ndarray],
                     leaf_mbrs_t: jnp.ndarray,
                     leaf_parent: jnp.ndarray, *,
                     tb: int = DEF_TB, tl: int = DEF_TL,
                     sub_tl: int = SUB_TL,
                     interpret: bool = False,
                     tpu_form: bool | None = None) -> jnp.ndarray:
    """Transposed-layout entry point.

    ``q_t`` [4, B]; ``int_mbrs_t`` one [4, N_l] per internal level (root
    first, each N_l a multiple of 128); ``int_parents`` one [1, N_l] i32 per
    internal level *below the root*; ``leaf_mbrs_t`` [4, L];
    ``leaf_parent`` [1, L] i32 (into the last internal level). B must be a
    multiple of ``tb`` and L of ``tl`` (ops.py pads). Returns [B, L] bool.

    ``tpu_form`` defaults to ``not interpret``; pass ``tpu_form=True`` with
    ``interpret=True`` to validate the exact hardware graph off-TPU.
    """
    if tpu_form is None:
        tpu_form = not interpret
    n_int = len(int_mbrs_t)
    assert n_int >= 1 and len(int_parents) == n_int - 1
    _, B = q_t.shape
    _, L = leaf_mbrs_t.shape
    assert B % tb == 0 and L % tl == 0, (B, L, tb, tl)
    n_last = int_mbrs_t[-1].shape[1]
    grid = (B // tb, L // tl)

    rep = lambda shape: pl.BlockSpec(shape, lambda i, j: (0, 0))  # noqa: E731
    in_specs = [pl.BlockSpec((4, tb), lambda i, j: (0, i))]
    in_specs += [rep((4, m.shape[1])) for m in int_mbrs_t]
    in_specs += [rep((1, p.shape[1])) for p in int_parents]
    in_specs += [
        pl.BlockSpec((4, tl), lambda i, j: (0, j)),
        pl.BlockSpec((1, tl), lambda i, j: (0, j)),
    ]

    args = ([q_t.astype(jnp.float32)]
            + [m.astype(jnp.float32) for m in int_mbrs_t]
            + [p.astype(jnp.int32) for p in int_parents]
            + [leaf_mbrs_t.astype(jnp.float32),
               leaf_parent.astype(jnp.int32)])

    return pl.pallas_call(
        _make_kernel(n_int, tb, tl, tpu_form=tpu_form, sub_tl=sub_tl),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tb, tl), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, L), jnp.bool_),
        scratch_shapes=[pltpu.VMEM((tb, n_last), jnp.float32)],
        interpret=interpret,
        name="traverse_fused",
    )(*args)


@functools.partial(jax.jit,
                   static_argnames=("k", "tb", "tl", "sub_tl", "kc",
                                    "interpret", "tpu_form"))
def traverse_compact_t(q_t: jnp.ndarray,
                       int_mbrs_t: Sequence[jnp.ndarray],
                       int_parents: Sequence[jnp.ndarray],
                       leaf_mbrs_t: jnp.ndarray,
                       leaf_parent: jnp.ndarray, *,
                       k: int,
                       tb: int = DEF_TB, tl: int = DEF_TL,
                       sub_tl: int = SUB_TL, kc: int = COMPACT_KC,
                       interpret: bool = False,
                       tpu_form: bool | None = None
                       ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Transposed-layout fused traversal + compaction entry point.

    Operand layout and padding contract are identical to
    ``traverse_fused_t``. Returns ``(leaf_idx [B, KP] i32, count [B, 1]
    i32)`` with ``KP = k`` rounded up to ``LANE`` in the TPU form (lane
    tiling) and exactly ``k`` in the interpret form: row ``b``'s first
    ``min(count[b], KP)`` slots hold the ids of its visited leaves in
    leaf-ID order (exactly ``compact_mask``'s cumsum-rank order); slots past
    the count are 0. The ``[B, L]`` visited mask is never written — callers
    slice ``[:, :k]``, derive validity from ``count``, and overflow as
    ``count > k``.
    """
    if tpu_form is None:
        tpu_form = not interpret
    n_int = len(int_mbrs_t)
    assert n_int >= 1 and len(int_parents) == n_int - 1
    _, B = q_t.shape
    _, L = leaf_mbrs_t.shape
    assert B % tb == 0 and L % tl == 0, (B, L, tb, tl)
    kp = (k + LANE - 1) // LANE * LANE if tpu_form else k
    assert kp % kc == 0 or not tpu_form, (kp, kc)
    n_last = int_mbrs_t[-1].shape[1]
    grid = (B // tb, L // tl)

    rep = lambda shape: pl.BlockSpec(shape, lambda i, j: (0, 0))  # noqa: E731
    in_specs = [pl.BlockSpec((4, tb), lambda i, j: (0, i))]
    in_specs += [rep((4, m.shape[1])) for m in int_mbrs_t]
    in_specs += [rep((1, p.shape[1])) for p in int_parents]
    in_specs += [
        pl.BlockSpec((4, tl), lambda i, j: (0, j)),
        pl.BlockSpec((1, tl), lambda i, j: (0, j)),
    ]

    args = ([q_t.astype(jnp.float32)]
            + [m.astype(jnp.float32) for m in int_mbrs_t]
            + [p.astype(jnp.int32) for p in int_parents]
            + [leaf_mbrs_t.astype(jnp.float32),
               leaf_parent.astype(jnp.int32)])

    return pl.pallas_call(
        _make_compact_kernel(n_int, tb, tl, kp, L // tl, tpu_form=tpu_form,
                             sub_tl=sub_tl, kc=kc),
        grid=grid,
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((tb, kp), lambda i, j: (i, 0)),
                   pl.BlockSpec((tb, 1), lambda i, j: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, kp), jnp.int32),
                   jax.ShapeDtypeStruct((B, 1), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((tb, n_last), jnp.float32)],
        interpret=interpret,
        name="traverse_compact",
    )(*args)


# ---------------------------------------------------------------------------
# Ancestor-sliced form: same walk, windowed operands.
#
# The full-VMEM kernels above replicate every internal level into VMEM —
# fine while the tree is small, impossible past the budget. The sliced form
# exploits the flatten's sibling contiguity (each leaf tile's ancestor set
# per level is a contiguous index range): a host-built AncestorTable
# (``core.device_tree.build_ancestor_table``) records one block-aligned
# window per (internal level, leaf tile), the window starts ride in through
# scalar prefetch, and each grid cell's BlockSpecs stage only its tile's
# windows. Parent indices are rebased in-kernel (global − window start);
# out-of-window relative indices can only belong to padding lanes, whose
# never-intersecting MBRs are dead regardless (true-range ancestors land
# in-window by the table's min/max construction). The walk reruns per
# (query, leaf) tile over the small windows instead of persisting a
# frontier scratch across leaf tiles — that rerun is the price of a VMEM
# working set that no longer grows with the tree.
# ---------------------------------------------------------------------------


def _walk_sliced_tpu(q, int_m, int_rel, widths, n_int: int):
    """TPU-form windowed internal walk → frontier value [TB, widths[-1]].

    ``int_m``: per-level window MBR blocks; ``int_rel``: per-level
    window-relative parent rows (levels 1..) as values.
    """
    mask = _tile_intersect(q, int_m[0][:, :]).astype(jnp.float32)
    for l in range(1, n_int):
        alive = _expand_mxu(mask, int_rel[l - 1], widths[l - 1])
        hit = _tile_intersect(q, int_m[l][:, :])
        mask = jnp.where((alive > 0.0) & hit, 1.0, 0.0)
    return mask


def _leaf_mask_interp_sliced(q, int_m, int_rel, lm_v, leaf_rel, widths,
                             n_int: int, tb: int, tl: int,
                             sub_tl: int = SUB_TL):
    """Interpret-form sliced leaf mask as a value (no ref writes).

    Mirrors ``_leaf_mask_interp`` — value-level ``lax.cond`` early exits
    on in-kernel bounding boxes, lane gathers instead of one-hot matmuls —
    but over windowed operands: gathers use clamped window-relative parent
    indices with an explicit in-window validity mask (clamping alone would
    alias padding lanes onto real window slots).
    """

    def subtile_hit(sm):
        return jnp.any((q[0, :] <= jnp.max(sm[2, :]))
                       & (jnp.min(sm[0, :]) <= q[2, :])
                       & (q[1, :] <= jnp.max(sm[3, :]))
                       & (jnp.min(sm[1, :]) <= q[3, :]))

    def live():
        int_all = jnp.concatenate([m[:, :] for m in int_m], axis=1)
        hit_all = _tile_intersect(q, int_all)        # [TB, Σwidths]
        off = widths[0]
        mask = hit_all[:, :off]
        for l in range(1, n_int):
            rel = int_rel[l - 1]
            ok = (rel >= 0) & (rel < widths[l - 1])
            g = mask[:, jnp.clip(rel, 0, widths[l - 1] - 1)]
            mask = g & ok[None, :] & hit_all[:, off:off + widths[l]]
            off += widths[l]
        outs = []
        w_last = widths[-1]
        for s in range(0, tl, sub_tl):
            e = min(s + sub_tl, tl)
            sm = lm_v[:, s:e]
            rel = leaf_rel[s:e]
            ok = (rel >= 0) & (rel < w_last)
            outs.append(jax.lax.cond(
                subtile_hit(sm),
                lambda sm=sm, rel=rel, ok=ok:
                mask[:, jnp.clip(rel, 0, w_last - 1)] & ok[None, :]
                & _tile_intersect(q, sm),
                lambda e=e, s=s: jnp.zeros((tb, e - s), jnp.bool_)))
        return outs[0] if len(outs) == 1 else \
            jnp.concatenate(outs, axis=1)

    tile_live = subtile_hit(lm_v)
    mask = jax.lax.cond(tile_live, live,
                        lambda: jnp.zeros((tb, tl), jnp.bool_))
    return mask, tile_live


def _sliced_refs(refs, n_int: int):
    """Unpack the sliced kernels' ref list (scalar-prefetch ref first)."""
    s_ref = refs[0]
    q_ref = refs[1]
    int_m = refs[2:2 + n_int]                        # [4, w_l] windows
    int_p = refs[2 + n_int:1 + 2 * n_int]            # [1, w_l], levels 1..
    leaf_m = refs[1 + 2 * n_int]                     # [4, TL]
    leaf_p = refs[2 + 2 * n_int]                     # [1, TL]
    return s_ref, q_ref, int_m, int_p, leaf_m, leaf_p


def _sliced_rel_rows(s_ref, int_p, leaf_p, widths, n_int: int, j):
    """Window-relative parent rows (values): global − window start."""
    int_rel = [int_p[l - 1][0, :] - s_ref[l - 1, j] * widths[l - 1]
               for l in range(1, n_int)]
    leaf_rel = leaf_p[0, :] - s_ref[n_int - 1, j] * widths[n_int - 1]
    return int_rel, leaf_rel


def _make_sliced_kernel(n_int: int, widths, tb: int, tl: int,
                        tpu_form: bool, sub_tl: int = SUB_TL):
    """Mask-output kernel body over windowed operands.

    Same forms as ``_make_kernel``; the walk runs per grid cell over the
    tile's windows (no frontier scratch — nothing persists across ``j``),
    with the same ``pl.when`` dead-tile early exit on the leaf expansion.
    """

    def kernel(*refs):
        s_ref, q_ref, int_m, int_p, leaf_m, leaf_p = _sliced_refs(refs,
                                                                  n_int)
        o_ref = refs[3 + 2 * n_int]                  # [TB, TL] bool
        j = pl.program_id(1)
        q = q_ref[:, :]
        int_rel, leaf_rel = _sliced_rel_rows(s_ref, int_p, leaf_p, widths,
                                             n_int, j)

        if tpu_form:
            frontier = _walk_sliced_tpu(q, int_m, int_rel, widths, n_int)
            alive = _expand_mxu(frontier, leaf_rel, widths[-1])
            any_live = jnp.max(alive) > 0.0

            @pl.when(jnp.logical_not(any_live))
            def _dead_tile():
                o_ref[:, :] = jnp.zeros((tb, tl), jnp.bool_)

            @pl.when(any_live)
            def _live_tile():
                o_ref[:, :] = (alive > 0.0) & _tile_intersect(
                    q, leaf_m[:, :])
        else:
            o_ref[:, :] = _leaf_mask_interp_sliced(
                q, int_m, int_rel, leaf_m[:, :], leaf_rel, widths, n_int,
                tb, tl, sub_tl)[0]

    return kernel


def _make_sliced_compact_kernel(n_int: int, widths, tb: int, tl: int,
                                kp: int, tpu_form: bool,
                                sub_tl: int = SUB_TL, kc: int = COMPACT_KC):
    """Sliced traversal + the shared compaction epilogues.

    Identical slot semantics to ``_make_compact_kernel`` (revisited
    ``(i, 0)`` output blocks carry the running rank base across leaf
    tiles); only the walk's operands differ. The interpret form always
    uses the cross-tile epilogue — the sliced form exists precisely
    because the leaf axis spans multiple tiles.
    """

    def kernel(*refs):
        s_ref, q_ref, int_m, int_p, leaf_m, leaf_p = _sliced_refs(refs,
                                                                  n_int)
        idx_ref = refs[3 + 2 * n_int]                # [TB, KP] i32 (i, 0)
        cnt_ref = refs[4 + 2 * n_int]                # [TB, 1] i32 (i, 0)
        j = pl.program_id(1)
        q = q_ref[:, :]
        int_rel, leaf_rel = _sliced_rel_rows(s_ref, int_p, leaf_p, widths,
                                             n_int, j)

        if tpu_form:
            col = j * tl + jax.lax.broadcasted_iota(jnp.int32, (tb, tl), 1)

            @pl.when(j == 0)
            def _init():
                idx_ref[:, :] = jnp.zeros((tb, kp), jnp.int32)
                cnt_ref[:, :] = jnp.zeros((tb, 1), jnp.int32)

            frontier = _walk_sliced_tpu(q, int_m, int_rel, widths, n_int)
            alive = _expand_mxu(frontier, leaf_rel, widths[-1])
            any_live = jnp.max(alive) > 0.0

            @pl.when(any_live)
            def _live_tile():
                mask = (alive > 0.0) & _tile_intersect(q, leaf_m[:, :])
                _compact_epilogue_tpu(mask, col, idx_ref, cnt_ref, kp, kc)
        else:
            mask, _ = _leaf_mask_interp_sliced(
                q, int_m, int_rel, leaf_m[:, :], leaf_rel, widths, n_int,
                tb, tl, sub_tl)
            _compact_epilogue_interp(mask, j, tl, kp, idx_ref, cnt_ref)

    return kernel


def _sliced_grid_spec(n_int: int, widths, tb: int, tl: int, grid,
                      out_specs):
    """PrefetchScalarGridSpec shared by both sliced entry points: the
    ``[n_int, n_tiles]`` window-start table is the scalar-prefetch operand,
    and every internal level's BlockSpec indexes its block by the tile's
    prefetched start (index maps receive grid indices then the scalar
    ref)."""
    in_specs = [pl.BlockSpec((4, tb), lambda i, j, s: (0, i))]
    in_specs += [pl.BlockSpec((4, widths[l]),
                              lambda i, j, s, l=l: (0, s[l, j]))
                 for l in range(n_int)]
    in_specs += [pl.BlockSpec((1, widths[l]),
                              lambda i, j, s, l=l: (0, s[l, j]))
                 for l in range(1, n_int)]
    in_specs += [
        pl.BlockSpec((4, tl), lambda i, j, s: (0, j)),
        pl.BlockSpec((1, tl), lambda i, j, s: (0, j)),
    ]
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
        out_specs=out_specs)


@functools.partial(jax.jit,
                   static_argnames=("widths", "tb", "tl", "sub_tl",
                                    "interpret", "tpu_form"))
def traverse_fused_sliced_t(starts: jnp.ndarray,
                            q_t: jnp.ndarray,
                            int_mbrs_t: Sequence[jnp.ndarray],
                            int_parents: Sequence[jnp.ndarray],
                            leaf_mbrs_t: jnp.ndarray,
                            leaf_parent: jnp.ndarray, *,
                            widths: tuple, tb: int = DEF_TB,
                            tl: int = DEF_TL, sub_tl: int = SUB_TL,
                            interpret: bool = False,
                            tpu_form: bool | None = None) -> jnp.ndarray:
    """Ancestor-sliced transposed-layout entry point → [B, L] bool.

    ``starts`` [n_int, L//tl] i32 block-index window starts (the
    AncestorTable's, sharded rows matching the leaf shard if any);
    ``widths`` the matching static window widths. ``int_mbrs_t`` /
    ``int_parents`` follow ``traverse_fused_t``'s layout but each level
    must be padded to a multiple of its window width (ops.py does). B must
    be a multiple of ``tb`` and L of ``tl``.
    """
    if tpu_form is None:
        tpu_form = not interpret
    n_int = len(int_mbrs_t)
    assert n_int >= 1 and len(int_parents) == n_int - 1
    assert len(widths) == n_int and starts.shape[0] == n_int
    _, B = q_t.shape
    _, L = leaf_mbrs_t.shape
    assert B % tb == 0 and L % tl == 0, (B, L, tb, tl)
    assert starts.shape[1] == L // tl, (starts.shape, L, tl)
    for m, w in zip(int_mbrs_t, widths):
        assert m.shape[1] % w == 0, (m.shape, w)
    grid = (B // tb, L // tl)

    args = ([q_t.astype(jnp.float32)]
            + [m.astype(jnp.float32) for m in int_mbrs_t]
            + [p.astype(jnp.int32) for p in int_parents]
            + [leaf_mbrs_t.astype(jnp.float32),
               leaf_parent.astype(jnp.int32)])

    return pl.pallas_call(
        _make_sliced_kernel(n_int, widths, tb, tl, tpu_form=tpu_form,
                            sub_tl=sub_tl),
        grid_spec=_sliced_grid_spec(
            n_int, widths, tb, tl, grid,
            pl.BlockSpec((tb, tl), lambda i, j, s: (i, j))),
        out_shape=jax.ShapeDtypeStruct((B, L), jnp.bool_),
        interpret=interpret,
        name="traverse_fused_sliced",
    )(starts.astype(jnp.int32), *args)


@functools.partial(jax.jit,
                   static_argnames=("k", "widths", "tb", "tl", "sub_tl",
                                    "kc", "interpret", "tpu_form"))
def traverse_compact_sliced_t(starts: jnp.ndarray,
                              q_t: jnp.ndarray,
                              int_mbrs_t: Sequence[jnp.ndarray],
                              int_parents: Sequence[jnp.ndarray],
                              leaf_mbrs_t: jnp.ndarray,
                              leaf_parent: jnp.ndarray, *,
                              k: int, widths: tuple, tb: int = DEF_TB,
                              tl: int = DEF_TL, sub_tl: int = SUB_TL,
                              kc: int = COMPACT_KC,
                              interpret: bool = False,
                              tpu_form: bool | None = None
                              ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Ancestor-sliced traversal + compaction → ``(leaf_idx [B, KP] i32,
    count [B, 1] i32)``; operand/slot contracts as ``traverse_compact_t``
    (KP lane-rounded in TPU form, exactly ``k`` interp), windows as
    ``traverse_fused_sliced_t``.
    """
    if tpu_form is None:
        tpu_form = not interpret
    n_int = len(int_mbrs_t)
    assert n_int >= 1 and len(int_parents) == n_int - 1
    assert len(widths) == n_int and starts.shape[0] == n_int
    _, B = q_t.shape
    _, L = leaf_mbrs_t.shape
    assert B % tb == 0 and L % tl == 0, (B, L, tb, tl)
    assert starts.shape[1] == L // tl, (starts.shape, L, tl)
    kp = (k + LANE - 1) // LANE * LANE if tpu_form else k
    assert kp % kc == 0 or not tpu_form, (kp, kc)
    grid = (B // tb, L // tl)

    args = ([q_t.astype(jnp.float32)]
            + [m.astype(jnp.float32) for m in int_mbrs_t]
            + [p.astype(jnp.int32) for p in int_parents]
            + [leaf_mbrs_t.astype(jnp.float32),
               leaf_parent.astype(jnp.int32)])

    return pl.pallas_call(
        _make_sliced_compact_kernel(n_int, widths, tb, tl, kp,
                                    tpu_form=tpu_form, sub_tl=sub_tl,
                                    kc=kc),
        grid_spec=_sliced_grid_spec(
            n_int, widths, tb, tl, grid,
            [pl.BlockSpec((tb, kp), lambda i, j, s: (i, 0)),
             pl.BlockSpec((tb, 1), lambda i, j, s: (i, 0))]),
        out_shape=[jax.ShapeDtypeStruct((B, kp), jnp.int32),
                   jax.ShapeDtypeStruct((B, 1), jnp.int32)],
        interpret=interpret,
        name="traverse_compact_sliced",
    )(starts.astype(jnp.int32), *args)
