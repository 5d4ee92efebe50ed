"""Shared compaction-epilogue helpers for the fused Pallas kernels.

Three kernels end in the same move: a ``[TB, TL]`` boolean tile of "this
(query, column) pair is selected" must become a compact per-row slot table
``[TB, KP]`` of the selected columns in column order, plus a running
per-row count — without ever materializing the mask outside VMEM. The
fused R-path traversal (``traverse_fused``), the fused MLP prediction
(``mlp_infer``) and the delta-buffer probe (``delta_probe``) all import
these two epilogues; this module is the single home so the rank scheme
cannot drift between kernels (it used to live in ``traverse_fused`` with
the other two importing it across kernel modules).

Both forms realize ``compact_mask_counted``'s cumsum-rank scheme per
tile, carrying the running per-row total across tiles in the *revisited*
output blocks (both output blocks map to ``(i, 0)`` in every caller, so
they stay VMEM-resident across the column-tile sweep):

* ``compact_epilogue_tpu`` — the Mosaic-friendly hardware form: the
  prefix count as an MXU matmul, then one rank-equality compare +
  lane-sum per in-tile rank (ranks are unique per row, so sum == select)
  in a loop bounded by the tile's largest row count;
* ``compact_epilogue_interp`` — the interpret-mode form: value-level
  rowwise binary search of each slot's rank over the tile's inclusive
  prefix count (interpret mode functionalizes ref-touching conds, so the
  scatter must be unconditional value ops).

The old ``_compact_epilogue_*`` names remain importable from
``traverse_fused``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def vmem_bytes(tb: int, tl: int, tpu_form: bool = True) -> int:
    """VMEM transient bytes of one epilogue call over a ``[tb, tl]``
    tile. TPU form: the ``[tl, tl]`` bf16 triangle, the prefix count, the
    rank table, the column ids and one compare in flight; interpret
    form: the prefix count."""
    if tpu_form:
        return tl * tl * 2 + 4 * tb * tl * 4
    return tb * tl * 4


def exclusive_prefix_count(mask):
    """``[TB, TL]`` bool → ``[TB, TL]`` i32 count of set lanes strictly
    left of each lane in its row.

    Mosaic has no lane ``cumsum``, so the prefix runs on the MXU: the 0/1
    tile times a strictly-upper-triangular ones matrix built in VMEM from
    an iota compare. Exact: 0/1 operands are exact in bf16 and the f32
    accumulator holds every count below 2^24.
    """
    tl_ = mask.shape[1]
    upper = (jax.lax.broadcasted_iota(jnp.int32, (tl_, tl_), 0)
             < jax.lax.broadcasted_iota(jnp.int32, (tl_, tl_), 1))
    return jax.lax.dot(mask.astype(jnp.bfloat16), upper.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32).astype(jnp.int32)


def compact_epilogue_tpu(mask, col, idx_ref, cnt_ref, kp: int, kc: int):
    """TPU-form cumsum-rank compaction epilogue over one ``[TB, TL]`` tile.

    Ranks the tile's set lanes by exclusive prefix count (on the MXU, see
    ``exclusive_prefix_count``) and writes the ``col`` value of the lane of
    in-tile rank ``t`` to slot ``base + t`` of ``idx_ref`` (the revisited
    ``[TB, KP]`` slot block), ``base`` being the running per-row total in
    ``cnt_ref`` (the revisited ``[TB, 1]`` block). A loop runs once per
    in-tile rank, up to the tile's largest row count (and never past the
    last slot); each step is a rank-equality compare + lane-sum (ranks are
    unique per row, so sum == select) and a slot-equality select, all
    dense 2-D work Mosaic vectorizes, unrolled ``kc`` ranks per step.
    Callers guard the whole epilogue on tile liveness; shared by
    ``traverse_compact_t``, ``mlp_infer`` and ``delta_probe``. ``mask`` is
    the tile's set-lane mask, ``col`` the value to scatter (global leaf
    ids / buffer slot ids).
    """
    tb_ = mask.shape[0]
    base = cnt_ref[:, :]                                  # [TB, 1]
    n_tile = jnp.sum(mask.astype(jnp.int32), axis=1, keepdims=True)
    cnt_ref[:, :] = base + n_tile
    sl = jnp.where(mask, exclusive_prefix_count(mask), -1)  # -1 never hits
    slot = jax.lax.broadcasted_iota(jnp.int32, (tb_, kp), 1)
    n_rank = jnp.maximum(
        jnp.minimum(jnp.max(n_tile), kp - jnp.min(base)), 0)

    def step(i, acc):
        for u in range(kc):
            t = i * kc + u
            v = jnp.sum(jnp.where(sl == t, col, 0), axis=1, keepdims=True)
            acc = acc + jnp.where(slot == base + t, v, 0)
        return acc

    idx_ref[:, :] = jax.lax.fori_loop(0, (n_rank + kc - 1) // kc, step,
                                      idx_ref[:, :])


def compact_epilogue_interp(mask, j, tl: int, kp: int, idx_ref, cnt_ref):
    """Interpret-form compaction epilogue: value-level rowwise binary
    search of each slot's rank over the tile's inclusive prefix count
    (``compact_mask_counted``'s scheme), with the running rank base carried
    across tiles in the revisited output blocks. Output blocks are
    uninitialized before the first visit — the ``j == 0`` reads are masked
    at value level (no ref-touching cond). Shared by ``traverse_compact_t``,
    ``mlp_infer`` and ``delta_probe``.
    """
    tb_ = mask.shape[0]
    m = mask.astype(jnp.int32)
    prev_idx = jnp.where(j == 0, 0, idx_ref[:, :])
    prev_cnt = jnp.where(j == 0, 0, cnt_ref[:, :])
    base = prev_cnt[:, 0]                        # [TB]
    cs = jnp.cumsum(m, axis=1)                   # [TB, TL]
    # slot t - 1 holds the column whose inclusive prefix count first
    # reaches t - base; slots filled by earlier tiles keep their value,
    # later slots wait for a later tile.
    targets = 1 + jax.lax.broadcasted_iota(jnp.int32, (tb_, kp), 1)
    rel = targets - base[:, None]                # [TB, KP]
    pos = jax.vmap(lambda c, t: jnp.searchsorted(
        c, t, side="left"))(cs, rel)
    newly = (rel >= 1) & (rel <= cs[:, -1][:, None])
    idx_ref[:, :] = jnp.where(
        newly, j * tl + pos.astype(jnp.int32), prev_idx)
    cnt_ref[:, :] = (base + cs[:, -1])[:, None]
