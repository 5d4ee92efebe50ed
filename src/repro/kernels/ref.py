"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

Each function has identical semantics to its kernel twin; kernel tests sweep
shapes/dtypes and ``assert_allclose`` against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def mbr_intersect(queries: jnp.ndarray, mbrs: jnp.ndarray) -> jnp.ndarray:
    """[B, 4] × [N, 4] → [B, N] bool (closed-rectangle intersection)."""
    q = queries[:, None, :].astype(jnp.float32)
    m = mbrs[None, :, :].astype(jnp.float32)
    return (
        (q[..., 0] <= m[..., 2]) & (m[..., 0] <= q[..., 2])
        & (q[..., 1] <= m[..., 3]) & (m[..., 1] <= q[..., 3])
    )


def traverse_fused(queries: jnp.ndarray, level_mbrs, level_parents
                   ) -> jnp.ndarray:
    """Level-synchronous traversal ground truth: [B, 4] → [B, L] bool.

    ``level_mbrs``: one [N_l, 4] per level, root first (leaf level last);
    ``level_parents``: matching [N_l] i32 (entry 0 unused — the root has no
    parent). A leaf is visited iff every ancestor MBR and its own intersect
    the query; identical to ``core.traversal.visited_leaf_mask``.
    """
    mask = mbr_intersect(queries, level_mbrs[0])
    for mbrs, parent in zip(level_mbrs[1:], level_parents[1:]):
        mask = mask[:, parent] & mbr_intersect(queries, mbrs)
    return mask


def traverse_fused_sliced(queries: jnp.ndarray, level_mbrs, level_parents,
                          starts, widths, tl: int) -> jnp.ndarray:
    """Windowed twin of ``traverse_fused`` — ground truth for the
    ancestor-sliced kernels' window semantics: [B, 4] → [B, L] bool.

    Per leaf tile the walk sees only each internal level's
    ``widths[l]``-wide window at element offset ``starts[l, t] *
    widths[l]`` (the ``AncestorTable`` contract); parent indices are
    rebased window-relative and out-of-window ones masked dead. With a
    correctly built table this equals ``traverse_fused`` exactly — that
    equality is what the tests assert.
    """
    never = jnp.array([1.0, 1.0, 0.0, 0.0], jnp.float32)

    def window(mbrs, parent, s, w):
        n = mbrs.shape[0]
        pad = max(0, s + w - n)
        if pad:
            mbrs = jnp.concatenate(
                [mbrs.astype(jnp.float32),
                 jnp.broadcast_to(never, (pad, 4))])
            parent = jnp.concatenate(
                [parent, jnp.zeros((pad,), parent.dtype)])
        return mbrs[s:s + w], parent[s:s + w]

    n_int = len(level_mbrs) - 1
    L = level_mbrs[-1].shape[0]
    starts = jnp.asarray(starts)
    outs = []
    for t in range(-(-L // tl)):
        mask = None
        prev_s = 0
        for l in range(n_int):
            s = int(starts[l, t]) * widths[l]
            wm, wp = window(jnp.asarray(level_mbrs[l]),
                            jnp.asarray(level_parents[l]), s, widths[l])
            hit = mbr_intersect(queries, wm)
            if l == 0:
                mask = hit
            else:
                rel = wp - prev_s
                ok = (rel >= 0) & (rel < widths[l - 1])
                mask = (mask[:, jnp.clip(rel, 0, widths[l - 1] - 1)]
                        & ok[None, :] & hit)
            prev_s = s
        lm = jnp.asarray(level_mbrs[-1])[t * tl:(t + 1) * tl]
        lp = jnp.asarray(level_parents[-1])[t * tl:(t + 1) * tl]
        rel = lp - prev_s
        ok = (rel >= 0) & (rel < widths[-1])
        outs.append(mask[:, jnp.clip(rel, 0, widths[-1] - 1)]
                    & ok[None, :] & mbr_intersect(queries, lm))
    return jnp.concatenate(outs, axis=1)


def spatial_key(cxy: jnp.ndarray, curve: str = "hilbert",
                order: int = 15) -> jnp.ndarray:
    """Space-filling-curve keys: ``cxy`` [B, 2] f32 in [0, 1) → [B] i32.

    Ground truth for ``kernels.spatial_key``: quantize each normalized
    center to ``order``-bit integer coordinates, then either interleave
    bits (``morton``, x high) or run the classic xy→d Hilbert walk with
    quadrant rotations as selects (``hilbert``).
    """
    n = jnp.int32(1 << order)
    q = jnp.clip((cxy.astype(jnp.float32) * n.astype(jnp.float32))
                 .astype(jnp.int32), 0, n - 1)
    x, y = q[:, 0], q[:, 1]
    if curve == "morton":
        key = jnp.zeros_like(x)
        for i in range(order):
            key = key | (((x >> i) & 1) << (2 * i + 1)) | (((y >> i) & 1)
                                                           << (2 * i))
        return key
    d = jnp.zeros_like(x)
    for i in range(order - 1, -1, -1):
        s = 1 << i
        rx = (x >> i) & 1
        ry = (y >> i) & 1
        d = d + s * s * ((3 * rx) ^ ry)
        swap = ry == 0
        flip = swap & (rx == 1)
        fx = jnp.where(flip, s - 1 - x, x)
        fy = jnp.where(flip, s - 1 - y, y)
        x = jnp.where(swap, fy, fx)
        y = jnp.where(swap, fx, fy)
    return d


def mlp_predict_scores(x: jnp.ndarray, cell_ids: jnp.ndarray,
                       slot_ok: jnp.ndarray, w1: jnp.ndarray,
                       b1: jnp.ndarray, w2: jnp.ndarray, b2: jnp.ndarray,
                       label_map: jnp.ndarray, lmask: jnp.ndarray,
                       n_leaves: int) -> jnp.ndarray:
    """Dense AI-path prediction ground truth: [B, F] → scores [B, n_leaves].

    Gathered per-cell MLP forward (``cell_logits_for``'s contraction
    order), sigmoid, and the ``global_scores`` max-union scatter over the
    full leaf axis — the exact pipeline the fused kernel collapses.
    """
    B, S = cell_ids.shape
    w1g = w1[cell_ids]                              # [B, S, F, H]
    b1g = b1[cell_ids]
    w2g = w2[cell_ids]                              # [B, S, H, Cl]
    b2g = b2[cell_ids]
    hi = jax.lax.Precision.HIGHEST          # the bank's f32 contract
    h = jnp.maximum(
        jnp.einsum("bf,bsfh->bsh", x.astype(jnp.float32), w1g,
                   precision=hi) + b1g, 0.0)
    probs = jax.nn.sigmoid(
        jnp.einsum("bsh,bshl->bsl", h, w2g, precision=hi) + b2g)
    lm = label_map[cell_ids]                        # [B, S, Cl]
    ok = slot_ok[:, :, None] & lmask[cell_ids]
    tgt = jnp.where(ok, lm, n_leaves)               # park invalid at L
    Cl = lm.shape[-1]
    flat_t = tgt.reshape(B, S * Cl)
    flat_p = jnp.where(ok, probs, 0.0).reshape(B, S * Cl)
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    out = jnp.zeros((B, n_leaves + 1), probs.dtype)
    out = out.at[rows, flat_t].max(flat_p)
    return out[:, :n_leaves]


def mlp_predict_compact(x: jnp.ndarray, cell_ids: jnp.ndarray,
                        slot_ok: jnp.ndarray, w1: jnp.ndarray,
                        b1: jnp.ndarray, w2: jnp.ndarray, b2: jnp.ndarray,
                        label_map: jnp.ndarray, lmask: jnp.ndarray, *,
                        n_leaves: int, k: int, threshold: float
                        ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Ground truth for ``kernels.mlp_infer``: dense scores → threshold →
    ``compact_mask_counted``. Returns ``(leaf_idx [B, k], valid, count)``.
    """
    from repro.core.traversal import compact_mask_counted
    scores = mlp_predict_scores(x, cell_ids, slot_ok, w1, b1, w2, b2,
                                label_map, lmask, n_leaves)
    return compact_mask_counted(scores > threshold, k)


def delta_contains(queries: jnp.ndarray, pts: jnp.ndarray) -> jnp.ndarray:
    """Dense delta-probe ground truth: [B, 4] × [cap, 2] → [B, cap] bool.

    Closed-rectangle containment (the shared ``geometry`` predicate, so
    the convention cannot drift from the refine path's) of each buffer
    point in each query rect; +inf (unstaged/padding) points never hit —
    the same convention the kernel's tile test relies on.
    """
    from repro.core import geometry as geo
    return geo.jnp_contains_point(
        queries.astype(jnp.float32)[:, None, :],
        pts.astype(jnp.float32)[None, :, :])


def delta_probe(queries: jnp.ndarray, pts: jnp.ndarray, k: int
                ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Ground truth for ``kernels.delta_probe``: dense containment mask →
    ``compact_mask_counted``. Returns ``(slot_idx [B, k], valid, count)``
    with slots in buffer (= insertion) order.
    """
    from repro.core.traversal import compact_mask_counted
    return compact_mask_counted(delta_contains(queries, pts), k)


def leaf_refine(queries: jnp.ndarray, entries: jnp.ndarray,
                leaf_idx: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """queries [B,4], entries [L,2,M], leaf_idx [B,K], valid [B,K]
    → [B,K,M]."""
    g = entries[leaf_idx].astype(jnp.float32)   # [B, K, 2, M]
    gx, gy = g[:, :, 0], g[:, :, 1]
    q = queries.astype(jnp.float32)
    x0, y0, x1, y1 = (q[:, i][:, None, None] for i in range(4))
    ok = (gx >= x0) & (gx <= x1) & (gy >= y0) & (gy <= y1)
    return ok & (valid[:, :, None] > 0)


def knn_browse(centers: jnp.ndarray, entries: jnp.ndarray,
               leaf_idx: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """centers [B,3] (cx,cy,r²), entries [L,2,M], leaf_idx/valid [B,K]
    → d2 [B,K,M] f32 (+inf outside the radius / invalid slots).

    Ground truth for ``kernels.knn_browse``: squared Euclidean distance
    from each gathered leaf entry to the query center, masked to +inf
    when the entry lies outside the probed radius or the slot is
    invalid. +inf-padded entries yield +inf distance by arithmetic —
    the identical term order (dx·dx + dy·dy) keeps the kernel twin
    bit-exact.
    """
    g = entries[leaf_idx].astype(jnp.float32)   # [B, K, 2, M]
    gx, gy = g[:, :, 0], g[:, :, 1]
    q = centers.astype(jnp.float32)
    cx = q[:, 0][:, None, None]
    cy = q[:, 1][:, None, None]
    r2 = q[:, 2][:, None, None]
    dx = gx - cx
    dy = gy - cy
    d2 = dx * dx + dy * dy
    ok = (d2 <= r2) & (valid[:, :, None] > 0)
    return jnp.where(ok, d2, jnp.inf)


def forest_infer(sel: jnp.ndarray, thresh: jnp.ndarray,
                 tables: jnp.ndarray) -> jnp.ndarray:
    """sel [B,T,D], thresh [T,D], tables [T,2^D,C] → scores [B,C]."""
    B, T, D = sel.shape
    bits = (sel.astype(jnp.float32) > thresh[None].astype(jnp.float32))
    powers = 2 ** jnp.arange(D - 1, -1, -1, dtype=jnp.int32)
    leaf = jnp.sum(bits.astype(jnp.int32) * powers[None, None, :], axis=-1)
    # [B, T] leaf ids → gather votes per tree, sum over trees
    votes = jax.vmap(lambda tb, lf: tb[lf], in_axes=(0, 1),
                     out_axes=1)(tables.astype(jnp.float32), leaf)  # [B,T,C]
    return jnp.sum(votes, axis=1)


def forest_infer_percell(sel: jnp.ndarray, thresh: jnp.ndarray,
                         tables: jnp.ndarray) -> jnp.ndarray:
    """Per-tree votes (no cross-tree sum): sel [B,T,D] → [B, T, C]."""
    B, T, D = sel.shape
    bits = (sel.astype(jnp.float32) > thresh[None].astype(jnp.float32))
    powers = 2 ** jnp.arange(D - 1, -1, -1, dtype=jnp.int32)
    leaf = jnp.sum(bits.astype(jnp.int32) * powers[None, None, :], axis=-1)
    return jax.vmap(lambda tb, lf: tb[lf], in_axes=(0, 1),
                    out_axes=1)(tables.astype(jnp.float32), leaf)


def wkv6(r: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, w: jnp.ndarray,
         u: jnp.ndarray) -> jnp.ndarray:
    """Naive sequential RWKV-6 scan.

    r/k/w: [BH, T, dk], v: [BH, T, dv], u: [BH, dk] → y [BH, T, dv]
        y_t = r_t · (S_{t-1} + (u ⊙ k_t) v_tᵀ)
        S_t = diag(w_t) S_{t-1} + k_t v_tᵀ
    """
    r = r.astype(jnp.float32)
    k = k.astype(jnp.float32)
    v = v.astype(jnp.float32)
    w = w.astype(jnp.float32)
    u = u.astype(jnp.float32)
    BH, T, dk = r.shape
    dv = v.shape[-1]

    def one(rb, kb, vb, wb, ub):
        def step(S, inp):
            rt, kt, vt, wt = inp
            kv = kt[:, None] * vt[None, :]                # [dk, dv]
            yt = rt @ (S + ub[:, None] * kv)              # [dv]
            return wt[:, None] * S + kv, yt

        S0 = jnp.zeros((dk, dv), jnp.float32)
        _, yb = jax.lax.scan(step, S0, (rb, kb, vb, wb))
        return yb

    return jax.vmap(one)(r, k, v, w, u)
