"""Stacked multi-label MLP experts — the TPU-native cell classifier.

One tiny MLP per non-empty grid cell, all cells stacked into single tensors
``[C, ...]`` so that (a) expert-parallel sharding over the ``model`` mesh
axis is a plain array partition and (b) inference over all local cells is a
dense einsum on the MXU — no per-query parameter gathers.

The paper intentionally **overfits** its per-cell models (§III-B); we train
with full-batch AdamW until the training workload is exactly fit (predicted
set == true set under the 0.5 threshold) or an epoch cap is hit. Residual
misfit is absorbed by the hybrid fallback rule, exactly as in the paper.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.celldata import CellDataset

# Every bank matmul runs at full f32 precision — training, the dense
# oracle and the fused kernel then agree on the thresholded labels (a
# TPU's default f32 matmul rounds operands to bf16).
F32 = jax.lax.Precision.HIGHEST


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class MLPBank:
    w1: jnp.ndarray         # [C, F, H]
    b1: jnp.ndarray         # [C, H]
    w2: jnp.ndarray         # [C, H, Cl]
    b2: jnp.ndarray         # [C, Cl]
    mu: jnp.ndarray         # [F] feature normalizer
    sd: jnp.ndarray         # [F]
    label_map: jnp.ndarray  # [C, Cl] i32 (-1 pad)
    lmask: jnp.ndarray      # [C, Cl] bool

    @property
    def n_cells(self) -> int:
        return self.w1.shape[0]

    @property
    def n_local_labels(self) -> int:
        return self.w2.shape[-1]

    def byte_size(self) -> int:
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in
                   (self.w1, self.b1, self.w2, self.b2, self.label_map))


def init_bank(ds: CellDataset, hidden: int = 64, seed: int = 0) -> MLPBank:
    C, _, F = ds.feats.shape
    Cl = ds.max_labels
    rng = np.random.default_rng(seed)
    flat = ds.feats[ds.qmask]
    mu = flat.mean(axis=0) if flat.size else np.zeros((F,), np.float32)
    sd = flat.std(axis=0) + 1e-6 if flat.size else np.ones((F,), np.float32)
    return MLPBank(
        w1=jnp.asarray(rng.normal(0, 1.0 / np.sqrt(F), (C, F, hidden)),
                       jnp.float32),
        b1=jnp.zeros((C, hidden), jnp.float32),
        w2=jnp.asarray(rng.normal(0, 1.0 / np.sqrt(hidden), (C, hidden, Cl)),
                       jnp.float32),
        b2=jnp.zeros((C, Cl), jnp.float32),
        mu=jnp.asarray(mu, jnp.float32),
        sd=jnp.asarray(sd, jnp.float32),
        label_map=jnp.asarray(ds.label_map),
        lmask=jnp.asarray(ds.lmask),
    )


def cell_logits(bank: MLPBank, feats: jnp.ndarray) -> jnp.ndarray:
    """Dense all-cells forward: feats [..., B, F] → logits [..., B, C, Cl]."""
    x = (feats - bank.mu) / bank.sd
    h = jnp.maximum(
        jnp.einsum("...bf,cfh->...bch", x, bank.w1, precision=F32) + bank.b1,
        0.0)
    return jnp.einsum("...bch,chl->...bcl", h, bank.w2,
                      precision=F32) + bank.b2


def cell_logits_for(bank: MLPBank, feats: jnp.ndarray,
                    cell_ids: jnp.ndarray) -> jnp.ndarray:
    """Gathered forward for (query, cell-slot) pairs.

    feats [B, F], cell_ids [B, S] → logits [B, S, Cl]. Used by the
    single-device path where B·S ≪ B·C.
    """
    x = (feats - bank.mu) / bank.sd
    w1 = bank.w1[cell_ids]                    # [B, S, F, H]
    b1 = bank.b1[cell_ids]
    w2 = bank.w2[cell_ids]                    # [B, S, H, Cl]
    b2 = bank.b2[cell_ids]
    h = jnp.maximum(jnp.einsum("bf,bsfh->bsh", x, w1, precision=F32) + b1,
                    0.0)
    return jnp.einsum("bsh,bshl->bsl", h, w2, precision=F32) + b2


def global_scores(bank: MLPBank, probs: jnp.ndarray, slot_valid: jnp.ndarray,
                  cell_ids: jnp.ndarray, n_leaves: int) -> jnp.ndarray:
    """Union of per-cell predictions (paper: union of model outputs).

    probs [B, S, Cl] sigmoid scores, slot_valid [B, S], cell_ids [B, S]
    → [B, n_leaves] max-combined scores over the models a query overlaps.
    """
    B, S, Cl = probs.shape
    lm = bank.label_map[cell_ids]                         # [B, S, Cl]
    ok = slot_valid[:, :, None] & bank.lmask[cell_ids]
    tgt = jnp.where(ok, lm, n_leaves)                     # park invalid at L
    flat_t = tgt.reshape(B, S * Cl)
    flat_p = jnp.where(ok, probs, 0.0).reshape(B, S * Cl)
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    out = jnp.zeros((B, n_leaves + 1), probs.dtype)
    out = out.at[rows, flat_t].max(flat_p)
    return out[:, :n_leaves]


# ---------------------------------------------------------------------------
# training — cell-granular by construction (the online-refit contract)
#
# Every coupling between cells is removed so that training a *subset* of
# cells reproduces, bit for bit, what training the full bank would have
# given those cells (``build.refit_cells ≡ build.fit_airtree`` on the
# retrained cells — property-tested):
#   * init: each cell's weights come from its own fold-in rng stream
#     ``default_rng((seed, cell_id, tensor))`` — independent of which
#     other cells are in the batch;
#   * normalizer: ``mu``/``sd`` derive from the grid geometry, not from
#     the pooled workload features;
#   * loss: per-cell mean summed over cells, so each cell's gradient is
#     exactly what it would be trained alone (the old global-mask mean
#     rescaled every cell's gradient by the other cells' mask counts);
#   * early stop: per-cell freeze — a cell that reaches exact fit at a
#     ``check_every`` boundary stops updating (params *and* Adam state
#     held), so its final weights do not depend on how long the other
#     cells keep training. Adam is elementwise, so per-cell trajectories
#     are independent given the decoupled gradients.
# ---------------------------------------------------------------------------

def grid_norm(grid) -> tuple[np.ndarray, np.ndarray]:
    """Feature normalizer derived from the grid bbox alone: rect corners
    centered on the bbox center and scaled by its half-extents. Workload-
    independent, so a cell's normalized features — and hence its whole
    training trajectory — never change when other cells' queries do."""
    b = np.asarray(grid.bbox, np.float32)
    cx, cy = (b[0] + b[2]) / 2, (b[1] + b[3]) / 2
    hx = max((b[2] - b[0]) / 2, 1e-6)
    hy = max((b[3] - b[1]) / 2, 1e-6)
    return (np.array([cx, cy, cx, cy], np.float32),
            np.array([hx, hy, hx, hy], np.float32))


def init_cell_params(cell_ids: np.ndarray, n_feats: int, hidden: int,
                     n_labels: int, seed: int = 0) -> dict:
    """Per-cell fold-in init: cell ``c``'s weights come from rng streams
    keyed ``(seed, c, tensor)`` — identical whether ``c`` is initialized
    alone or inside the full bank."""
    w1, w2 = [], []
    for c in np.asarray(cell_ids, np.int64):
        r1 = np.random.default_rng((seed, int(c), 0))
        r2 = np.random.default_rng((seed, int(c), 1))
        w1.append(r1.normal(0, 1.0 / np.sqrt(n_feats),
                            (n_feats, hidden)).astype(np.float32))
        w2.append(r2.normal(0, 1.0 / np.sqrt(hidden),
                            (hidden, n_labels)).astype(np.float32))
    C = len(w1)
    return {"w1": jnp.asarray(np.stack(w1)),
            "b1": jnp.zeros((C, hidden), jnp.float32),
            "w2": jnp.asarray(np.stack(w2)),
            "b2": jnp.zeros((C, n_labels), jnp.float32)}


def _cell_logits_p(params: dict, feats, mu, sd) -> jnp.ndarray:
    x = (feats - mu) / sd
    h = jnp.maximum(jnp.einsum("cqf,cfh->cqh", x, params["w1"], precision=F32)
                    + params["b1"][:, None, :], 0.0)
    return jnp.einsum("cqh,chl->cql", h, params["w2"], precision=F32) \
        + params["b2"][:, None, :]


def _bce_cells(params: dict, feats, labels, qmask, lmask, live, mu, sd
               ) -> jnp.ndarray:
    """Decoupled loss: per-cell masked mean, summed over live cells."""
    z = jnp.clip(_cell_logits_p(params, feats, mu, sd), -30, 30)
    ce = jnp.maximum(z, 0) - z * labels + jnp.log1p(jnp.exp(-jnp.abs(z)))
    # positive-class upweighting: multi-hot targets are sparse
    w = jnp.where(labels > 0, 4.0, 1.0)
    m = (qmask[:, :, None] & lmask[:, None, :]).astype(jnp.float32)
    per = jnp.sum(ce * w * m, axis=(1, 2)) \
        / jnp.maximum(jnp.sum(m, axis=(1, 2)), 1.0)
    return jnp.sum(per * live)


def cell_fit_fractions(params: dict, feats, labels, qmask, lmask, mu, sd,
                       threshold: float = 0.5) -> jnp.ndarray:
    """[C] per-cell fraction of valid training queries whose predicted set
    equals the true set. Cells with no valid query are vacuously 1.0."""
    logits = _cell_logits_p(params, feats, mu, sd)
    pred = (jax.nn.sigmoid(logits) > threshold) & lmask[:, None, :]
    ok = jnp.all(pred == (labels > 0.5), axis=-1) | ~qmask
    n = jnp.sum(qmask, axis=1)
    return jnp.where(n > 0,
                     jnp.sum(ok & qmask, axis=1) / jnp.maximum(n, 1), 1.0)


@jax.jit
def _update_cells(params, opt_m, opt_v, t, live, feats, labels, qmask,
                  lmask, mu, sd, lr, weight_decay):
    loss, g = jax.value_and_grad(_bce_cells)(
        params, feats, labels, qmask, lmask, live, mu, sd)
    b1c, b2c = 0.9, 0.999
    opt_m2 = jax.tree.map(lambda m_, g_: b1c * m_ + (1 - b1c) * g_,
                          opt_m, g)
    opt_v2 = jax.tree.map(lambda v_, g_: b2c * v_ + (1 - b2c) * g_ ** 2,
                          opt_v, g)
    mhat = jax.tree.map(lambda m_: m_ / (1 - b1c ** t), opt_m2)
    vhat = jax.tree.map(lambda v_: v_ / (1 - b2c ** t), opt_v2)
    new = jax.tree.map(
        lambda p, mh, vh: p - lr * (mh / (jnp.sqrt(vh) + 1e-8)
                                    + weight_decay * p),
        params, mhat, vhat)

    def keep_live(new_a, old_a):
        lv = live.astype(bool).reshape((-1,) + (1,) * (new_a.ndim - 1))
        return jnp.where(lv, new_a, old_a)

    # frozen cells hold params AND optimizer state: their trajectory ended
    # at their own freeze epoch, independent of the loop's total length
    params = jax.tree.map(keep_live, new, params)
    opt_m = jax.tree.map(keep_live, opt_m2, opt_m)
    opt_v = jax.tree.map(keep_live, opt_v2, opt_v)
    return params, opt_m, opt_v, loss


_fit_cells_j = jax.jit(cell_fit_fractions)


@dataclasses.dataclass
class TrainReport:
    epochs: int
    final_loss: float
    exact_fit: float


def train_cells(feats: np.ndarray, labels: np.ndarray, qmask: np.ndarray,
                lmask: np.ndarray, mu: np.ndarray, sd: np.ndarray,
                cell_ids: np.ndarray, *, hidden: int = 64, lr: float = 3e-3,
                weight_decay: float = 0.0, max_epochs: int = 3000,
                check_every: int = 200, target_fit: float = 1.0,
                seed: int = 0) -> Tuple[dict, TrainReport]:
    """Train a stack of per-cell experts over ``[C, Qp, ...]`` data rows.

    ``cell_ids`` names each row's *global* cell id — the fold-in init key —
    so a sub-stack of changed cells trains bit-identically to the same
    cells inside the full bank (see the module docstring). Returns the
    trained ``{w1, b1, w2, b2}`` rows and a ``TrainReport``.

    ``target_fit < 1.0`` keeps the legacy aggregate early stop; note that
    stopping before every cell froze makes the still-live cells' params
    depend on the co-trained set, so the refit-equivalence guarantee only
    holds at the default ``target_fit=1.0`` (where the stop condition —
    every cell exactly fit — is itself per-cell).
    """
    Cl = labels.shape[-1]
    params = init_cell_params(cell_ids, feats.shape[-1], hidden, Cl,
                              seed=seed)
    feats_j = jnp.asarray(feats, jnp.float32)
    labels_j = jnp.asarray(labels, jnp.float32)
    qmask_j = jnp.asarray(qmask)
    lmask_j = jnp.asarray(lmask)
    mu_j = jnp.asarray(mu, jnp.float32)
    sd_j = jnp.asarray(sd, jnp.float32)
    opt_m = jax.tree.map(jnp.zeros_like, params)
    opt_v = jax.tree.map(jnp.zeros_like, params)
    live = jnp.ones((feats.shape[0],), jnp.float32)

    loss = np.inf
    fit = 0.0
    epoch = 0
    for epoch in range(1, max_epochs + 1):
        params, opt_m, opt_v, loss = _update_cells(
            params, opt_m, opt_v, jnp.float32(epoch), live, feats_j,
            labels_j, qmask_j, lmask_j, mu_j, sd_j, jnp.float32(lr),
            jnp.float32(weight_decay))
        if epoch % check_every == 0 or epoch == max_epochs:
            fr = _fit_cells_j(params, feats_j, labels_j, qmask_j, lmask_j,
                              mu_j, sd_j)
            live = jnp.where(fr >= 1.0, 0.0, live)
            nq = np.asarray(jnp.sum(qmask_j, axis=1))
            frh = np.asarray(fr)
            fit = float((frh * nq).sum() / max(nq.sum(), 1))
            if not bool(np.any(np.asarray(live) > 0)) or fit >= target_fit:
                break
    return params, TrainReport(epochs=epoch, final_loss=float(loss),
                               exact_fit=float(fit))


def train_bank(ds: CellDataset, *, hidden: int = 64, lr: float = 3e-3,
               weight_decay: float = 0.0, max_epochs: int = 3000,
               check_every: int = 200, target_fit: float = 1.0,
               seed: int = 0) -> Tuple[MLPBank, TrainReport]:
    """Full-bank fit: ``train_cells`` over every grid cell + assembly.

    Kept as the one-shot entry point; the incremental path
    (``build.refit_cells``) runs the identical per-cell pipeline on a
    row subset and splices the results into the live bank."""
    C = ds.feats.shape[0]
    mu, sd = grid_norm(ds.grid)
    params, rep = train_cells(
        ds.feats, ds.labels, ds.qmask, ds.lmask, mu, sd,
        np.arange(C, dtype=np.int64), hidden=hidden, lr=lr,
        weight_decay=weight_decay, max_epochs=max_epochs,
        check_every=check_every, target_fit=target_fit, seed=seed)
    bank = MLPBank(
        w1=params["w1"], b1=params["b1"], w2=params["w2"], b2=params["b2"],
        mu=jnp.asarray(mu), sd=jnp.asarray(sd),
        label_map=jnp.asarray(ds.label_map), lmask=jnp.asarray(ds.lmask))
    return bank, rep


def exact_fit_fraction(bank: MLPBank, feats, labels, qmask, lmask,
                       threshold: float = 0.5) -> jnp.ndarray:
    """Fraction of (valid) training queries whose predicted set == true set."""
    params = {"w1": bank.w1, "b1": bank.b1, "w2": bank.w2, "b2": bank.b2}
    fr = cell_fit_fractions(params, feats, labels, qmask, lmask, bank.mu,
                            bank.sd, threshold)
    n = jnp.sum(qmask, axis=1)
    return jnp.sum(fr * n) / jnp.maximum(jnp.sum(n), 1)
