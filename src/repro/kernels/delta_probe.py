"""Pallas TPU kernel: probe the device-side insert delta store.

The freshness subsystem (``repro.core.delta``) absorbs dynamic inserts
into a fixed-capacity append-only point buffer instead of mutating the
served tree. Every query batch must then check that buffer too — points
staged since the last repack are invisible to both the R and AI paths.
This kernel is that check, kept to the serving contract PR 5 settled on:
the only HBM output is a compact ``[B, K]`` slot table of hit positions
plus per-row counts — the dense ``[B, cap]`` query×buffer containment
mask lives tile-by-tile in VMEM and never reaches the serving HLO.

Input layout (planar, like the traversal kernels): queries as ``[4, B]``
f32 rows and buffer points as ``[2, cap]`` f32 rows. Unstaged/padding
slots hold +inf coordinates, so the closed-rectangle containment test
fails on them without the kernel ever consulting the staged count — the
wrapper's padding and the store's capacity padding share one convention.

The compaction epilogue is the shared cumsum-rank machinery of
``kernels.epilogue`` (slots in buffer order = insertion order): the TPU
form ranks by an MXU prefix count and scatters one in-tile rank per
loop step, the interpret form binary-searches slot ranks over the
tile's prefix count; both are bit-identical to
``compact_mask_counted(contains(q, pts), k)`` — the jnp oracle in
``ref.delta_probe``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.epilogue import (
    compact_epilogue_interp as _compact_epilogue_interp,
    compact_epilogue_tpu as _compact_epilogue_tpu,
    vmem_bytes as epilogue_vmem,
)
from repro.kernels.traverse_fused import (COMPACT_KC, LANE,
                                          tuned_tiles_for_key)

DEF_TB = 256    # query tile (sublane axis)
DEF_TN = 512    # buffer tile (lane axis, multiple of 128)


def tune_key_delta(B: int, cap: int, interp: bool) -> str:
    """Autotune-cache key for the delta-probe form space (same cache file
    as the traversal/mlp forms; see ``benchmarks/autotune``)."""
    return f"delta-{'interp' if interp else 'tpu'}:B{B}:N{cap}"


def tuned_tiles_delta(B: int, cap: int, interp: bool) -> dict:
    return tuned_tiles_for_key(tune_key_delta(B, cap, interp))


def vmem_estimate_delta(tb: int, tn: int, kp: int,
                        tpu_form: bool = True) -> int:
    """Rough VMEM working-set bytes for one probe tile.

    Query tile + buffer-point tile + containment mask + the compaction
    epilogue transient (form-dependent, exactly as
    ``vmem_estimate_compact``) + the revisited slot/count blocks.
    """
    est = 4 * tb * 4 + 2 * tn * 4                 # q tile, point tile
    est += tb * tn                                # containment mask
    est += epilogue_vmem(tb, tn, tpu_form)
    est += tb * (kp + 1) * 4                      # slot table + count
    return est


def _tile_contains(q, p):
    """q [4, TB] × p [2, TN] → [TB, TN] bool closed-rect containment.

    Padding points are +inf, so ``px <= qx1`` fails and they can never
    hit — the count input the host tracks stays out of the kernel.
    """
    px = p[0, :][None, :]
    py = p[1, :][None, :]
    return ((q[0, :][:, None] <= px) & (px <= q[2, :][:, None])
            & (q[1, :][:, None] <= py) & (py <= q[3, :][:, None]))


def _make_probe_kernel(tb: int, tn: int, kp: int, tpu_form: bool,
                       kc: int = COMPACT_KC):
    """Kernel body: containment over one buffer tile + compaction epilogue.

    Output blocks (slot table ``[TB, KP]`` + count ``[TB, 1]``) map to
    ``(i, 0)`` so they stay VMEM-resident across the buffer-tile sweep of
    a query tile, exactly as ``traverse_compact_t``'s epilogue blocks do.
    """

    def kernel(q_ref, p_ref, idx_ref, cnt_ref):
        q = q_ref[:, :]                               # [4, TB]
        j = pl.program_id(1)

        if tpu_form:
            col = j * tn + jax.lax.broadcasted_iota(jnp.int32, (tb, tn), 1)

            @pl.when(j == 0)
            def _init():
                idx_ref[:, :] = jnp.zeros((tb, kp), jnp.int32)
                cnt_ref[:, :] = jnp.zeros((tb, 1), jnp.int32)

            mask = _tile_contains(q, p_ref[:, :])
            # buffer tiles are mostly padding until the store fills — one
            # any() reduce buys skipping the whole chunked scatter
            @pl.when(jnp.any(mask))
            def _live_tile():
                _compact_epilogue_tpu(mask, col, idx_ref, cnt_ref, kp, kc)
        else:
            # the shared interpret epilogue handles the single-tile fold
            # too (j == 0 masks the uninitialized output reads), so there
            # is no special case — unlike traverse_fused there is no
            # traversal-liveness early exit to exploit here
            mask = _tile_contains(q, p_ref[:, :])
            _compact_epilogue_interp(mask, j, tn, kp, idx_ref, cnt_ref)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("k", "tb", "tn", "kc", "interpret",
                                    "tpu_form"))
def delta_probe_t(q_t: jnp.ndarray, pts_t: jnp.ndarray, *, k: int,
                  tb: int = DEF_TB, tn: int = DEF_TN, kc: int = COMPACT_KC,
                  interpret: bool = False, tpu_form: bool | None = None
                  ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Transposed-layout entry point.

    ``q_t`` [4, B] f32 query rects; ``pts_t`` [2, cap] f32 buffer points
    (+inf on unstaged/padding slots). B must be a multiple of ``tb`` and
    cap of ``tn`` (ops.py pads). Returns ``(slot_idx [B, KP] i32,
    count [B, 1] i32)`` with ``KP = k`` rounded up to ``LANE`` in the TPU
    form and exactly ``k`` in the interpret form: row ``b``'s first
    ``min(count[b], KP)`` slots hold the buffer positions of its hits in
    insertion order; slots past the count are 0. The ``[B, cap]``
    containment mask is never written.
    """
    if tpu_form is None:
        tpu_form = not interpret
    _, B = q_t.shape
    _, N = pts_t.shape
    assert B % tb == 0 and N % tn == 0, (B, N, tb, tn)
    kp = (k + LANE - 1) // LANE * LANE if tpu_form else k
    assert kp % kc == 0 or not tpu_form, (kp, kc)
    grid = (B // tb, N // tn)

    return pl.pallas_call(
        _make_probe_kernel(tb, tn, kp, tpu_form=tpu_form, kc=kc),
        grid=grid,
        in_specs=[pl.BlockSpec((4, tb), lambda i, j: (0, i)),
                  pl.BlockSpec((2, tn), lambda i, j: (0, j))],
        out_specs=[pl.BlockSpec((tb, kp), lambda i, j: (i, 0)),
                   pl.BlockSpec((tb, 1), lambda i, j: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, kp), jnp.int32),
                   jax.ShapeDtypeStruct((B, 1), jnp.int32)],
        interpret=interpret,
        name="delta_probe",
    )(q_t.astype(jnp.float32), pts_t.astype(jnp.float32))
