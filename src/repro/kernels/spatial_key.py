"""Pallas TPU kernel: space-filling-curve keys for query rectangles.

The spatial batch scheduler (``repro.core.schedule``) sorts incoming
traffic by Hilbert or Morton key before batching, so each serving batch
covers a compact region of space and the fused traversal kernel's
tile-level early exit (and the compaction epilogue that inherits it) fires
on most leaf tiles. The key computation itself is the only per-query work
the scheduler adds to the hot admission path, so it gets a kernel too.

Input layout: normalized query-rect centers as two planar rows
(``cxy_t`` [2, B] f32 in [0, 1) — ``ops.py`` computes centers and
normalizes by the workload bounding box; a shared bbox is what makes keys
comparable across batches). Output: ``[1, B]`` int32 keys.

Both curves quantize each center to ``order``-bit integer coordinates and
run a static ``order``-iteration bit loop on the VPU — pure element-wise
int32 compare/select/shift ops over the lane dimension, no gathers, no MXU:

* ``morton``  — bit interleave (x high bit first). Cheap, but adjacent keys
  can still be spatially far at quadrant boundaries.
* ``hilbert`` — the classic xy→d walk (per-step quadrant rotation carried
  as compare/selects). Strictly better locality: consecutive keys are
  always adjacent cells, which is exactly what batch formation wants.

``order`` defaults to 15 so the key (2·order = 30 bits) stays inside a
*signed* int32 — keys only need to be sort-stable, not dense, and int32 is
the native sort/compare width on both the VPU and XLA:CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEF_TB = 1024   # query tile (lane axis, multiple of 128)
DEF_ORDER = 15  # bits per dimension; 2·order must stay < 32 (signed keys)


def _quantize(c, order: int):
    """[N] f32 in [0, 1) → [N] i32 in [0, 2^order) (clamped)."""
    n = jnp.int32(1 << order)
    q = (c * n.astype(jnp.float32)).astype(jnp.int32)
    return jnp.clip(q, 0, n - 1)


def _morton_bits(x, y, order: int):
    """Interleave order-bit x/y (x in the odd/high positions) → i32 key."""
    key = jnp.zeros_like(x)
    for i in range(order):
        key = key | (((x >> i) & 1) << (2 * i + 1)) | (((y >> i) & 1)
                                                       << (2 * i))
    return key


def _hilbert_bits(x, y, order: int):
    """Classic xy→d Hilbert walk, vectorized: rotations become selects.

    Per step (s = 2^i, high bit first): d += s²·((3·rx) ^ ry), then the
    standard quadrant rotation — when ry == 0, flip both coords if rx == 1
    and swap x/y. Unrolled ``order`` times (static), all int32 lane ops.
    """
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


def _make_kernel(order: int, curve: str):
    def kernel(c_ref, o_ref):
        # c_ref: [2, TB] f32 normalized centers; o_ref: [1, TB] i32 keys
        x = _quantize(c_ref[0, :], order)
        y = _quantize(c_ref[1, :], order)
        bits = _hilbert_bits if curve == "hilbert" else _morton_bits
        o_ref[0, :] = bits(x, y, order)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("curve", "order", "tb", "interpret"))
def spatial_key_t(cxy_t: jnp.ndarray, *, curve: str = "hilbert",
                  order: int = DEF_ORDER, tb: int = DEF_TB,
                  interpret: bool = False) -> jnp.ndarray:
    """Transposed-layout entry point: ``cxy_t`` [2, B] f32 → [1, B] i32.

    B must be a multiple of ``tb`` (ops.py pads); padding lanes produce
    ordinary keys and are sliced off by the caller.
    """
    assert curve in ("hilbert", "morton"), curve
    assert 2 * order < 32, order
    _, B = cxy_t.shape
    assert B % tb == 0, (B, tb)
    return pl.pallas_call(
        _make_kernel(order, curve),
        grid=(B // tb,),
        in_specs=[pl.BlockSpec((2, tb), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, tb), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, B), jnp.int32),
        interpret=interpret,
        name="spatial_key",
    )(cxy_t.astype(jnp.float32))
