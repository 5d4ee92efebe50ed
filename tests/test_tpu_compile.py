"""The main-path kernels' TPU forms compile with Mosaic for a v5e.

Interpret mode cannot see what the chip's compiler refuses: a block not
aligned to the (8, 128) tiling, a primitive Mosaic has no lowering for,
more VMEM or SMEM than a kernel may use. These tests compile each serving
kernel for a described (not attached) ``v5e:2x2`` topology at the widths
``chip_smoke.py`` serves — the Chicago-Crimes-like index at 872,000
points (12,730 leaves of 128 entries, four levels), batches of 512, the
narrow R-path bound 64 and its wide tier 512 — and check that the
compiled program calls the kernel as a Mosaic custom call. The router's
forest is among them: every range step routes its queries through it.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and test workers import every file.
"""
import os
import types

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import delta_probe as dp
from repro.kernels import forest_infer as fi
from repro.kernels import knn_browse as kb
from repro.kernels import leaf_refine as lr
from repro.kernels import mlp_infer as mi
from repro.kernels import spatial_key as sk
from repro.kernels import traverse_fused as tf
from repro.kernels import ops
from repro.kernels.ops import mosaic_kernels

B = 512            # serving batch
K = 64             # narrow R-path bound (max_visited)
WIDE = 8           # wide-tier factor
LP = 12_800        # 12,730 leaves, padded to the 512-leaf tile
M = 128            # node capacity
INT_WIDTHS = (128, 128, 256)   # lane-padded internal levels, root first
C, F, H, CL, S = 400, 4, 64, 670, 4   # mlp bank: cells, features, hidden,
#                                       label slots (the fitted 20² grid's),
#                                       cells/query
MAX_PRED = 64      # the AI path's predicted-leaf bound
CAP = 32_768       # delta buffer
TREES, DEPTH = 16, 6   # the router's oblivious forest


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """``shape(dims, dtype)`` → a ShapeDtypeStruct on one described chip;
    the persistent compile cache stays off meanwhile (a described-chip
    compile is written to it but cannot be read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda dims, dt=jnp.float32: jax.ShapeDtypeStruct(
        dims, dt, sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)


def _levels(shape):
    ints = [shape((4, w)) for w in INT_WIDTHS]
    pars = [shape((1, w), jnp.int32) for w in INT_WIDTHS[1:]]
    return (shape((4, B)), ints, pars, shape((4, LP)),
            shape((1, LP), jnp.int32))


def _traverse_compact(shape, k):
    q, ints, pars, lm, lp = _levels(shape)
    fn = lambda q, i, p, lm, lp: tf.traverse_compact_t(  # noqa: E731
        q, i, p, lm, lp, k=k, tb=256, tl=512)
    return fn, (q, ints, pars, lm, lp), {"traverse_compact"}


def _traverse_compact_sliced(shape):
    q, ints, pars, lm, lp = _levels(shape)
    fn = lambda st, q, i, p, lm, lp: tf.traverse_compact_sliced_t(  # noqa
        st, q, i, p, lm, lp, k=K, widths=INT_WIDTHS, tb=256, tl=512)
    return fn, (shape((len(INT_WIDTHS), LP // 512), jnp.int32), q, ints,
                pars, lm, lp), {"traverse_compact_sliced"}


def _mlp(shape):
    # the tiles the wrapper would pick for the TPU form at these widths;
    # the kernel sees the label slots lane-padded
    bank = types.SimpleNamespace(w1=shape((C, F, H)), w2=shape((C, H, CL)))
    tb, tl, _, _ = ops._mlp_tiles(B, LP, bank, S, MAX_PRED, interp=False)
    assert ops._mlp_vmem(bank, S, MAX_PRED, False, tb, tl) <= \
        tf.VMEM_BUDGET, "the VMEM gate would refuse the kernel on a TPU"
    clp = CL + (-CL) % 128
    fn = lambda *a: mi.mlp_predict_compact_t(  # noqa: E731
        *a, k=MAX_PRED, lp=LP, thr=0.5, tb=tb, tl=tl)
    return fn, (shape((B, F)), shape((B, S), jnp.int32),
                shape((B, S), jnp.int32), shape((C, F, H)), shape((C, H)),
                shape((C, H, clp)), shape((C, clp)),
                shape((C, clp), jnp.int32)), {"mlp_infer", "mlp_union"}


def _slots(shape, kernel, k, q_cols):
    fn = lambda q, e, i, v: kernel(q, e, i, v, interpret=False)  # noqa: E731
    return fn, (shape((B, q_cols)), shape((LP, 2, M)),
                shape((B, k), jnp.int32), shape((B, k), jnp.bool_)), \
        {kernel.__name__}


# case → (fn, shapes, names of the kernels the compiled program must call)
CASES = {
    "traverse_compact": lambda s: _traverse_compact(s, K),
    "traverse_compact_wide": lambda s: _traverse_compact(s, K * WIDE),
    "traverse_compact_sliced": _traverse_compact_sliced,
    "mlp_predict_compact": _mlp,
    "leaf_refine": lambda s: _slots(s, lr.leaf_refine, K, 4),
    "leaf_refine_wide": lambda s: _slots(s, lr.leaf_refine, K * WIDE, 4),
    "delta_probe": lambda s: (
        lambda q, p: dp.delta_probe_t(q, p, k=64, tb=256, tn=512),
        (s((4, B)), s((2, CAP))), {"delta_probe"}),
    "knn_browse": lambda s: _slots(s, kb.knn_browse, K, 3),
    "spatial_key": lambda s: (
        lambda c: sk.spatial_key_t(c, tb=B), (s((2, B)),), {"spatial_key"}),
    "forest_infer": lambda s: (
        lambda x, t, v: fi.forest_infer(x, t, v, tb=256),
        (s((B, TREES, DEPTH)), s((TREES, DEPTH)),
         s((TREES, 2 ** DEPTH, 1))), {"forest_infer"}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(shape, case):
    fn, args, kernels = CASES[case](shape)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert kernels <= mosaic_kernels(text), mosaic_kernels(text)
