"""Synthetic MLP banks and hybrid trees shared by the test modules.

Kept out of the test modules so that a module imports them at its top:
importing a test module from inside a ``@given`` body would nest that
module's own ``@given`` tests inside the running property.
"""
import jax.numpy as jnp
import numpy as np

from repro.core.aitree import make_aitree
from repro.core.classifiers.mlp import MLPBank
from repro.core.classifiers.router import Router
from repro.core.device_tree import DeviceTree, Level
from repro.core.grid import Grid
from repro.core.hybrid import HybridTree
from repro.data.synth_tree import synth_levels


def synth_bank(rng, C, L, F=4, H=8, Cl=6, pos_bias=0.0):
    """A random (untrained) MLPBank over C cells and L global leaves."""
    lm = rng.integers(0, L, (C, Cl)).astype(np.int32)
    lmask = rng.uniform(size=(C, Cl)) < 0.8
    lm[~lmask] = -1
    return MLPBank(
        w1=jnp.asarray(rng.normal(0, 1.0, (C, F, H)), jnp.float32),
        b1=jnp.asarray(rng.normal(0, 1.0, (C, H)), jnp.float32),
        w2=jnp.asarray(rng.normal(0, 1.0, (C, H, Cl)), jnp.float32),
        b2=jnp.asarray(rng.normal(pos_bias, 0.5, (C, Cl)), jnp.float32),
        mu=jnp.zeros((F,), jnp.float32),
        sd=jnp.ones((F,), jnp.float32),
        label_map=jnp.asarray(lm),
        lmask=jnp.asarray(lmask),
    )


def synth_hybrid(rng, L=1000, g=3, Cl=6, pos_bias=0.5):
    """Synthetic HybridTree over a 2-level tree (mlp bank, tiny router)."""
    mbrs, parents = synth_levels(L, 8, rng, str_pack=True)
    M = 8
    tree = DeviceTree(
        levels=tuple(Level(mbrs=jnp.asarray(m), parent=jnp.asarray(p))
                     for m, p in zip(mbrs, parents)),
        leaf_entries=jnp.asarray(rng.uniform(-1, 1, (L, 2, M)), jnp.float32),
        leaf_entry_ids=jnp.asarray(np.arange(L * M).reshape(L, M), jnp.int32),
        leaf_counts=jnp.full((L,), M, jnp.int32),
        n_points=L * M, max_entries=M)
    bank = synth_bank(rng, g * g, L, Cl=Cl, pos_bias=pos_bias)
    grid = Grid(bbox=jnp.asarray([-1.0, -1.0, 1.0, 1.0], jnp.float32), g=g)
    ait = make_aitree(grid, bank, max_cells=4, max_pred=16)
    router = Router(
        feat_idx=jnp.asarray(rng.integers(0, 6, (4, 3)), jnp.int32),
        thresh=jnp.asarray(rng.uniform(-1, 1, (4, 3)), jnp.float32),
        tables=jnp.asarray(rng.uniform(0, 1, (4, 8, 1)), jnp.float32),
        tau=0.75)
    return HybridTree(tree=tree, ait=ait, router=router)
