"""Mesh construction: the one place a ``jax.sharding.Mesh`` is built.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required for the dry-run's
``xla_force_host_platform_device_count`` trick to work.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple, axes: tuple, *, devices=None):
    """A mesh over ``jax.devices()`` (or ``devices``) with ``Auto`` axes.

    ``jax.make_mesh`` makes ``Explicit`` axes by default, under which the
    ambient mesh (``jax.set_mesh``) turns every unsharded jnp call into a
    sharding-type check; the engine places its arrays through
    ``shard_map`` specs, so all of its axes are ``Auto``.
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single pod (256 chips) or 2×16×16 (512 chips, 2 pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2, *,
                    multi_pod: bool = False):
    """Small mesh for CPU multi-device tests (host platform device count)."""
    if multi_pod:
        return make_mesh((2, n_data, n_model), ("pod", "data", "model"))
    return make_mesh((n_data, n_model), ("data", "model"))


def batch_axes(mesh) -> tuple:
    """The axes the global batch shards over."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
