"""Mesh builders (functions — importing never touches jax device state; jax
locks the device count on first backend init)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``.

    jax >= 0.7 makes mesh axes ``Explicit`` by default, and an explicit axis
    refuses the ``with_sharding_constraint`` hints that ``sharding.specs``
    places inside ``Model.loss_fn``. Every mesh of this repo is built here.
    """
    axes = tuple(axes)
    return jax.make_mesh(
        tuple(shape), axes, axis_types=(AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2x16x16 = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for CPU multi-device tests (host platform device count)."""
    return make_mesh((n_data, n_model), ("data", "model"))
