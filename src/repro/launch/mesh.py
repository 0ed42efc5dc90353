"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — required because the dry-run
must set ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before
any jax initialization, while smoke tests must see 1 device.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax

__all__ = ["make_production_mesh", "make_host_mesh", "make_one_chip_mesh",
           "dp_axes", "tp_axis"]


def _auto(n_axes: int):
    """Auto axis types: the compiler propagates shardings from the
    ``with_sharding_constraint`` annotations (``jax.make_mesh`` defaults
    to Explicit axes, under which every unannotated gather raises)."""
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(model_parallel: int = 1):
    """Whatever this host has (tests / examples): (data, model)."""
    n = jax.device_count()
    assert n % model_parallel == 0
    return jax.make_mesh((n // model_parallel, model_parallel),
                         ("data", "model"), axis_types=_auto(2))


def make_one_chip_mesh():
    """A 1 x 1 (data, model) mesh on the first device: one chip's share."""
    return jax.make_mesh((1, 1), ("data", "model"), axis_types=_auto(2),
                         devices=jax.devices()[:1])


def dp_axes(mesh) -> Tuple[str, ...]:
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def tp_axis(mesh) -> Optional[str]:
    return "model" if "model" in mesh.axis_names else None
