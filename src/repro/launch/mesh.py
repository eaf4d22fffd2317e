"""Production mesh builders.

Functions, not module-level constants — importing this module never touches
jax device state. The dry-run (launch/dryrun.py) sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import
so these meshes can be built on the CPU-only container.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256-chip pod ("data","model"); 2x16x16 = 512-chip 2-pod
    ("pod","data","model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(*, data: int | None = None, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = jax.device_count()
    data = data or (n // model)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=_auto(2))


def _auto(ndim: int):
    """Auto axis types: the sharding policy steers layouts with
    with_sharding_constraint, which only accepts Auto mesh axes (JAX's
    make_mesh defaults to Explicit ones)."""
    return (jax.sharding.AxisType.Auto,) * ndim
