"""Mesh construction: the repo's one mesh constructor and the production mesh.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init,
and tests/benches must keep seeing 1 device.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(
    shape: Sequence[int], axes: Sequence[str], *, devices: Optional[Sequence] = None
) -> Mesh:
    """Every mesh in the repo comes from here.  Axes are Auto:
    ``distributed/sharding.py`` places arrays with sharding constraints,
    which JAX accepts only on Auto axes (``jax.make_mesh`` builds Explicit
    ones by default)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), (AxisType.Auto,) * len(axes), devices=devices
    )


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
