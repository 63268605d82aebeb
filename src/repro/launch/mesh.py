"""The repo's one device-mesh constructor, and the production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state.  Every mesh in the repo is built by
:func:`make_mesh`, whose axes are all ``AxisType.Auto``: the model code
places layouts with ``with_sharding_constraint`` and leaves the rest to the
SPMD partitioner, which ``jax.make_mesh``'s Explicit default would refuse.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """A mesh of ``shape`` named ``axes``, every axis Auto."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many host devices exist (tests/examples)."""
    return make_mesh((data, model), ("data", "model"))
