"""The one place every device mesh in the repo is built.

``jax.make_mesh`` defaults its axes to ``AxisType.Explicit`` on current JAX,
which puts each array's sharding into its type.  The repo's sharded paths
(``shard_map`` ppermute gossip, ``shard="nodes"``, ``shard="cells"``) mix
mesh-placed values with unplaced ones under ``vmap`` and rely on GSPMD to
propagate shardings, so every mesh here uses ``AxisType.Auto``.
"""

from __future__ import annotations

from typing import Sequence

import jax
import numpy as np
from jax.sharding import AxisType

__all__ = ["make_mesh"]


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """A mesh of ``shape`` named ``axes`` with Auto axis types, over the
    first ``prod(shape)`` visible devices."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=jax.devices()[:int(np.prod(shape))])
