"""The local device mesh.

Defined as a function (importing this module never touches jax device
state).  Every axis is ``Auto``: callers place arrays with
``NamedSharding`` and ``shard_map``, and the compiler propagates shardings
between them.  ``jax.make_mesh`` would otherwise make ``Explicit`` axes,
under which every array's type carries its sharding.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_local_mesh():
    """Whatever devices exist locally, as (data, model) with every device
    on ``data``."""
    axes = ("data", "model")
    return jax.make_mesh((len(jax.devices()), 1), axes,
                         axis_types=(AxisType.Auto,) * len(axes))
