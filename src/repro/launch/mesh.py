"""Production meshes.

Defined as functions (importing this module never touches jax device state).
Single pod: 256 chips as (data=16, model=16).  Multi-pod: 2 pods = 512 chips
as (pod=2, data=16, model=16); the ``pod`` axis extends FSDP/data-parallel
sharding across the DCN boundary (gradients reduce over pod+data).

Every axis is ``Auto``: callers place arrays with ``NamedSharding`` and
``shard_map``, and the compiler propagates shardings between them.
``jax.make_mesh`` would otherwise make ``Explicit`` axes, under which every
array's type carries its sharding.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(model_axis: int = 1):
    """Whatever devices exist locally, as (data, model) — used by smoke tests
    and the CPU examples."""
    n = len(jax.devices())
    assert n % model_axis == 0
    return _auto_mesh((n // model_axis, model_axis), ("data", "model"))


# TPU v5e hardware constants for the roofline (per chip)
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW = 50e9                     # B/s per link
