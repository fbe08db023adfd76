"""Production mesh definition (TPU v5e: 16x16 = 256 chips per pod)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``.

    jax 0.9 makes ``Explicit`` axes by default, and
    ``with_sharding_constraint`` (``runtime.sharding.shard``) refuses a
    mesh whose axes are not ``Auto``.
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_local_mesh():
    """Whatever devices exist, as a (data, model) mesh (tests/examples)."""
    n = len(jax.devices())
    return auto_mesh((n, 1), ("data", "model"))


# TPU v5e hardware constants for the roofline model (per chip).
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW = 50e9                     # B/s per link
