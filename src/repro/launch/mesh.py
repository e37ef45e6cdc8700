"""Production meshes (functions, not constants: importing this module must
never touch jax device state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod (TPU v5e); 2 pods = 512 chips multi-pod.

    Axes: "data" (batch / fsdp), "model" (tensor/expert parallel), and for
    multi-pod a leading "pod" axis that shards batch only (params replicate
    across the DCN; gradient all-reduce is the only cross-pod collective).
    Axes are Auto: ``repro.sharding.specs`` places arrays by name and the
    compiler propagates the rest.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))
