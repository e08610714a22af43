"""Production mesh definitions (TPU v5e pods).

A function, not a module-level constant: importing this module never
touches jax device state (device count locks on first backend init).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi-pod = 2 pods = 512 chips.

    Axes: "data" carries DP/FSDP, "model" carries TP/EP/sequence-parallel
    KV; "pod" (multi-pod) carries cross-DCN data parallelism.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, (jax.sharding.AxisType.Auto,) * len(axes))

