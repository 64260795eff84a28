"""Production mesh definition.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before any jax init,
and unit tests must keep seeing 1 device.

Topology assumptions (TPU v5e-class): 256 chips/pod arranged (16, 16) as
("data", "model") — 16-way Megatron TP within a pod row, 16-way DP across.
Multi-pod adds a leading "pod" axis for cross-pod data parallelism (DCN-class
links: only DP gradient all-reduces cross it). The same code takes
(P, 16, 16) for P pods — 2 pods here per the assignment; nothing in the
sharding rules is specific to P=2.
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "batch_axes_for", "MODEL_AXIS_SIZE"]

MODEL_AXIS_SIZE = 16


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the sharding rules constrain and let GSPMD propagate
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def batch_axes_for(multi_pod: bool):
    """Mesh axes carrying the global batch (DP spans pods x data rows)."""
    return ("pod", "data") if multi_pod else ("data",)
