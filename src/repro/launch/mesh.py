"""Production meshes.

Target hardware: TPU v5e pods — 256 chips (16x16 ICI torus) per pod.
Single-pod mesh: (data=16, model=16).  Multi-pod: (pod=2, data=16,
model=16) — the ``pod`` axis is also the Distributed-GAN ``users`` axis in
the paper's 2-user topology (one user's private shard per pod; only
selected deltas / logits cross the DCN between pods).

``make_production_mesh`` is a function (not a module constant) so importing
this module never touches jax device state.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


# v5e hardware constants (roofline denominators; see roofline/analysis.py)
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link (≈2 usable links per axis)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever local devices exist (tests / smoke)."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def make_users_mesh(num_users: int):
    """Federation mesh for the SPMD Distributed-GAN (one user per slice)."""
    return jax.make_mesh((num_users,), ("users",),
                         axis_types=(AxisType.Auto,))
