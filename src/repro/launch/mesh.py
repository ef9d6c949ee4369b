"""Mesh definitions.

A v5e pod is 16x16 = 256 chips; the multi-pod config stacks 2 pods on a
'pod' axis (DCN-connected). Defined as FUNCTIONS so importing this module
never touches jax device state (device count is locked at first use).

Every mesh is built by :func:`auto_mesh`, with ``Auto`` axis types: the
model code places arrays with sharding constraints and lets GSPMD propagate
(``jax.make_mesh`` would default to ``Explicit`` axes, under which
``jax.grad`` through the shard_map'd layers is refused).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType

from repro.sharding.specs import Topology, make_topology


def auto_mesh(
    shape: Sequence[int],
    axes: Sequence[str],
    devices: Optional[Sequence] = None,
):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_local_mesh():
    """Every local device on the data axis."""
    n = len(jax.devices())
    return auto_mesh((n, 1), ("data", "model"))


def production_topology(*, multi_pod: bool = False) -> Topology:
    return make_topology(make_production_mesh(multi_pod=multi_pod))
