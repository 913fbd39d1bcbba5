"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state; the dry-run sets XLA_FLAGS for 512 host devices
*before* calling it.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(model: int = 1):
    """Whatever this host has (used by smoke tests / examples)."""
    n = len(jax.devices())
    data = max(1, n // model)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def make_cam_mesh(banks: int | None = None, queries: int = 1):
    """Device mesh for sharded CAM search (core.sharded).

    The 'bank' axis carries the stored grid's nv (vertical/bank) dimension
    — the bank level of the paper's subarray→array→mat→bank hierarchy as a
    physical parallelism axis; the optional 'query' axis splits the search
    batch.  Defaults to all local devices on 'bank'.
    """
    n = len(jax.devices())
    if banks is None:
        banks = max(1, n // max(1, queries))
    return jax.make_mesh((banks, queries), ("bank", "query"),
                         axis_types=(AxisType.Auto,) * 2)
