"""Production mesh builders.

Functions, not module-level constants: importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before any
device query).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 dual-pod (512 chips).

    Axes: ``data`` carries FSDP + data parallelism, ``model`` carries
    tensor/expert parallelism, ``pod`` (multi-pod only) is an outer
    data-parallel axis whose collectives ride the inter-pod DCN.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Whatever this process actually has (CPU smoke runs): 1x1 mesh."""
    n = len(jax.devices())
    return _auto_mesh((n, 1), ("data", "model"))


def _auto_mesh(shape, axes):
    # ``jax.make_mesh`` defaults to Explicit axes, under which a gather
    # from a vocab-sharded embedding needs an ``out_sharding`` at every
    # call site; the train step states its shardings through jit instead.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
