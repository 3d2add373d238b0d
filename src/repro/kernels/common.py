"""Shared helpers for the Pallas TPU kernels.

All kernels target TPU (pl.pallas_call + explicit BlockSpec VMEM tiling)
and are validated on CPU in interpret mode: ``interpret_default()`` turns
interpretation on automatically when no TPU is present, so the same
``ops.py`` entry points run everywhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def lane_tile(*vals: jnp.ndarray) -> jnp.ndarray:
    """Scalars -> one native ``(8, 128)`` tile with ``vals[i]`` at ``[0, i]``.

    A grid step's scalar results leave a TPU kernel as one whole tile:
    Mosaic only accepts output blocks whose last two dims are multiples
    of ``(8, 128)``, so per-step ``(1, k)`` rows do not compile.
    """
    rows = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)
    out = jnp.zeros((8, 128), vals[0].dtype)
    for i, v in enumerate(vals):
        out = jnp.where((rows == 0) & (lanes == i), v, out)
    return out


def sums_tile_u32(*xs: jnp.ndarray) -> jnp.ndarray:
    """uint32 arrays -> ``lane_tile`` of their sums mod 2**32, as uint32.

    Mosaic has no reduction over unsigned integers and bitcasts only
    vectors, so the sums run in int32 (two's-complement addition wraps
    to the same 32 bits) and the packed tile is bitcast back.
    """
    sums = [
        jnp.sum(jax.lax.bitcast_convert_type(x, jnp.int32), dtype=jnp.int32)
        for x in xs
    ]
    return jax.lax.bitcast_convert_type(lane_tile(*sums), jnp.uint32)


def pad_to_multiple(x: jnp.ndarray, multiple: int, axis: int = 0, value=0):
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value), n


def bytes_to_u32(data: bytes) -> np.ndarray:
    buf = np.frombuffer(data, dtype=np.uint8)
    pad = (-buf.size) % 4
    if pad:
        buf = np.concatenate([buf, np.zeros(pad, dtype=np.uint8)])
    return buf.view(np.uint32)
