"""Pallas TPU kernel: fused XOR-delta + changed-word count.

One pass over both streams in ``(8, 128)`` uint32 tiles: emits the XOR
delta and a per-tile changed-word count (int32).  Fusing the count into
the delta pass saves a second HBM sweep — at checkpoint sizes (GBs) the
kernel is purely HBM-bandwidth-bound, so one pass instead of two halves
the cost of incremental checkpointing's encode step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import lane_tile

ROWS = 8
COLS = 128
TILE = ROWS * COLS


def _delta_kernel(c_ref, p_ref, d_ref, n_ref):
    c = c_ref[0]
    p = p_ref[0]
    d = jnp.bitwise_xor(c, p)
    d_ref[0] = d
    n_ref[0] = lane_tile(jnp.sum((d != 0).astype(jnp.int32)))


def delta_tiles(cur: jnp.ndarray, prev: jnp.ndarray, *, interpret: bool):
    """(n_tiles, 8, 128) u32 x2 -> (delta same shape, counts (n_tiles, 8, 128)
    i32 with the tile's changed-word count at ``[:, 0, 0]``)."""
    n = cur.shape[0]
    return pl.pallas_call(
        _delta_kernel,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, ROWS, COLS), lambda g: (g, 0, 0)),
            pl.BlockSpec((1, ROWS, COLS), lambda g: (g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, ROWS, COLS), lambda g: (g, 0, 0)),
            pl.BlockSpec((1, ROWS, COLS), lambda g: (g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, ROWS, COLS), jnp.uint32),
            jax.ShapeDtypeStruct((n, ROWS, COLS), jnp.int32),
        ],
        interpret=interpret,
    )(cur, prev)
