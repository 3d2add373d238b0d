"""Pallas TPU kernel: two-track chunked checksum over uint32 words.

Layout: the word stream is reshaped to ``(n_tiles, 8, 128)`` — one
``(8, 128)`` uint32 tile per grid step, the native VREG-aligned 32-bit
tile shape.  Each grid step reduces its tile to a partial
``(S_tile, T_tile)`` pair; the cheap cross-tile combine happens in
``ops.py`` (the global position weight of tile ``g`` is ``g * TILE %
IDX_MOD``, folded in after the fact).

All arithmetic is uint32 with natural wrap-around (mod 2^32), identical
to the numpy oracle in ``ref.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import sums_tile_u32

TILE_ROWS = 8
TILE_COLS = 128
TILE = TILE_ROWS * TILE_COLS  # 1024 words per grid step


def _checksum_kernel(w_ref, out_ref):
    w = w_ref[0]  # (8, 128) uint32 tile in VMEM
    # local position index 0..TILE-1 (row-major), exact in uint32
    rows = jax.lax.broadcasted_iota(jnp.uint32, (TILE_ROWS, TILE_COLS), 0)
    cols = jax.lax.broadcasted_iota(jnp.uint32, (TILE_ROWS, TILE_COLS), 1)
    idx = rows * jnp.uint32(TILE_COLS) + cols
    out_ref[0] = sums_tile_u32(w, idx * w)


def checksum_tiles(words: jnp.ndarray, *, interpret: bool) -> jnp.ndarray:
    """words: (n_tiles, 8, 128) uint32 -> (n_tiles, 8, 128) uint32 with
    the per-tile partials ``(S, T)`` at ``[:, 0, :2]``."""
    n_tiles = words.shape[0]
    return pl.pallas_call(
        _checksum_kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((1, TILE_ROWS, TILE_COLS), lambda g: (g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, TILE_ROWS, TILE_COLS), lambda g: (g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tiles, TILE_ROWS, TILE_COLS), jnp.uint32),
        interpret=interpret,
    )(words)
