"""Pallas TPU kernel: fused XOR-delta + per-chunk dirty count + checksum.

One grid step per *chunk* (``CheckpointConfig.chunk_size`` bytes =
``tiles_per_chunk`` native ``(8, 128)`` uint32 tiles), one pass over
both streams.  Each step emits:

* the XOR delta of its chunk (``kernels/delta`` semantics),
* the changed-word count (``> 0`` == the chunk is dirty), and
* the two-track checksum partials ``(S, T)`` of the *current* chunk —
  the same function as ``kernels/checksum`` restarted at every chunk
  boundary, so the pair digests the chunk exactly like
  ``checksum_u32`` over the chunk's words alone.

Fusing the three saves two extra HBM sweeps over the full state: the
separate delta + per-chunk checksum composition reads the streams once
per kernel, and at checkpoint sizes the pass is purely
HBM-bandwidth-bound.  The position index is computed in-kernel from the
tile/row/col iotas and reduced mod ``IDX_MOD`` (a power of two, so a
bitwise AND), keeping every product exact in uint32 before the
deliberate wrap-around accumulation — identical to the numpy oracle in
``ref.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.checksum.ref import IDX_MOD
from repro.kernels.common import sums_tile_u32

TILE_ROWS = 8
TILE_COLS = 128
TILE = TILE_ROWS * TILE_COLS  # 1024 uint32 words per native tile


def _fused_kernel(c_ref, b_ref, d_ref, m_ref):
    c = c_ref[0]  # (tiles_per_chunk, 8, 128) uint32, the current chunk
    b = b_ref[0]  # same shape, the base snapshot's chunk
    d = jnp.bitwise_xor(c, b)
    d_ref[0] = d
    shape = c.shape
    tile = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    rows = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    cols = jax.lax.broadcasted_iota(jnp.uint32, shape, 2)
    idx = (
        tile * jnp.uint32(TILE)
        + rows * jnp.uint32(TILE_COLS)
        + cols
    ) & jnp.uint32(IDX_MOD - 1)
    m_ref[0] = sums_tile_u32((d != 0).astype(jnp.uint32), c, idx * c)


def fused_chunk_tiles(cur: jnp.ndarray, base: jnp.ndarray, *, interpret: bool):
    """(n_chunks, tiles_per_chunk, 8, 128) u32 x2 ->
    (delta same shape, meta (n_chunks, 8, 128) u32 with
    ``meta[:, 0, :3] = (changed, S, T)`` and zeros elsewhere)."""
    n, t = cur.shape[0], cur.shape[1]
    return pl.pallas_call(
        _fused_kernel,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, t, TILE_ROWS, TILE_COLS), lambda g: (g, 0, 0, 0)),
            pl.BlockSpec((1, t, TILE_ROWS, TILE_COLS), lambda g: (g, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, t, TILE_ROWS, TILE_COLS), lambda g: (g, 0, 0, 0)),
            pl.BlockSpec((1, TILE_ROWS, TILE_COLS), lambda g: (g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, t, TILE_ROWS, TILE_COLS), jnp.uint32),
            jax.ShapeDtypeStruct((n, TILE_ROWS, TILE_COLS), jnp.uint32),
        ],
        interpret=interpret,
    )(cur, base)
