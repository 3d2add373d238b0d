"""Host-facing wrapper for the fused chunk pre-codec pass.

``fused_precodec`` takes the current and base snapshots as flat uint32
word streams (the serialized-tree byte stream viewed as words) and runs
the fused kernel once over the whole state: one launch, one HBM sweep,
emitting per-chunk XOR deltas plus a ``(changed, S, T)`` meta row per
chunk.  ``CheckpointConfig.chunk_size`` must be a multiple of
``CHUNK_ALIGN`` (4096 bytes — one native ``(8, 128)`` uint32 tile) so
chunks tile exactly.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.common import interpret_default
from repro.kernels.fused.fused import TILE, TILE_COLS, fused_chunk_tiles

CHUNK_ALIGN = TILE * 4  # bytes per native tile; chunk_size must be a multiple


@partial(jax.jit, static_argnames=("chunk_words", "interpret"))
def fused_precodec(cur, base, *, chunk_words: int, interpret=None):
    """Fused delta + dirty-count + checksum over chunked word streams.

    ``cur``/``base``: equal-length 1-D uint32 arrays (zero-pad is
    applied here up to a chunk multiple; zero padding is neutral for
    both the dirty count and the checksum tracks, and a stream that is
    already a chunk multiple is not copied).  Returns ``(delta, meta)``
    with ``delta`` shaped ``(n_chunks, chunk_words // 128, 128)`` uint32
    (chunk ``i``'s words are ``delta[i].reshape(-1)``) and ``meta``
    shaped ``(n_chunks, 3)`` uint32 rows of ``(changed_words, S, T)``.

    The delta keeps rows of 128 lanes because that is free on a TPU: a
    ``(n_chunks, chunk_words)`` array tiles 8 chunks per ``(8, 128)``
    tile, and the relayout would copy the whole delta in HBM.
    """
    if interpret is None:
        interpret = interpret_default()
    if chunk_words <= 0 or chunk_words % TILE:
        raise ValueError(
            f"chunk_words must be a positive multiple of {TILE}, got {chunk_words}"
        )
    c = jnp.asarray(cur, dtype=jnp.uint32).reshape(-1)
    b = jnp.asarray(base, dtype=jnp.uint32).reshape(-1)
    if c.shape != b.shape:
        raise ValueError(f"stream length mismatch: {c.shape} vs {b.shape}")
    rem = (-c.size) % chunk_words
    if rem:
        c = jnp.pad(c, (0, rem))
        b = jnp.pad(b, (0, rem))
    tiles_per_chunk = chunk_words // TILE
    n_chunks = c.size // chunk_words
    ct = c.reshape(n_chunks, tiles_per_chunk, 8, 128)
    bt = b.reshape(n_chunks, tiles_per_chunk, 8, 128)
    delta, meta = fused_chunk_tiles(ct, bt, interpret=interpret)
    return delta.reshape(n_chunks, -1, TILE_COLS), meta[:, 0, :3]


def digests_from_meta(meta: np.ndarray) -> np.ndarray:
    """(n_chunks, 3) uint32 meta rows -> (n_chunks,) uint64 digests."""
    m = np.asarray(meta, dtype=np.uint64)
    return (m[:, 2] << np.uint64(32)) | m[:, 1]


def dirty_from_meta(meta: np.ndarray) -> np.ndarray:
    """(n_chunks, 3) uint32 meta rows -> (n_chunks,) bool dirty mask."""
    return np.asarray(meta)[:, 0] > 0
