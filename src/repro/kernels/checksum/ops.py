"""jit'd wrapper around the checksum kernel + cross-tile combine."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.common import bytes_to_u32, interpret_default
from repro.kernels.checksum.checksum import TILE, TILE_COLS, TILE_ROWS, checksum_tiles
from repro.kernels.checksum.ref import IDX_MOD


@partial(jax.jit, static_argnames=("interpret",))
def checksum_u32(words: jnp.ndarray, *, interpret: bool | None = None) -> jnp.ndarray:
    """Two-track checksum of a 1-D uint32 array -> (2,) uint32 = (S, T).

    Zero-padding to a tile multiple is checksum-neutral for S and T
    (padded words are 0).
    """
    if interpret is None:
        interpret = interpret_default()
    w = words.astype(jnp.uint32).reshape(-1)
    if w.shape[0] == 0:
        return jnp.zeros((2,), jnp.uint32)
    pad = (-w.shape[0]) % TILE
    if pad:
        w = jnp.pad(w, (0, pad))
    n_tiles = w.shape[0] // TILE
    tiles = w.reshape(n_tiles, TILE_ROWS, TILE_COLS)
    partials = checksum_tiles(tiles, interpret=interpret)
    s_g = partials[:, 0, 0]
    t_g = partials[:, 0, 1]
    base = (jnp.arange(n_tiles, dtype=jnp.uint32) * jnp.uint32(TILE)) % jnp.uint32(
        IDX_MOD
    )
    s = jnp.sum(s_g, dtype=jnp.uint32)
    t = jnp.sum(t_g + base * s_g, dtype=jnp.uint32)
    return jnp.stack([s, t])


def digest_bytes(data: bytes, *, interpret: bool | None = None) -> int:
    """Host entry: digest of a byte string via the device kernel."""
    words = jnp.asarray(bytes_to_u32(data))
    s, t = np.asarray(checksum_u32(words, interpret=interpret))
    return (int(t) << 32) | int(s)


def _as_u32(x: jax.Array) -> jax.Array:
    x = x.reshape(-1)
    isz = x.dtype.itemsize
    if isz == 4:
        return jax.lax.bitcast_convert_type(x, jnp.uint32)
    if isz < 4:
        per = 4 // isz
        pad = (-x.shape[0]) % per
        if pad:
            x = jnp.pad(x, (0, pad))
        return jax.lax.bitcast_convert_type(x.reshape(-1, per), jnp.uint32).reshape(-1)
    # 8-byte dtypes -> (n, 2) u32 limbs
    return jax.lax.bitcast_convert_type(x, jnp.uint32).reshape(-1)


def digest_array(x: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """Digest of an on-device array (pre-D2H integrity for the flush path)."""
    return checksum_u32(_as_u32(x), interpret=interpret)
