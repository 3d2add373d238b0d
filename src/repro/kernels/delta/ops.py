"""jit'd wrapper for the XOR-delta kernel."""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.common import interpret_default
from repro.kernels.delta.delta import COLS, ROWS, TILE, delta_tiles


@partial(jax.jit, static_argnames=("interpret",))
def xor_delta(
    cur: jax.Array, prev: jax.Array, *, interpret: bool | None = None
) -> Tuple[jax.Array, jax.Array]:
    """uint32 streams -> (delta uint32 same length, changed word count int32)."""
    if interpret is None:
        interpret = interpret_default()
    c = cur.reshape(-1).astype(jnp.uint32)
    p = prev.reshape(-1).astype(jnp.uint32)
    if c.shape != p.shape:
        raise ValueError("delta requires equal-length streams")
    n = c.shape[0]
    pad = (-n) % TILE
    if pad:
        c = jnp.pad(c, (0, pad))
        p = jnp.pad(p, (0, pad))
    ct = c.reshape(-1, ROWS, COLS)
    pt = p.reshape(-1, ROWS, COLS)
    d, counts = delta_tiles(ct, pt, interpret=interpret)
    return d.reshape(-1)[:n], jnp.sum(counts[:, 0, 0])
