"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

Interpret mode on the CPU runs kernels that the TPU compiler refuses
(block shapes off the ``(8, 128)`` tiling, unsigned reductions).  These
tests hand each kernel, at real sizes and with ``interpret=False``, to
the chip's own compiler against a described ``v5e:2x2`` topology; no
device is attached and nothing runs.  The topology is described inside
a fixture, never at import: only one process at a time may load the TPU
library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.checksum import checksum_u32
from repro.kernels.delta import xor_delta
from repro.kernels.fused import fused_precodec
from repro.kernels.quantize import quantize

STREAM = 64 << 20  # bytes per kernel input
CHUNK = 1 << 20    # the engine's default chunk_size


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip
    from jax.experimental.compilation_cache import compilation_cache

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", old)
        compilation_cache.reset_cache()


KERNELS = {
    "fused_precodec": (
        lambda c, b: fused_precodec(c, b, chunk_words=CHUNK // 4, interpret=False),
        (jnp.uint32, jnp.uint32),
    ),
    "quantize": (lambda x: quantize(x, interpret=False), (jnp.float32,)),
    "checksum_u32": (lambda w: checksum_u32(w, interpret=False), (jnp.uint32,)),
    "xor_delta": (
        lambda c, p: xor_delta(c, p, interpret=False), (jnp.uint32, jnp.uint32)
    ),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, dtypes = KERNELS[name]
    args = [
        jax.ShapeDtypeStruct((STREAM // 4,), dt, sharding=one_chip) for dt in dtypes
    ]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
