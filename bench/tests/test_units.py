"""The benchmark's own arithmetic: FLOPs, peaks, digests."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from bench import flops
from bench.harness import BenchError, peaks_for

ROOT = Path(__file__).resolve().parents[2]
QWEN = json.loads((ROOT / "bench/configs/qwen1.5-0.5b.json").read_text())


def test_flops_hand_count_qwen_as_run():
    d, ff, L, V, S = 1024, 2816, 4, 18992, 2048
    per_layer = 4 * d * d + 3 * d * ff          # q, k, v, o; gate, up, down
    weights = L * per_layer + V * d             # and the output head
    assert flops.matmul_weights(QWEN) == weights == 70_828_032
    attn = 12 * L * 16 * 64 * S
    assert flops.train_flops_per_token(QWEN, S) == 6 * weights + attn == 525_631_488


def test_flops_hand_count_published_qwen_and_gqa():
    full = dict(QWEN, num_hidden_layers=24, vocab_size=151936)
    assert flops.matmul_weights(full) == 24 * 12_845_056 + 151936 * 1024
    # grouped-query attention: k and v have num_key_value_heads heads
    mistral = {"hidden_size": 4096, "num_attention_heads": 32,
               "num_key_value_heads": 8, "intermediate_size": 14336,
               "num_hidden_layers": 32, "vocab_size": 32000}
    per_layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert flops.matmul_weights(mistral) == 32 * per_layer + 32000 * 4096
    assert flops.train_flops_per_token(mistral, 4096) == (
        6 * (32 * per_layer + 32000 * 4096) + 12 * 32 * 32 * 128 * 4096)


def test_peaks_known_and_unknown():
    assert peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(BenchError):
        peaks_for("TPU v9 imaginary")


@pytest.mark.parametrize("dtype", [np.float32, np.int32, np.uint8, "bfloat16"])
def test_device_and_host_digest_agree(dtype):
    import jax
    import jax.numpy as jnp

    from bench.job import device_digest, host_digest

    rng = np.random.default_rng(0)
    a = rng.standard_normal((33, 7)).astype(np.float32) * 1000
    x = jnp.asarray(a).astype(dtype)
    want = np.asarray(device_digest([x]))[0]
    assert np.array_equal(host_digest(np.asarray(x)), want)
    b = np.array(np.asarray(x))
    b.reshape(-1).view(np.uint8)[5] ^= 1
    assert not np.array_equal(host_digest(b), want)
