"""Per-kernel shape/dtype sweeps vs the pure-jnp/numpy oracles."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")  # interpret mode, no device needed

import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # fuzz tests skip; deterministic sweeps still run
    HAVE_HYPOTHESIS = False

    def given(*_a, **_kw):  # noqa: D103 - placeholder so decorators still apply
        return lambda fn: pytest.mark.skip(reason="hypothesis not installed")(fn)

    def settings(*_a, **_kw):
        return lambda fn: fn

    class _St:
        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _St()

from repro.kernels.checksum import checksum_u32, digest_bytes
from repro.kernels.checksum.ref import checksum_ref_np, digest_ref
from repro.kernels.delta import xor_delta
from repro.kernels.delta.ref import delta_ref
from repro.kernels.fused import (
    CHUNK_ALIGN,
    TILE,
    chunk_digests_ref,
    digests_from_meta,
    dirty_from_meta,
    fused_precodec,
    fused_ref,
)
from repro.kernels.quantize import dequantize, quantize
from repro.kernels.quantize.ref import dequantize_ref, quantize_ref

RNG = np.random.default_rng(1234)


# ---------------------------------------------------------------------------
# checksum
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [0, 1, 3, 1023, 1024, 1025, 4096, 100_003])
def test_checksum_shapes(n):
    w = RNG.integers(0, 2**32, n, dtype=np.uint32)
    s, t = np.asarray(checksum_u32(jnp.asarray(w)))
    rs, rt = checksum_ref_np(w)
    assert (int(s), int(t)) == (rs, rt)


def test_checksum_detects_flip_and_swap():
    w = RNG.integers(0, 2**32, 5000, dtype=np.uint32)
    base = digest_ref(w)
    flip = w.copy()
    flip[1234] ^= 1
    assert digest_ref(flip) != base
    swap = w.copy()
    swap[10], swap[4000] = swap[4000], swap[10]
    assert digest_ref(swap) != base  # position track catches moves


@settings(max_examples=25, deadline=None)
@given(st.binary(min_size=0, max_size=4096))
def test_checksum_bytes_fuzz(data):
    got = digest_bytes(data)
    pad = (-len(data)) % 4
    w = np.frombuffer(data + b"\0" * pad, dtype=np.uint32)
    assert got == digest_ref(w)


# ---------------------------------------------------------------------------
# quantize
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [np.float32, np.float16])
@pytest.mark.parametrize("n", [128, 4096, 4096 + 77, 50_000])
def test_quantize_matches_ref(dtype, n):
    x = (RNG.standard_normal(n) * 7).astype(dtype)
    q, s = quantize(jnp.asarray(x))
    pad = (-n) % 4096
    ref_q, ref_s = quantize_ref(
        np.pad(x.astype(np.float32), (0, pad)).reshape(-1, 128)
    )
    # XLA and numpy f32 division may differ by 1 ulp exactly at rounding
    # ties -> allow |q - ref| <= 1 on a vanishing fraction of elements.
    diff = np.abs(np.asarray(q).astype(np.int32) - ref_q.astype(np.int32))
    assert diff.max() <= 1
    assert (diff != 0).mean() < 1e-3
    np.testing.assert_allclose(np.asarray(s), ref_s, rtol=1e-6)
    back = np.asarray(dequantize(q, s, n=n))
    ref_back = dequantize_ref(ref_q, ref_s).reshape(-1)[:n]
    scale_full = np.repeat(ref_s, 128)[:n]
    assert np.abs(back - ref_back).max() <= scale_full.max() + 1e-6


def test_quantize_error_bound():
    x = (RNG.standard_normal(10_000) * 100).astype(np.float32)
    q, s = quantize(jnp.asarray(x))
    back = np.asarray(dequantize(q, s, n=x.size))
    blocks = np.pad(x, (0, (-x.size) % 4096)).reshape(-1, 128)
    bound = (np.abs(blocks).max(1) / 127.0)[:, None] * 0.5 + 1e-7
    err = np.abs(np.pad(x, (0, (-x.size) % 4096)).reshape(-1, 128)
                 - np.pad(back, (0, (-x.size) % 4096)).reshape(-1, 128))
    assert (err <= bound + 1e-6).all()


def test_quantize_zero_block():
    x = np.zeros(256, np.float32)
    q, s = quantize(jnp.asarray(x))
    assert np.asarray(q).sum() == 0
    np.testing.assert_array_equal(np.asarray(dequantize(q, s, n=256)), x)


# ---------------------------------------------------------------------------
# delta
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [1, 1024, 9999, 65536])
def test_delta_matches_ref(n):
    a = RNG.integers(0, 2**32, n, dtype=np.uint32)
    b = a.copy()
    b[:: max(1, n // 17)] ^= 0xA5A5A5A5
    d, cnt = xor_delta(jnp.asarray(a), jnp.asarray(b))
    rd, rcnt = delta_ref(a, b)
    np.testing.assert_array_equal(np.asarray(d), rd)
    assert int(cnt) == rcnt


def test_delta_roundtrip():
    a = RNG.integers(0, 2**32, 5000, dtype=np.uint32)
    b = RNG.integers(0, 2**32, 5000, dtype=np.uint32)
    d, _ = xor_delta(jnp.asarray(a), jnp.asarray(b))
    back, _ = xor_delta(d, jnp.asarray(a))
    np.testing.assert_array_equal(np.asarray(back), b)


# ---------------------------------------------------------------------------
# fused precodec pass (delta + dirty counts + checksums, one launch)
# ---------------------------------------------------------------------------

CW = TILE  # smallest legal chunk: one (8, 128) u32 tile = 4 KiB


def _fused_vs_ref(cur, base, chunk_words):
    delta, meta = fused_precodec(
        jnp.asarray(cur), jnp.asarray(base), chunk_words=chunk_words
    )
    rd, rc, rg = fused_ref(cur, base, chunk_words)
    assert delta.shape == (rd.shape[0], chunk_words // 128, 128)
    np.testing.assert_array_equal(np.asarray(delta).reshape(rd.shape), rd)
    np.testing.assert_array_equal(np.asarray(meta)[:, 0], rc)
    np.testing.assert_array_equal(np.asarray(digests_from_meta(meta)), rg)
    np.testing.assert_array_equal(np.asarray(dirty_from_meta(meta)), rc > 0)


@pytest.mark.parametrize("n", [1, 1023, 1024, 4096, 4097, 12_305])
@pytest.mark.parametrize("chunk_words", [CW, 4 * CW])
def test_fused_matches_ref(n, chunk_words):
    cur = RNG.integers(0, 2**32, n, dtype=np.uint32)
    base = cur.copy()
    base[:: max(1, n // 13)] ^= 0xDEADBEEF
    _fused_vs_ref(cur, base, chunk_words)


def test_fused_all_clean_and_all_dirty():
    cur = RNG.integers(0, 2**32, 5 * CW, dtype=np.uint32)
    # all clean: every chunk digest still set, no chunk dirty
    _, meta = fused_precodec(jnp.asarray(cur), jnp.asarray(cur), chunk_words=CW)
    assert not np.asarray(dirty_from_meta(meta)).any()
    np.testing.assert_array_equal(
        np.asarray(digests_from_meta(meta)), chunk_digests_ref(cur, CW)
    )
    # all dirty (base = ~cur flips every word)
    _, meta = fused_precodec(jnp.asarray(cur), jnp.asarray(~cur), chunk_words=CW)
    assert np.asarray(dirty_from_meta(meta)).all()


def test_fused_digest_matches_per_chunk_checksum():
    # per-chunk digests restart indexing at the chunk boundary, so each one
    # must equal digest_ref of that chunk's words taken in isolation
    cur = RNG.integers(0, 2**32, 3 * CW + 100, dtype=np.uint32)
    _, meta = fused_precodec(
        jnp.asarray(cur), jnp.zeros(cur.shape, np.uint32), chunk_words=CW
    )
    got = np.asarray(digests_from_meta(meta))
    padded = np.pad(cur, (0, (-cur.size) % CW))
    for ci, chunk in enumerate(padded.reshape(-1, CW)):
        assert int(got[ci]) == digest_ref(chunk)


def test_fused_rejects_bad_chunk_words():
    w = jnp.zeros(CW, jnp.uint32)
    with pytest.raises(ValueError):
        fused_precodec(w, w, chunk_words=CW + 1)
    with pytest.raises(ValueError):
        fused_precodec(w, jnp.zeros(2 * CW, jnp.uint32), chunk_words=CW)


@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(min_value=1, max_value=3 * CW + 7),
    flips=st.integers(min_value=0, max_value=50),
    seed=st.integers(min_value=0, max_value=2**31),
)
def test_fused_fuzz(n, flips, seed):
    rng = np.random.default_rng(seed)
    cur = rng.integers(0, 2**32, n, dtype=np.uint32)
    base = cur.copy()
    if flips and n:
        base[rng.integers(0, n, flips)] ^= rng.integers(
            1, 2**32, flips, dtype=np.uint32
        )
    _fused_vs_ref(cur, base, CW)
