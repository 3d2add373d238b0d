"""Device-resident pre-codec: staging equivalence, engine wiring, guards.

Everything runs in Pallas interpret mode on CPU; the host pre-codec +
serializer remain the executable reference spec, so every test here is a
byte-for-byte (or post-dequantize exact) comparison against that path.
"""
import json
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CheckpointConfig, CheckpointManager, theta_like
from repro.core.engine import UnsupportedPrecodecError
from repro.core.precodec import DevicePrecodec, quantize_tree
from repro.core.serialize import (
    chunk_aligned_sizes,
    decode_stream,
    encode_state,
    encode_state_staged,
    serialize_tree,
)

RNG = np.random.default_rng(99)


def mixed_state(step=0):
    return {
        "w": jnp.asarray(
            (RNG.standard_normal((64, 300)) * 3).astype(np.float32) + step
        ),
        "tiny": jnp.full((37,), 1.5 + step, jnp.float32),  # below quant floor
        "h": jnp.asarray(RNG.standard_normal((32, 256)).astype(np.float32) + step,
                         jnp.bfloat16),
        "i": jnp.asarray(RNG.integers(0, 100, 511), jnp.int32),
        "flag": jnp.asarray(RNG.random(65) < 0.5),
    }


def aligned_state(step=0):
    # every leaf a whole number of 4-byte words: staged through the word
    # assembly (4-byte bitcast, 2- and 1-byte packing), where mixed_state's
    # 65-byte flag sends the whole stream through the byte assembly
    return {
        "w": jnp.asarray(
            (RNG.standard_normal((64, 300)) * 3).astype(np.float32) + step
        ),
        "tiny": jnp.full((36,), 1.5 + step, jnp.float32),
        "h": jnp.asarray(RNG.standard_normal((32, 256)).astype(np.float32) + step,
                         jnp.bfloat16),
        "i8": jnp.asarray(RNG.integers(-128, 128, 1000), jnp.int8),
        "flag": jnp.asarray(RNG.random(64) < 0.5),
        "i": jnp.asarray(RNG.integers(0, 100, 511), jnp.int32),
    }


def bump(state, key="w", amt=0.25):
    state = dict(state)
    state[key] = state[key] + jnp.asarray(amt, state[key].dtype)
    return state


def host_stream(state, precodec):
    tree = quantize_tree(state) if precodec == "int8" else state
    return serialize_tree(tree)


def assert_tree_equal(a, b):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x), np.asarray(y)),
        a, b,
    )


# ---------------------------------------------------------------------------
# DevicePrecodec staging vs the host reference serializer
# ---------------------------------------------------------------------------


def check_stage_full(state, precodec):
    dev = DevicePrecodec(chunk_size=4096, precodec=precodec)
    bufs = dev.consume(dev.stage(1, state))
    stream, leaves = host_stream(state, precodec)
    assert bytes(bufs.stream) == bytes(stream)
    assert bufs.leaves == leaves
    assert bufs.base_step is None
    assert bool(bufs.mask.all())  # anchors are dirty everywhere by definition
    dev.close()


def check_stage_delta(s1, precodec):
    dev = DevicePrecodec(chunk_size=4096, precodec=precodec)
    b1 = dev.consume(dev.stage(1, s1))
    s2 = bump(s1)
    bufs = dev.consume(dev.stage(2, s2, base_step=1), base_stream=b1.stream)
    stream, _ = host_stream(s2, precodec)
    assert bytes(bufs.stream) == bytes(stream)
    assert bufs.base_step == 1
    mask = np.asarray(bufs.mask)
    assert 0 < mask.sum() < mask.size  # touched one leaf -> partial dirty set
    assert set(bufs.deltas) == set(np.flatnonzero(mask))
    dev.close()


@pytest.mark.parametrize("precodec", ["none", "int8"])
def test_stage_full_matches_host_serialize(precodec):
    check_stage_full(mixed_state(), precodec)


@pytest.mark.parametrize("precodec", ["none", "int8"])
def test_stage_full_aligned_matches_host_serialize(precodec):
    check_stage_full(aligned_state(), precodec)


@pytest.mark.parametrize("precodec", ["none", "int8"])
def test_stage_delta_matches_host_serialize(precodec):
    check_stage_delta(mixed_state(), precodec)


@pytest.mark.parametrize("precodec", ["none", "int8"])
def test_stage_delta_aligned_matches_host_serialize(precodec):
    check_stage_delta(aligned_state(), precodec)


def test_stage_base_miss_degrades_to_full():
    dev = DevicePrecodec(chunk_size=4096, precodec="none")
    s1 = mixed_state()
    dev.consume(dev.stage(1, s1))
    # ask for a base the device never staged -> silently re-anchors
    bufs = dev.consume(dev.stage(5, bump(s1), base_step=3))
    assert bufs.base_step is None
    assert bool(bufs.mask.all())
    dev.close()


def test_stage_rejects_wide_dtypes_without_x64():
    if jax.config.jax_enable_x64:
        pytest.skip("x64 enabled; narrow-on-transfer hazard absent")
    dev = DevicePrecodec(chunk_size=4096, precodec="none")
    with pytest.raises(ValueError, match="x64"):
        dev.stage(1, {"x": np.arange(8, dtype=np.int64)})
    dev.close()


# ---------------------------------------------------------------------------
# staged encode vs host encode_state (byte-for-byte)
# ---------------------------------------------------------------------------


def _staged_encode(dev, cluster, step, state, base_step, base_stream):
    staged = dev.stage(step, state, base_step=base_step)
    bufs = dev.consume(staged, base_stream=base_stream)
    enc = encode_state_staged(
        step, cluster,
        stream=bufs.stream, leaves=bufs.leaves, chunk_size=dev.chunk_size,
        base_step=bufs.base_step, dirty=bufs.mask, deltas=bufs.deltas,
        digests=bufs.digests,
    )
    return enc, bufs


def test_encode_staged_matches_host_encode(tmp_path):
    cluster = theta_like(2, 2)
    dev = DevicePrecodec(chunk_size=4096, precodec="none")
    s1, s2 = mixed_state(), None
    enc1, b1 = _staged_encode(dev, cluster, 1, s1, None, None)
    s2 = bump(s1, "h")
    enc2, _ = _staged_encode(dev, cluster, 2, s2, 1, b1.stream)

    stream1, _ = host_stream(s1, "none")
    sizes = chunk_aligned_sizes(len(bytes(stream1)), cluster.world_size, 4096)
    h1 = encode_state(1, s1, cluster, codec="zstd+delta",
                      chunk_size=4096, rank_sizes=sizes)
    stream2, _ = host_stream(s2, "none")
    h2 = encode_state(2, s2, cluster, codec="zstd+delta",
                      chunk_size=4096, base=h1, rank_sizes=sizes)

    for enc, h in ((enc1, h1), (enc2, h2)):
        assert [bytes(b) for b in enc.blobs] == [
            bytes(b) for b in h.blobs
        ]
        assert enc.manifest.base_step == h.manifest.base_step
        t, ht = enc.manifest.chunks, h.manifest.chunks
        for col in ("raw_off", "raw_len", "stored_off", "stored_len", "crc",
                    "flags"):
            np.testing.assert_array_equal(getattr(t, col), getattr(ht, col))
        assert t.digest is not None and ht.digest is None

    # digest-verified decode restores both steps exactly
    raw1 = decode_stream(enc1.manifest, [bytes(b) for b in enc1.blobs])
    raw2 = decode_stream(enc2.manifest, [bytes(b) for b in enc2.blobs],
                         base_stream=raw1)
    assert bytes(raw2) == bytes(stream2)
    dev.close()


def test_chunk_digest_corruption_detected():
    cluster = theta_like(1, 2)
    dev = DevicePrecodec(chunk_size=4096, precodec="none")
    enc, _ = _staged_encode(dev, cluster, 1, mixed_state(), None, None)
    enc.manifest.chunks.digest = enc.manifest.chunks.digest.copy()
    enc.manifest.chunks.digest[0] ^= 1
    with pytest.raises(IOError, match="digest mismatch"):
        decode_stream(enc.manifest, [bytes(b) for b in enc.blobs])
    dev.close()


def test_manifest_roundtrips_digest_column():
    cluster = theta_like(1, 2)
    dev = DevicePrecodec(chunk_size=4096, precodec="none")
    enc, _ = _staged_encode(dev, cluster, 1, mixed_state(), None, None)
    man2 = type(enc.manifest).from_json(enc.manifest.to_json())
    assert man2.chunks == enc.manifest.chunks
    np.testing.assert_array_equal(man2.chunks.digest, enc.manifest.chunks.digest)
    dev.close()


# ---------------------------------------------------------------------------
# CheckpointManager end-to-end: device path vs host twin
# ---------------------------------------------------------------------------


def _mgr(root, *, device, precodec="none", strategy="stripe_aligned"):
    return CheckpointManager(CheckpointConfig(
        root=str(root), cluster=theta_like(2, 2), strategy=strategy,
        codec="zstd+delta", chunk_size=4096, precodec=precodec,
        device_precodec=device, delta_every=3,
    ))


@pytest.mark.parametrize("precodec", ["none", "int8"])
def test_manager_device_matches_host(tmp_path, precodec):
    dm = _mgr(tmp_path / "dev", device=True, precodec=precodec)
    hm = _mgr(tmp_path / "host", device=False, precodec=precodec)
    s = mixed_state()
    for step in (1, 2, 3, 4, 5):
        dm.save(step, s)
        hm.save(step, s)
        s = bump(s, "w" if step % 2 else "h")
    dm.wait(); hm.wait()
    assert not dm.flush_errors and not hm.flush_errors
    tgt = jax.tree_util.tree_map(lambda l: np.zeros(l.shape, l.dtype),
                                 mixed_state())
    for step in (1, 2, 3, 4, 5):
        # same base chain and, post-dequantize, identical restored bytes
        assert (dm._manifest_local(step).base_step
                == hm._manifest_local(step).base_step)
        _, td = dm.restore(tgt, step)
        _, th = hm.restore(tgt, step)
        assert_tree_equal(td, th)
    assert dm._manifest_local(2).chunks.digest is not None
    assert hm._manifest_local(2).chunks.digest is None
    dm.close(); hm.close()


def test_manager_stage_overlap(tmp_path):
    mgr = _mgr(tmp_path, device=True)
    s = mixed_state()
    assert mgr.stage(1, s)  # staged while "compute" would run
    stats = mgr.save(1, s)  # consumes the staged handle
    assert stats.stage_s > 0.0 and stats.stage_wait_s >= 0.0
    s2 = bump(s)
    stats2 = mgr.save(2, s2)  # no stage() first -> stages synchronously
    assert stats2.stage_s > 0.0
    mgr.wait()
    tgt = jax.tree_util.tree_map(lambda l: np.zeros(l.shape, l.dtype), s)
    _, out = mgr.restore(tgt, 2)
    assert_tree_equal(out, s2)
    mgr.close()


def test_manager_stage_noop_when_disabled(tmp_path):
    mgr = _mgr(tmp_path, device=False)
    assert mgr.stage(1, mixed_state()) is False
    mgr.close()


def test_device_precodec_config_validation(tmp_path):
    mgr = CheckpointManager(CheckpointConfig(
        root=str(tmp_path), cluster=theta_like(1, 2), codec="zstd",
        device_precodec=True,
    ))
    with pytest.raises(ValueError, match="zstd\\+delta"):
        mgr.save(1, mixed_state())
    mgr.close()
    mgr = CheckpointManager(CheckpointConfig(
        root=str(tmp_path), cluster=theta_like(1, 2), codec="zstd+delta",
        chunk_size=1 << 20 | 512, device_precodec=True,
    ))
    with pytest.raises(ValueError, match="multiple"):
        mgr.save(1, mixed_state())
    mgr.close()


# ---------------------------------------------------------------------------
# satellite a: precodec change invalidates the delta chain
# ---------------------------------------------------------------------------


def test_precodec_change_reanchors_chain(tmp_path):
    mgr = CheckpointManager(CheckpointConfig(
        root=str(tmp_path), cluster=theta_like(2, 2), codec="zstd+delta",
        chunk_size=4096, precodec="none", delta_every=10,
    ))
    s = mixed_state()
    mgr.save(1, s)
    mgr.save(2, bump(s))
    assert mgr._manifest_local(2).base_step == 1
    mgr.cfg.precodec = "int8"
    mgr.save(3, bump(s, "h"))  # stream layout changed -> must re-anchor
    assert mgr._manifest_local(3).base_step is None
    mgr.save(4, bump(bump(s, "h")))
    assert mgr._manifest_local(4).base_step == 3  # chain resumes off new anchor
    mgr.wait()
    tgt = jax.tree_util.tree_map(lambda l: np.zeros(l.shape, l.dtype),
                                 mixed_state())
    mgr.restore(tgt, 4)  # int8 restore decodes through the new anchor
    mgr.close()


def test_delta_with_mismatched_base_precodec_rejected(tmp_path):
    mgr = CheckpointManager(CheckpointConfig(
        root=str(tmp_path), cluster=theta_like(1, 2), codec="zstd+delta",
        chunk_size=4096, precodec="none", delta_every=10,
    ))
    s = mixed_state()
    mgr.save(1, s)
    mgr.save(2, bump(s))
    mgr.wait()
    assert mgr._manifest_local(2).base_step == 1
    # tamper: rewrite the base manifest as if it came from another precodec
    mp = mgr.root / "local" / "manifests" / "step_00000001.json"
    obj = json.loads(mp.read_text())
    obj["precodec"] = "int8"
    mp.write_text(json.dumps(obj))
    mgr._man_cache.clear()
    mgr._l0 = None
    mgr._last_full = None
    tgt = jax.tree_util.tree_map(lambda l: np.zeros(l.shape, l.dtype), s)
    with pytest.raises(IOError, match="chain is invalid"):
        mgr._restore_from_local(2, tgt)
    mgr.close()


# ---------------------------------------------------------------------------
# satellite f: partial restore of int8 manifests fails at plan time
# ---------------------------------------------------------------------------


def test_partial_restore_int8_raises_before_io(tmp_path):
    mgr = CheckpointManager(CheckpointConfig(
        root=str(tmp_path), cluster=theta_like(1, 2), codec="zstd+delta",
        chunk_size=4096, precodec="int8",
    ))
    s = mixed_state()
    mgr.save(1, s)
    mgr.wait()
    reads = []

    def counting(fn):
        def wrapped(*a, **k):
            reads.append(fn.__name__)
            return fn(*a, **k)
        return wrapped

    mgr.executor.execute_read_plan = counting(mgr.executor.execute_read_plan)
    mgr.local.read_blob = counting(mgr.local.read_blob)
    with pytest.raises(UnsupportedPrecodecError):
        mgr.restore_leaves(["['w']"], step=1)
    with pytest.raises(UnsupportedPrecodecError):
        mgr.restore_subtree({"w": np.zeros((64, 300), np.float32)},
                            prefix="", step=1)
    assert reads == []  # planning failed before any byte was fetched
    mgr.close()
