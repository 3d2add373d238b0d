"""Chunk-framed codec pipeline: round trips, chunk-granular delta,
corruption attribution, legacy manifests, partial restore under
compression, thread-local compressor reuse, vectorized dequantize.

The equivalence contract differs from tests/test_save_phase.py: with
chunk framing the *stored* bytes legitimately differ from the seed
whole-blob codecs, so equivalence is at the raw-stream level — chunked
encode -> decode must reproduce exactly the bytes
``encode_blob_reference`` -> ``decode_blob_reference`` does (and both
must reproduce the pytree).  Whole-blob byte-identity is pinned by
``chunk_size=0`` in the older suite.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CheckpointConfig,
    CheckpointManager,
    ChunkTable,
    Manifest,
    theta_like,
)
from repro.core.plan import merge_intervals
from repro.core.serialize import (
    CHUNK_BASE,
    CHUNK_DELTA,
    CHUNK_RAW,
    decode_state,
    decode_stream,
    default_codec_impl,
    encode_state,
)
from repro.core.serialize_ref import encode_state_reference

CODECS = ["none", "zstd", "zstd+delta"]


def state_tree(step=0, scale=1):
    return {
        "params": {
            "w": jnp.arange(3000 * scale, dtype=jnp.float32).reshape(-1, 50) + step,
            "b": jnp.full((64,), step, jnp.bfloat16),
        },
        "opt": {"mu": jnp.ones((40, 50), jnp.float32) * step,
                "count": jnp.array(step, jnp.int32)},
    }


def np_target(scale=1):
    return jax.tree_util.tree_map(np.asarray, state_tree(scale=scale))


def assert_tree_equal(a, b):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x), np.asarray(y)),
        a, b,
    )


# ---------------------------------------------------------------------------
# raw-stream equivalence: chunked encode/decode == whole-blob reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("codec", ["zstd", "zstd+delta"])
@pytest.mark.parametrize("chunk_size", [64, 1 << 12, 1 << 20])
def test_chunked_roundtrip_matches_reference_decode(codec, chunk_size):
    """The acceptance bar: chunked encode -> decode is byte-identical to
    the seed whole-blob reference pipeline's decode (both equal the
    original stream), across a delta chain."""
    c = theta_like(3, 2)
    prev_fast = prev_ref = None
    for step in (1, 2, 3):
        tree = state_tree(step)
        fast = encode_state(step, tree, c, codec=codec, base=prev_fast,
                            chunk_size=chunk_size)
        ref = encode_state_reference(step, tree, c, codec=codec, base=prev_ref)
        assert bytes(fast.stream) == bytes(ref.stream)
        assert fast.manifest.base_step == ref.manifest.base_step
        # raw/leaf bookkeeping identical; only the framing differs
        assert fast.manifest.leaves == ref.manifest.leaves
        assert [(r.offset, r.raw_size) for r in fast.manifest.ranks] == \
               [(r.offset, r.raw_size) for r in ref.manifest.ranks]
        base_stream = (
            bytes(prev_fast.stream) if fast.manifest.base_step is not None else None
        )
        got = decode_state(
            fast.manifest, fast.blobs, np_target(), base_stream=base_stream
        )
        ref_got = decode_state(
            ref.manifest, ref.blobs, np_target(),
            base_stream=bytes(prev_ref.stream) if ref.manifest.base_step is not None else None,
        )
        assert_tree_equal(got, ref_got)
        assert_tree_equal(got, tree)
        prev_fast, prev_ref = fast, ref


@pytest.mark.parametrize("codec", CODECS)
@pytest.mark.parametrize("chunk_size", [128, 1 << 12])
@pytest.mark.parametrize("geom", [(1, 1), (3, 2), (4, 4)])
def test_manager_roundtrip_matrix(tmp_path, codec, chunk_size, geom):
    """Full-manager round trip over codec x chunk size x world size:
    save a delta chain, restore from PFS and from L1."""
    n, p = geom
    root = tmp_path / f"{codec}-{chunk_size}-{n}x{p}"
    mgr = CheckpointManager(
        CheckpointConfig(
            root=str(root), cluster=theta_like(n, p), strategy="stripe_aligned",
            codec=codec, chunk_size=chunk_size, delta_every=3,
            async_flush=False,
        )
    )
    for s in (1, 2, 3):
        mgr.save(s, state_tree(s))
    assert not mgr.flush_errors
    mgr._l0 = None
    mgr._last_full = None
    step, got = mgr.restore(np_target())          # PFS
    assert step == 3
    assert_tree_equal(got, state_tree(3))
    import shutil

    shutil.rmtree(mgr.pfs_dir)
    mgr.pfs_dir.mkdir()
    mgr._man_cache.clear()
    step, got = mgr.restore(np_target())          # L1
    assert step == 3
    assert_tree_equal(got, state_tree(3))
    mgr.close()


# ---------------------------------------------------------------------------
# chunk-granular delta
# ---------------------------------------------------------------------------


def test_delta_skips_clean_chunks_and_roundtrips():
    c = theta_like(2, 2)
    chunk = 256
    base_tree = {"x": np.zeros(1 << 15, np.uint8)}
    base = encode_state(1, base_tree, c, codec="zstd+delta", chunk_size=chunk)
    # mutate a single narrow region: only the chunks covering it go dirty
    t2 = {"x": base_tree["x"].copy()}
    t2["x"][5000:5100] = 7
    enc = encode_state(2, t2, c, codec="zstd+delta", base=base, chunk_size=chunk)
    tab = enc.manifest.chunks
    flags = tab.flags
    n_base = int(((flags & CHUNK_BASE) != 0).sum())
    n_dirty = len(tab) - n_base
    assert n_dirty <= 2                      # the mutation spans <= 2 chunks
    assert n_base >= len(tab) - 2
    stored = sum(r.stored_size for r in enc.manifest.ranks)
    full = sum(r.stored_size for r in base.manifest.ranks)
    assert stored < full / 4                 # toward the differential ideal
    got = decode_state(
        enc.manifest, enc.blobs, {"x": np.empty(1 << 15, np.uint8)},
        base_stream=bytes(base.stream),
    )
    np.testing.assert_array_equal(got["x"], t2["x"])


def test_delta_identical_state_stores_zero_payload_bytes():
    """A step with no changes at all stores nothing but the manifest:
    every chunk is a base reference."""
    c = theta_like(2, 1)
    tree = {"x": np.arange(4096, dtype=np.int64)}
    base = encode_state(1, tree, c, codec="zstd+delta", chunk_size=512)
    enc = encode_state(2, tree, c, codec="zstd+delta", base=base, chunk_size=512)
    assert ((enc.manifest.chunks.flags & CHUNK_BASE) != 0).all()
    assert sum(r.stored_size for r in enc.manifest.ranks) == 0
    got = decode_state(
        enc.manifest, enc.blobs, {"x": np.empty(4096, np.int64)},
        base_stream=bytes(base.stream),
    )
    np.testing.assert_array_equal(got["x"], tree["x"])


@pytest.mark.parametrize(
    "strategy", ["file_per_process", "posix", "mpiio", "stripe_aligned", "gio_sync"]
)
def test_zero_byte_delta_step_flushes_and_restores(tmp_path, strategy):
    """An unchanged step stores 0 bytes per rank; every strategy must
    plan/flush/restore that degenerate (empty-rank) geometry, including
    partial restore, which then reads nothing but the base's chunks."""
    state = {"x": np.arange(8192, dtype=np.float32)}
    mgr = CheckpointManager(
        CheckpointConfig(
            root=str(tmp_path), cluster=theta_like(2, 2), strategy=strategy,
            codec="zstd+delta", chunk_size=512, delta_every=4,
            async_flush=False,
        )
    )
    mgr.save(1, state)
    st = mgr.save(2, state)
    assert not mgr.flush_errors
    assert st.stored_bytes == 0
    mgr._l0 = None
    mgr._last_full = None
    step, got = mgr.restore({"x": np.empty(8192, np.float32)})
    assert step == 2
    np.testing.assert_array_equal(got["x"], state["x"])
    s2, leaves = mgr.restore_leaves(["['x']"], step=2)
    assert s2 == 2
    np.testing.assert_array_equal(leaves["['x']"], state["x"])
    mgr.close()


def test_delta_mutated_base_produces_delta_or_raw_chunks():
    """Dirty chunks carry CHUNK_DELTA (XOR compressed) or CHUNK_RAW —
    never a silent stale base reference."""
    rng = np.random.default_rng(0)
    c = theta_like(1, 2)
    base_tree = {"x": rng.integers(0, 256, 1 << 14, np.uint8)}
    base = encode_state(1, base_tree, c, codec="zstd+delta", chunk_size=1024)
    t2 = {"x": rng.integers(0, 256, 1 << 14, np.uint8)}  # fully different
    enc = encode_state(2, t2, c, codec="zstd+delta", base=base, chunk_size=1024)
    tab = enc.manifest.chunks
    assert not ((tab.flags & CHUNK_BASE) != 0).any()
    assert (((tab.flags & CHUNK_DELTA) != 0) | ((tab.flags & CHUNK_RAW) != 0)).all()
    got = decode_state(
        enc.manifest, enc.blobs, {"x": np.empty(1 << 14, np.uint8)},
        base_stream=bytes(base.stream),
    )
    np.testing.assert_array_equal(got["x"], t2["x"])


# ---------------------------------------------------------------------------
# corruption: attribution at chunk granularity + restore fallback
# ---------------------------------------------------------------------------


def test_corrupt_single_chunk_detected_and_attributed():
    c = theta_like(2, 2)
    enc = encode_state(1, state_tree(1), c, codec="zstd", chunk_size=512)
    tab = enc.manifest.chunks
    # flip one byte inside rank 1's second chunk payload
    row = int(tab.rank_starts[1]) + 1
    blob = bytearray(enc.blobs[1])
    blob[int(tab.stored_off[row])] ^= 0xFF
    blobs = list(enc.blobs)
    blobs[1] = bytes(blob)
    with pytest.raises(IOError, match="chunk"):
        decode_stream(enc.manifest, blobs)
    # intact blobs still decode
    decode_stream(enc.manifest, enc.blobs)


def test_corrupt_chunk_in_pfs_falls_back_to_l1(tmp_path):
    mgr = CheckpointManager(
        CheckpointConfig(
            root=str(tmp_path), cluster=theta_like(2, 2),
            strategy="stripe_aligned", codec="zstd", chunk_size=512,
            async_flush=False,
        )
    )
    mgr.save(1, state_tree(1))
    assert not mgr.flush_errors
    agg = mgr.pfs_dir / "step_00000001" / "aggregate.dat"
    data = bytearray(agg.read_bytes())
    data[len(data) // 2] ^= 0xFF
    agg.write_bytes(bytes(data))
    mgr._l0 = None
    step, got = mgr.restore(np_target())
    assert step == 1                       # served from intact L1
    assert_tree_equal(got, state_tree(1))
    mgr.close()


def test_partial_restore_flags_corrupt_chunk(tmp_path):
    """Chunk CRCs close the old sub-blob integrity blind spot: a
    partial restore that touches a damaged chunk refuses it (and falls
    back to the intact L1 copy)."""
    mgr = CheckpointManager(
        CheckpointConfig(
            root=str(tmp_path), cluster=theta_like(2, 2),
            strategy="stripe_aligned", codec="zstd", chunk_size=256,
            async_flush=False,
        )
    )
    mgr.save(1, state_tree(1))
    man = mgr._manifest_pfs(1)
    agg = mgr.pfs_dir / "step_00000001" / "aggregate.dat"
    data = bytearray(agg.read_bytes())
    data[:] = bytes(len(data))             # wipe the whole aggregate
    agg.write_bytes(bytes(data))
    mgr._l0 = None
    # direct PFS partial read must raise (chunk checksum), manager falls back
    with pytest.raises(IOError, match="chunk"):
        mgr._leaves_from(man, 1, ["['params']['w']"], pfs=True)
    step, got = mgr.restore_leaves(["['params']['w']"])
    assert step == 1
    np.testing.assert_array_equal(
        got["['params']['w']"], np.asarray(state_tree(1)["params"]["w"])
    )
    mgr.close()


# ---------------------------------------------------------------------------
# legacy (whole-blob) manifests still parse and restore
# ---------------------------------------------------------------------------


def test_legacy_manifest_fields_default_to_whole_blob():
    c = theta_like(2, 1)
    enc = encode_state(1, state_tree(1), c, codec="zstd", chunk_size=0)
    d = json.loads(enc.manifest.to_json())
    # what a pre-chunking writer produced: no framing fields at all
    for k in ("chunk_size", "chunks", "codec_impl"):
        d.pop(k, None)
    man = Manifest.from_json(json.dumps(d))
    assert man.chunk_size == 0 and man.chunks is None
    assert man.codec_impl == "zstd"        # legacy manifests were zstd-only


@pytest.mark.parametrize("codec", ["zstd", "zstd+delta"])
def test_legacy_whole_blob_checkpoint_restores(tmp_path, codec):
    """A checkpoint written with whole-blob framing whose manifests are
    stripped back to the legacy schema (no chunk fields) must still
    restore — from PFS and from L1."""
    mgr = CheckpointManager(
        CheckpointConfig(
            root=str(tmp_path), cluster=theta_like(2, 2),
            strategy="stripe_aligned", codec=codec, chunk_size=0,
            delta_every=3, async_flush=False,
        )
    )
    for s in (1, 2):
        mgr.save(s, state_tree(s))
    assert not mgr.flush_errors
    impl = default_codec_impl()
    for p in list(mgr.pfs_dir.glob("step_*/manifest.json")) + list(
        (mgr.root / "local" / "manifests").glob("step_*.json")
    ):
        d = json.loads(p.read_text())
        d.pop("chunk_size", None)
        d.pop("chunks", None)
        # keep the backend honest for this environment (legacy default
        # is zstd, which may not be importable here)
        d["codec_impl"] = impl
        p.write_text(json.dumps(d))
    mgr._man_cache.clear()
    mgr._l0 = None
    mgr._last_full = None
    step, got = mgr.restore(np_target())
    assert step == 2
    assert_tree_equal(got, state_tree(2))
    # partial restore takes the whole-blob legacy path
    step, leaves = mgr.restore_leaves(["['opt']['mu']"])
    assert step == 2
    np.testing.assert_array_equal(
        leaves["['opt']['mu']"], np.asarray(state_tree(2)["opt"]["mu"])
    )
    mgr.close()


# ---------------------------------------------------------------------------
# partial restore under compression reads only the covering chunks
# ---------------------------------------------------------------------------


def big_state(step=0):
    rng = np.random.default_rng(1)
    return {
        "small": np.full((64,), step, np.float32),
        "big": (rng.standard_normal(1 << 16).astype(np.float32) + step),
        "tail": np.arange(333, dtype=np.int16) + step,
    }


def test_partial_restore_compressed_reads_only_covering_chunks(tmp_path):
    mgr = CheckpointManager(
        CheckpointConfig(
            root=str(tmp_path), cluster=theta_like(2, 2),
            strategy="stripe_aligned", codec="zstd", chunk_size=1 << 12,
            async_flush=False,
        )
    )
    st = mgr.save(1, big_state(1))
    mgr._l0 = None
    step, got = mgr.restore_leaves(["['small']"])
    assert step == 1
    np.testing.assert_array_equal(got["['small']"], big_state(1)["small"])
    rr = mgr.last_read_result
    assert rr is not None and 0 < rr.bytes_read < st.stored_bytes / 4
    # a leaf spanning many chunks still round-trips exactly
    _, got = mgr.restore_leaves(["['big']", "['tail']"])
    np.testing.assert_array_equal(got["['big']"], big_state(1)["big"])
    np.testing.assert_array_equal(got["['tail']"], big_state(1)["tail"])
    mgr.close()


def test_partial_restore_delta_recurses_into_base_chunks(tmp_path):
    """Partial restore of a delta step: base-referencing chunks pull
    their ranges out of the *base* checkpoint without materializing the
    whole base stream; changed chunks decode from the delta payload."""
    mgr = CheckpointManager(
        CheckpointConfig(
            root=str(tmp_path), cluster=theta_like(2, 2),
            strategy="stripe_aligned", codec="zstd+delta", chunk_size=1 << 12,
            delta_every=4, async_flush=False,
        )
    )
    s1 = big_state(1)
    mgr.save(1, s1)
    s2 = {k: v.copy() for k, v in s1.items()}
    s2["small"][:] = 42          # dirty a narrow region only
    mgr.save(2, s2)
    man2 = mgr._manifest_pfs(2)
    assert man2.base_step == 1
    assert ((man2.chunks.flags & CHUNK_BASE) != 0).any()
    # drop the in-memory twins: force the on-disk recursive path
    mgr._l0 = None
    mgr._last_full = None
    step, got = mgr.restore_leaves(["['small']", "['big']"], step=2)
    assert step == 2
    np.testing.assert_array_equal(got["['small']"], s2["small"])
    np.testing.assert_array_equal(got["['big']"], s2["big"])
    mgr.close()


# ---------------------------------------------------------------------------
# plumbing: merge_intervals, ChunkTable invariants, arrival callback
# ---------------------------------------------------------------------------


def test_merge_intervals_unions_and_drops_empty():
    s, n = merge_intervals([10, 0, 5, 30, 12], [5, 3, 5, 0, 2])
    np.testing.assert_array_equal(s, [0, 5])         # [5,10)+[10,15)+[12,14)
    np.testing.assert_array_equal(n, [3, 10])        # merge; [30,30) dropped
    s, n = merge_intervals([], [])
    assert len(s) == 0 and len(n) == 0


def test_chunk_table_validate_rejects_bad_tiling():
    c = theta_like(1, 2)
    enc = encode_state(1, state_tree(1), c, codec="zstd", chunk_size=512)
    tab = enc.manifest.chunks
    tab.validate(enc.manifest.ranks)       # the real table passes
    broken = ChunkTable(
        tab.rank_starts, tab.raw_off + 1, tab.raw_len,
        tab.stored_off, tab.stored_len, tab.crc, tab.flags,
    )
    with pytest.raises(ValueError, match="tile"):
        broken.validate(enc.manifest.ranks)


def test_read_plan_on_request_fires_once_per_request(tmp_path):
    from repro.core.plan import FileLayout, build_read_plan
    from repro.core.storage import LocalStore, RealExecutor

    rng = np.random.default_rng(5)
    payload = rng.bytes(1 << 14)
    sdir = tmp_path / "pfs" / "step_00000001"
    sdir.mkdir(parents=True)
    (sdir / "agg.dat").write_bytes(payload)
    layout = FileLayout(
        file_names=["agg.dat"], files={"agg.dat": len(payload)},
        start=[0], size=[len(payload)], file_id=[0], file_offset=[0],
        total=len(payload),
    )
    # several requests, including a zero-size one (fires up front)
    rp = build_read_plan(layout, [0, 100, 4000, 50], [100, 300, 1 << 10, 0])
    ex = RealExecutor(tmp_path / "pfs", LocalStore(tmp_path / "local", 1),
                      io_threads=4)
    seen = []
    bufs, _ = ex.execute_read_plan(rp, 1, on_request=lambda i, b: seen.append(i))
    ex.close()
    assert sorted(seen) == [0, 1, 2, 3]
    for i, (a, s) in enumerate([(0, 100), (100, 300), (4000, 1 << 10), (50, 0)]):
        assert bytes(bufs[i]) == payload[a : a + s]


def test_thread_local_compressor_reuse():
    """One compressor per worker thread, not one per chunk call."""
    zstd = pytest.importorskip("zstandard")
    from concurrent.futures import ThreadPoolExecutor

    from repro.core import serialize as ser

    made = []
    real = zstd.ZstdCompressor

    # count through a factory, not a subclass: freeing instances of a
    # Python subclass of the C-backend ZstdCompressor (zstandard 0.25)
    # corrupts the heap and segfaults a later garbage collection
    def counting(*a, **k):
        made.append(1)
        return real(*a, **k)

    old = ser._zstd.ZstdCompressor
    ser._zstd.ZstdCompressor = counting
    # fresh thread-locals for the counting run
    old_tls = ser._codec_tls
    ser._codec_tls = type(old_tls)()
    try:
        with ThreadPoolExecutor(max_workers=4) as pool:
            list(pool.map(
                lambda i: ser._zstd_c(bytes(1024)), range(256)
            ))
        assert 1 <= sum(made) <= 4         # bounded by threads, not calls
    finally:
        ser._zstd.ZstdCompressor = old
        ser._codec_tls = old_tls


def test_thread_local_codecs_survive_thread_exit():
    """Per-thread zstd contexts are freed when their pool threads exit
    and are collected; bytes still round-trip across pools and rounds."""
    pytest.importorskip("zstandard")
    import gc
    from concurrent.futures import ThreadPoolExecutor

    from repro.core import serialize as ser

    rng = np.random.default_rng(7)
    payloads = [
        memoryview(rng.integers(0, 4, 1 << 12, dtype=np.uint8)) for _ in range(16)
    ]

    def roundtrip(p):
        z = ser.compress_bytes(p, "zstd")
        return ser.decompress_bytes(z, len(p), "zstd") == bytes(p)

    for _ in range(20):
        with ThreadPoolExecutor(max_workers=8) as pool:
            assert all(pool.map(roundtrip, payloads * 4))
        gc.collect()


# ---------------------------------------------------------------------------
# vectorized dequantize_tree == per-leaf kernel reference
# ---------------------------------------------------------------------------


def test_dequantize_tree_matches_reference():
    from concurrent.futures import ThreadPoolExecutor

    from repro.core.precodec import (
        dequantize_tree,
        dequantize_tree_reference,
        quantize_tree,
    )

    rng = np.random.default_rng(9)
    target = {
        "a": rng.standard_normal((64, 128)).astype(np.float32),
        "b": rng.standard_normal(5000).astype(np.float32) * 40,
        "small": np.float32(3.5),                     # below quant threshold
        "ints": np.arange(10, dtype=np.int32),        # not quantized
    }
    q = quantize_tree(target)
    ref = dequantize_tree_reference(q, target)
    fast = dequantize_tree(q, target)
    with ThreadPoolExecutor(max_workers=4) as pool:
        pooled = dequantize_tree(q, target, pool=pool)
    for k in target:
        np.testing.assert_array_equal(np.asarray(ref[k]), np.asarray(fast[k]))
        np.testing.assert_array_equal(np.asarray(ref[k]), np.asarray(pooled[k]))


# ---------------------------------------------------------------------------
# hypothesis sweep (optional dep, mirrors the other suites)
# ---------------------------------------------------------------------------

try:
    from hypothesis import HealthCheck, given, settings, strategies as hst

    _HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - optional test dep
    _HAVE_HYPOTHESIS = False


if _HAVE_HYPOTHESIS:

    @settings(
        max_examples=20, deadline=None,
        suppress_health_check=[HealthCheck.function_scoped_fixture],
    )
    @given(
        codec=hst.sampled_from(CODECS),
        chunk_size=hst.sampled_from([0, 64, 257, 1 << 12]),
        nodes=hst.integers(1, 4),
        ppn=hst.integers(1, 3),
        n_elems=hst.integers(0, 5000),
        dirty_frac=hst.floats(0, 1),
        seed=hst.integers(0, 2**31 - 1),
    )
    def test_codec_roundtrip_sweep(
        codec, chunk_size, nodes, ppn, n_elems, dirty_frac, seed
    ):
        rng = np.random.default_rng(seed)
        c = theta_like(nodes, ppn)
        t1 = {
            "a": rng.integers(0, 256, n_elems, np.uint8),
            "b": rng.standard_normal(max(1, n_elems // 9)).astype(np.float32),
        }
        e1 = encode_state(1, t1, c, codec=codec, chunk_size=chunk_size)
        tgt = {k: np.empty_like(v) for k, v in t1.items()}
        got = decode_state(e1.manifest, e1.blobs, tgt)
        for k in t1:
            np.testing.assert_array_equal(got[k], t1[k])
        # a second (possibly delta) step mutating a random fraction
        t2 = {k: v.copy() for k, v in t1.items()}
        if n_elems:
            k = int(n_elems * dirty_frac)
            t2["a"][:k] = rng.integers(0, 256, k, np.uint8)
        e2 = encode_state(2, t2, c, codec=codec, base=e1, chunk_size=chunk_size)
        base_stream = (
            bytes(e1.stream) if e2.manifest.base_step is not None else None
        )
        man2 = Manifest.from_json(e2.manifest.to_json())   # survives JSON
        got2 = decode_state(
            man2, e2.blobs, tgt, base_stream=base_stream
        )
        for k in t2:
            np.testing.assert_array_equal(got2[k], t2[k])
