"""Columnar read planning: layout inversion, builder, validator, executor.

The read-side twin of tests/test_plan_arrays.py — every check compares
the array program against a brute-force byte-level simulation, and the
executor tests run against real files written by a real flush.
"""
import os

import numpy as np
import pytest

from repro.core import (
    CheckpointConfig,
    CheckpointManager,
    FileLayout,
    make_plan,
    theta_like,
)
from repro.core.plan import (
    PlanError,
    ReadColumns,
    assign_readers,
    build_read_plan,
    coalesce_read_columns,
    stored_space_offsets,
    validate_read_plan,
)

STRATEGIES = ["file_per_process", "posix", "mpiio", "stripe_aligned", "gio_sync"]

SIZES = [3_000_001, 1_500_000, 0, 2_000_000, 777, 4_000_000, 123_456, 999_999]


def layout_for(strategy, cluster=None, sizes=None):
    cluster = cluster or theta_like(4, 2)
    sizes = sizes if sizes is not None else SIZES
    plan = make_plan(strategy, cluster, sizes, chunk_stripes=4)
    return FileLayout.from_flush_plan(plan), sizes


def materialize(layout, stored):
    """Brute-force: write the stored space into per-file byte arrays."""
    files = {nm: bytearray(sz) for nm, sz in layout.files.items()}
    for st, sz, f, fo in zip(
        layout.start.tolist(), layout.size.tolist(),
        layout.file_id.tolist(), layout.file_offset.tolist(),
    ):
        files[layout.file_names[f]][fo : fo + sz] = stored[st : st + sz]
    return files


def execute_in_memory(rp, files):
    """Brute-force read-plan executor against in-memory file images."""
    bufs = [bytearray(int(n)) for n in rp.req_size.tolist()]
    r = rp.reads
    for f, fo, sz, q, do in zip(
        r.file_id.tolist(), r.file_offset.tolist(), r.size.tolist(),
        r.dst_req.tolist(), r.dst_offset.tolist(),
    ):
        bufs[q][do : do + sz] = files[rp.file_names[f]][fo : fo + sz]
    return bufs


# ---------------------------------------------------------------------------
# stored-space helpers
# ---------------------------------------------------------------------------


def test_stored_space_offsets():
    np.testing.assert_array_equal(
        stored_space_offsets([3, 0, 5]), np.array([0, 3, 3, 8])
    )
    np.testing.assert_array_equal(stored_space_offsets([]), np.array([0]))


def test_assign_readers_balanced():
    sizes = [100] * 64
    a = assign_readers(sizes, 4)
    assert a.min() == 0 and a.max() == 3
    assert (np.diff(a) >= 0).all()  # contiguous
    _, counts = np.unique(a, return_counts=True)
    assert counts.tolist() == [16, 16, 16, 16]
    # skewed sizes still balance by bytes, not by count
    sizes = [1000] + [1] * 10
    a = assign_readers(sizes, 2)
    assert a[0] == 0 and (a[1:] == 1).all()
    # degenerate cases
    assert assign_readers([0, 0], 3).tolist() == [0, 0]
    assert assign_readers([5], 1).tolist() == [0]


# ---------------------------------------------------------------------------
# layout inversion
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_layout_inverts_every_strategy(strategy):
    layout, sizes = layout_for(strategy)
    assert layout.total == sum(sizes)
    # tiling is enforced by the constructor; spot-check the columns too
    ends = layout.start + layout.size
    assert layout.start[0] == 0 and int(ends[-1]) == layout.total
    assert (layout.start[1:] == ends[:-1]).all()


def test_layout_rejects_gaps():
    with pytest.raises(PlanError):
        FileLayout(
            file_names=["a"], files={"a": 10},
            start=[0, 6], size=[5, 4], file_id=[0, 0], file_offset=[0, 6],
            total=10,
        )
    with pytest.raises(PlanError):  # overlap
        FileLayout(
            file_names=["a"], files={"a": 10},
            start=[0, 4], size=[5, 6], file_id=[0, 0], file_offset=[0, 4],
            total=10,
        )
    with pytest.raises(PlanError):  # wrong total
        FileLayout(
            file_names=["a"], files={"a": 10},
            start=[0], size=[5], file_id=[0], file_offset=[0], total=10,
        )


# ---------------------------------------------------------------------------
# builder vs brute force
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_read_plan_matches_bruteforce(strategy):
    layout, sizes = layout_for(strategy)
    rng = np.random.default_rng(7)
    stored = bytes(rng.integers(0, 256, layout.total, dtype=np.uint8))
    files = materialize(layout, stored)

    # random scattered requests, including zero-size and whole-space
    starts = np.sort(rng.integers(0, layout.total, 40)).astype(np.int64)
    sz = np.minimum(
        rng.integers(0, 200_000, 40), layout.total - starts
    ).astype(np.int64)
    starts = np.concatenate([starts, [0, 0]])
    sz = np.concatenate([sz, [0, layout.total]])
    readers = rng.integers(0, 3, len(starts))

    rp = build_read_plan(layout, starts, sz, readers)
    bufs = execute_in_memory(rp, files)
    for a, n, got in zip(starts.tolist(), sz.tolist(), bufs):
        assert bytes(got) == stored[a : a + n]


def test_full_restore_reads_match_blobs():
    layout, sizes = layout_for("stripe_aligned")
    rng = np.random.default_rng(3)
    stored = bytes(rng.integers(0, 256, layout.total, dtype=np.uint8))
    files = materialize(layout, stored)
    offsets = stored_space_offsets(sizes)
    rp = build_read_plan(
        layout, offsets[:-1], sizes, assign_readers(sizes, 3)
    )
    bufs = execute_in_memory(rp, files)
    for r, (a, n) in enumerate(zip(offsets[:-1].tolist(), sizes)):
        assert bytes(bufs[r]) == stored[a : a + n]


def test_coalescing_merges_contiguous_file_runs():
    # posix: the whole stored space is one contiguous file run, so a
    # whole-space request must collapse to a single ranged read.
    layout, sizes = layout_for("posix")
    rp = build_read_plan(layout, [0], [layout.total])
    assert rp.n_reads == 1
    assert rp.total_bytes == layout.total


def test_builder_rejects_bad_requests():
    layout, _ = layout_for("posix")
    with pytest.raises(PlanError):
        build_read_plan(layout, [-1], [10])
    with pytest.raises(PlanError):
        build_read_plan(layout, [0], [layout.total + 1])
    with pytest.raises(PlanError):
        build_read_plan(layout, [0, 1], [1])


# ---------------------------------------------------------------------------
# validator
# ---------------------------------------------------------------------------


def _valid_plan():
    layout, sizes = layout_for("mpiio")
    offsets = stored_space_offsets(sizes)
    rp = build_read_plan(layout, offsets[:-1], sizes, coalesce=False)
    return rp, layout


def test_validator_catches_dropped_read():
    rp, layout = _valid_plan()
    r = rp.reads
    rp.reads = r.take(np.arange(1, len(r)))
    with pytest.raises(PlanError, match="gap|cover"):
        validate_read_plan(rp, layout)


def test_validator_catches_wrong_file_offset():
    rp, layout = _valid_plan()
    rp.reads.file_offset[0] += 1
    with pytest.raises(PlanError):
        validate_read_plan(rp, layout)


def test_validator_catches_out_of_bounds_read():
    rp, layout = _valid_plan()
    rp.files = {nm: 1 for nm in rp.files}
    with pytest.raises(PlanError, match="past declared size"):
        validate_read_plan(rp, layout)


def test_validator_accepts_coalesced_multi_extent_reads():
    rp, layout = _valid_plan()
    validate_read_plan(rp, layout)
    coalesced = coalesce_read_columns(rp.reads)
    assert len(coalesced) <= len(rp.reads)
    rp.reads = coalesced
    validate_read_plan(rp, layout)  # spans are split at extent boundaries


def test_validator_catches_dst_overlap():
    layout, _ = layout_for("posix")
    rp = build_read_plan(layout, [0], [100])
    r = rp.reads
    rp.reads = ReadColumns(
        reader=np.concatenate([r.reader, r.reader]),
        file_id=np.concatenate([r.file_id, r.file_id]),
        file_offset=np.concatenate([r.file_offset, r.file_offset]),
        size=np.concatenate([r.size, r.size]),
        dst_req=np.concatenate([r.dst_req, r.dst_req]),
        dst_offset=np.concatenate([r.dst_offset, r.dst_offset]),
    )
    with pytest.raises(PlanError):
        validate_read_plan(rp, layout)


# ---------------------------------------------------------------------------
# real executor
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_executor_reads_real_flush(tmp_path, strategy):
    """Flush with one strategy, read back through an aggregated plan, and
    compare byte-for-byte with the encoded blobs."""
    import jax.numpy as jnp

    state = {"w": jnp.arange(40_000, dtype=jnp.float32),
             "b": jnp.ones((1000,), jnp.int32)}
    mgr = CheckpointManager(
        CheckpointConfig(root=str(tmp_path), cluster=theta_like(3, 2),
                         strategy=strategy, async_flush=False)
    )
    mgr.save(2, state)
    assert not mgr.flush_errors
    man = mgr._manifest_pfs(2)

    # one aggregated plan for all blobs == per-rank read_rank_blob
    by_rank = mgr._read_blobs_pfs(man, 2)
    for r in range(man.world_size):
        assert by_rank[r] == mgr.executor.read_rank_blob(man, 2, r)
        # and both equal the L1 ground truth
        node = r // man.procs_per_node
        assert by_rank[r] == mgr.local.read_blob(node, 2, r)
    assert mgr.last_read_result.bytes_read == man.total_stored_bytes
    mgr.close()


def _flushed_plan(tmp_path, strategy="stripe_aligned"):
    """A real flush of a 3x2 save and the aggregated plan reading every
    rank blob back onto 3 readers.  Returns (manager, ReadPlan)."""
    import jax.numpy as jnp

    state = {"w": jnp.arange(60_000, dtype=jnp.float32),
             "b": jnp.ones((1001,), jnp.int8)}
    mgr = CheckpointManager(
        CheckpointConfig(root=str(tmp_path), cluster=theta_like(3, 2),
                         strategy=strategy, async_flush=False)
    )
    mgr.save(2, state)
    assert not mgr.flush_errors
    man = mgr._manifest_pfs(2)
    sizes = np.asarray([r.stored_size for r in man.ranks], np.int64)
    rp = build_read_plan(man.file_layout(), man.stored_offsets()[: len(sizes)], sizes,
                         assign_readers(sizes, 3))
    return mgr, rp


def _destinations(rp):
    """One stream buffer and a view of it per request, in request order."""
    out = np.empty(int(rp.req_size.sum()), np.uint8)
    ends = np.cumsum(rp.req_size).tolist()
    view = memoryview(out)
    return out, [view[e - int(n) : e] for e, n in zip(ends, rp.req_size.tolist())]


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_read_into_caller_buffers_matches_allocating_path(tmp_path, strategy):
    mgr, rp = _flushed_plan(tmp_path, strategy)
    want, res_alloc = mgr.executor.execute_read_plan(rp, 2)
    out, dsts = _destinations(rp)
    seen = {}
    got, res = mgr.executor.execute_read_plan(
        rp, 2, into=dsts, on_request=lambda i, buf: seen.__setitem__(i, buf)
    )
    mgr.close()
    assert res.in_place and not res_alloc.in_place
    assert (res.bytes_read, res.n_reads) == (res_alloc.bytes_read, res_alloc.n_reads)
    assert out.tobytes() == b"".join(bytes(b) for b in want)
    assert sorted(seen) == list(range(rp.n_requests))
    for i, (g, w) in enumerate(zip(got, want)):
        assert bytes(g) == bytes(w)
        assert g.obj is out and seen[i].obj is out  # views, nothing copied


def test_read_into_rejects_destinations_of_the_wrong_size(tmp_path):
    mgr, rp = _flushed_plan(tmp_path)
    _, dsts = _destinations(rp)
    dsts[0] = dsts[0][1:]
    with pytest.raises(ValueError, match="destinations"):
        mgr.executor.execute_read_plan(rp, 2, into=dsts)
    mgr.close()


def test_read_into_ignored_with_a_hedge_armed(tmp_path):
    from repro.core.storage import HedgePolicy

    mgr, rp = _flushed_plan(tmp_path)
    want, _ = mgr.executor.execute_read_plan(rp, 2)
    out, dsts = _destinations(rp)
    out[:] = 0xAB
    got, res = mgr.executor.execute_read_plan(
        rp, 2, into=dsts, hedge=HedgePolicy(alt_read=lambda *a: None)
    )
    mgr.close()
    assert not res.in_place
    assert [bytes(b) for b in got] == [bytes(b) for b in want]
    assert (out == 0xAB).all()  # the caller's buffer was not written


def test_read_into_retry_rereads_the_same_slice(tmp_path, monkeypatch):
    """A transient error after a partial read into the slice: the retry
    rereads the whole slice, and the result is exact."""
    import errno
    import threading

    from repro.core import storage
    from repro.core.storage import RealExecutor, RetryPolicy

    mgr, rp = _flushed_plan(tmp_path)
    want, _ = mgr.executor.execute_read_plan(rp, 2)
    ex = RealExecutor(mgr.pfs_dir, mgr.local,
                      retry=RetryPolicy(base_delay=0.001, seed=0))
    real, lock, failed = os.preadv, threading.Lock(), []

    def flaky(fd, bufs, offset):
        with lock:
            first = not failed
            failed.append(offset)
        if first:
            half = len(bufs[0]) // 2
            bufs[0][:half] = b"\xee" * half  # garbage, then the error
            raise OSError(errno.EIO, "injected transient read error")
        return real(fd, bufs, offset)

    monkeypatch.setattr(storage.os, "preadv", flaky)
    out, dsts = _destinations(rp)
    got, res = ex.execute_read_plan(rp, 2, into=dsts)
    ex.close()
    mgr.close()
    assert res.in_place and res.io_retries == 1
    assert out.tobytes() == b"".join(bytes(b) for b in want)


def test_read_into_loops_over_short_preadv_returns(tmp_path, monkeypatch):
    """``preadv`` may return fewer bytes than asked (as it does past 2
    GiB): the read loops until the slice is full."""
    from repro.core import storage

    mgr, rp = _flushed_plan(tmp_path)
    want, _ = mgr.executor.execute_read_plan(rp, 2)
    real, calls = os.preadv, []

    def short(fd, bufs, offset):
        calls.append(offset)
        return real(fd, [bufs[0][:4096]], offset)  # at most 4 KiB a call

    monkeypatch.setattr(storage.os, "preadv", short)
    out, dsts = _destinations(rp)
    _, res = mgr.executor.execute_read_plan(rp, 2, into=dsts)
    mgr.close()
    assert len(calls) > 2 * res.n_reads
    assert out.tobytes() == b"".join(bytes(b) for b in want)


def test_read_into_truncated_file_raises_short_read(tmp_path):
    mgr, rp = _flushed_plan(tmp_path)
    sdir = mgr.executor.step_dir(2)
    for name in rp.file_names:
        os.truncate(sdir / name, os.path.getsize(sdir / name) // 2)
    _, dsts = _destinations(rp)
    with pytest.raises(IOError, match="short PFS read"):
        mgr.executor.execute_read_plan(rp, 2, into=dsts)
    mgr.close()


def test_partial_leaf_reads_only_leaf_bytes(tmp_path):
    """codec='none' partial restore touches exactly the leaves' bytes."""
    import jax.numpy as jnp

    state = {"big": jnp.zeros((1 << 16,), jnp.float32),
             "small": jnp.arange(100, dtype=jnp.int32)}
    mgr = CheckpointManager(
        CheckpointConfig(root=str(tmp_path), cluster=theta_like(2, 2),
                         strategy="stripe_aligned", async_flush=False)
    )
    mgr.save(1, state)
    step, got = mgr.restore_leaves(["['small']"])
    assert step == 1
    np.testing.assert_array_equal(got["['small']"], np.arange(100, dtype=np.int32))
    assert mgr.last_read_result.bytes_read == 400  # 100 x int32, nothing more
    mgr.close()


def test_restore_leaves_unknown_name_raises(tmp_path):
    import jax.numpy as jnp

    mgr = CheckpointManager(
        CheckpointConfig(root=str(tmp_path), cluster=theta_like(2, 1),
                         strategy="posix", async_flush=False)
    )
    mgr.save(1, {"x": jnp.zeros((8,), jnp.float32)})
    with pytest.raises(FileNotFoundError, match="leaves not in checkpoint"):
        mgr.restore_leaves(["['nope']"])
    mgr.close()


def test_assign_readers_property_sweep():
    """Property sweep (hypothesis-style; the library is not vendored,
    so cases come from seeded generators): for adversarial stored
    layouts and arbitrary N-writer -> M-reader geometries,
    ``assign_readers`` must (1) assign every blob, (2) keep assignments
    contiguous and monotonic, (3) stay within the midpoint balance
    bound — no reader carries more than an even byte share plus one
    largest blob.
    """
    rng = np.random.default_rng(0xA55E7)

    def cases():
        for case in range(120):
            n_readers = int(rng.integers(1, 20))
            n_blobs = int(rng.integers(1, 200))
            kind = case % 5
            if kind == 0:      # uniform
                sizes = rng.integers(0, 1 << 20, n_blobs)
            elif kind == 1:    # power-law skew
                sizes = (rng.pareto(0.5, n_blobs) * 4096).astype(np.int64)
            elif kind == 2:    # one giant among dust
                sizes = rng.integers(0, 64, n_blobs)
                sizes[rng.integers(0, n_blobs)] = 1 << 28
            elif kind == 3:    # many zeros (empty ranks)
                sizes = rng.integers(0, 4096, n_blobs)
                sizes[rng.random(n_blobs) < 0.5] = 0
            else:              # N -> M: more readers than blobs
                n_readers = int(rng.integers(n_blobs, n_blobs + 50))
                sizes = rng.integers(1, 1 << 16, n_blobs)
            yield sizes.astype(np.int64), n_readers
        yield np.zeros(17, np.int64), 5          # all-empty layout
        yield np.asarray([1], np.int64), 19      # single tiny blob, many readers

    for sizes, n_readers in cases():
        a = assign_readers(sizes, n_readers)
        ctx = (sizes[:8], n_readers)
        # (1) full coverage: one reader per blob, all in range
        assert len(a) == len(sizes), ctx
        assert a.min() >= 0 and a.max() < n_readers, ctx
        # (2) contiguous + monotonic: each reader owns one run of blobs
        assert (np.diff(a) >= 0).all(), ctx
        # (3) byte-balance bound from the midpoint rule
        per = np.zeros(n_readers, np.int64)
        np.add.at(per, a, sizes)
        assert per.sum() == sizes.sum(), ctx
        total = int(sizes.sum())
        if total == 0:
            assert (a == 0).all(), ctx
            continue
        bound = total / n_readers + int(sizes.max())
        assert per.max() <= bound + 1e-9, (per.max(), bound, ctx)
