"""Whole-tree PFS restore of a codec-``none`` checkpoint, in place.

The stored blobs of such a step are the raw stream, so the restore reads
each one straight into its slice of one stream buffer (``preadv``) and
hands out the leaves as views of that buffer: the bytes are written once,
by the read.  Every other manifest (zstd, delta, hedged reads, partial
restores) keeps the allocating read and the copying decode, and so do
the L0 and L1 levels, whose streams the restore does not own.
"""
import glob
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CheckpointConfig, CheckpointManager, spans, theta_like
from repro.core.serialize import decode_state

ALL_STRATEGIES = ["file_per_process", "posix", "mpiio", "stripe_aligned", "gio_sync"]

# (save geometry, restore geometry): N ranks saved, M readers
GEOMETRIES = [((4, 2), (3, 1)), ((2, 3), (5, 2))]


def mixed_tree(step=0):
    """Leaves in flatten order: an aligned float32, an odd-length int8,
    then float32, bfloat16 and float64 leaves the int8 leaves misaligned
    in the stream, a scalar and an empty leaf."""
    return {
        "a_f32": jnp.arange(600, dtype=jnp.float32) + step,
        "b_i8": np.arange(7, dtype=np.int8) + np.int8(step),
        "c_f32": np.linspace(-1, 1, 333, dtype=np.float32) * (step + 1),
        "d_bf16": jnp.full((11,), 1.25 + step, jnp.bfloat16),
        "e_f64": np.arange(45, dtype=np.float64) / (step + 3),
        "f_i32": np.int32(step),
        "g_empty": np.empty((0, 3), np.float32),
    }


def np_target(step=0):
    return jax.tree_util.tree_map(np.asarray, mixed_tree(step))


def manager(root, cluster=(4, 2), **kw):
    kw.setdefault("strategy", "stripe_aligned")
    kw.setdefault("async_flush", False)
    return CheckpointManager(CheckpointConfig(
        root=str(root), cluster=theta_like(*cluster), **kw))


def save_flushed(root, step=3, cluster=(4, 2), **kw):
    mgr = manager(root, cluster, **kw)
    mgr.save(step, mixed_tree(step))
    mgr.wait()
    assert not mgr.flush_errors
    mgr.close()


def fresh_pfs_restore(root, step=3, cluster=(4, 2), **kw):
    """Restore ``step`` from the PFS alone in a fresh manager.  Returns
    (tree, ReadResult, manager); the caller closes the manager."""
    shutil.rmtree(root / "local", ignore_errors=True)
    mgr = manager(root, cluster, **kw)
    got, tree = mgr.restore(np_target(), step=step)
    assert got == step
    return tree, mgr.last_read_result, mgr


def assert_bit_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        assert x.tobytes() == y.tobytes()


def owner(a):
    """The array that owns ``a``'s memory."""
    while isinstance(a.base, np.ndarray):
        a = a.base
    return a


def data_file(root, step=3):
    files = sorted(glob.glob(str(root / "pfs" / f"step_{step:08d}" / "*.dat")))
    return max(files, key=os.path.getsize)


# ---------------------------------------------------------------------------
# same result as the copying decode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("strategy", ALL_STRATEGIES)
@pytest.mark.parametrize("geoms", GEOMETRIES, ids=["4x2->3x1", "2x3->5x2"])
def test_in_place_restore_equals_decode_state(tmp_path, strategy, geoms):
    (n1, p1), (n2, p2) = geoms
    save_flushed(tmp_path, cluster=(n1, p1), strategy=strategy)
    tree, rr, mgr = fresh_pfs_restore(tmp_path, cluster=(n2, p2), strategy="posix")
    assert rr.in_place and rr.bytes_read > 0 and rr.n_readers <= n2
    man = mgr._manifest_pfs(3)
    blobs = [mgr.executor.read_rank_blob(man, 3, r) for r in range(man.world_size)]
    want = decode_state(man, blobs, np_target())
    mgr.close()
    assert_bit_equal(tree, want)
    assert_bit_equal(tree, np_target(3))


# ---------------------------------------------------------------------------
# the zero-copy bar
# ---------------------------------------------------------------------------


def test_pfs_restore_leaves_are_views_of_one_stream(tmp_path):
    """Twin of the save side's zero-copy test: every aligned leaf is a
    view of the one stream buffer the read filled; only the leaves the
    odd-length int8 leaf misaligned are copies."""
    save_flushed(tmp_path)
    tree, rr, mgr = fresh_pfs_restore(tmp_path)
    man = mgr._manifest_pfs(3)
    mgr.close()
    assert rr.in_place
    leaves = jax.tree_util.tree_leaves(tree)
    entries = man.leaves
    # an empty leaf is aligned wherever it starts
    aligned = [e.size == 0 or e.offset % np.dtype(e.dtype).itemsize == 0
               for e in entries]
    assert not all(aligned)  # the tree does exercise the copy
    stream = {id(owner(a)) for a, ok in zip(leaves, aligned) if ok}
    assert len(stream) == 1
    base = next(owner(a) for a, ok in zip(leaves, aligned) if ok)
    assert base.nbytes == man.total_raw_bytes
    for a, ok in zip(leaves, aligned):
        assert a.flags.aligned and a.flags.writeable
        assert (owner(a) is base) == ok


def test_in_place_counter_on_the_read_span(tmp_path):
    """``in_place`` on ``ckpt.restore.read``: 1 for the whole-tree PFS
    restore, 0 for a partial restore of the same step."""
    save_flushed(tmp_path)
    shutil.rmtree(tmp_path / "local")
    spans.clear()
    with jax.profiler.trace(str(tmp_path / "trace")):
        mgr = manager(tmp_path)
        mgr.restore(np_target(), step=3)
        mgr.restore_subtree(np.asarray(mixed_tree()["a_f32"]), "['a_f32']", step=3)
        mgr.close()
    reads = [r for r in spans.spans() if r.name == "ckpt.restore.read"]
    spans.clear()
    assert [r.attrs["in_place"] for r in reads] == [1, 0]


# ---------------------------------------------------------------------------
# integrity: the same failures as the copying path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("strategy", ["file_per_process", "stripe_aligned"])
def test_flipped_byte_fails_the_crc_and_l1_serves(tmp_path, strategy):
    save_flushed(tmp_path, strategy=strategy)
    path = data_file(tmp_path)
    data = bytearray(open(path, "rb").read())
    data[len(data) // 2] ^= 0xFF
    open(path, "wb").write(bytes(data))
    mgr = manager(tmp_path, strategy=strategy)
    with pytest.raises(IOError, match="checksum mismatch"):
        mgr._restore_from_pfs(3, np_target())
    assert mgr.last_read_result.in_place
    spans.clear()
    with jax.profiler.trace(str(tmp_path / "trace")):
        got, tree = mgr.restore(np_target(), step=3)  # L1 still present
    level = [r.attrs["level"] for r in spans.spans() if r.name == "ckpt.restore"]
    spans.clear()
    mgr.close()
    assert got == 3 and level == ["local"]
    assert_bit_equal(tree, np_target(3))


def test_truncated_data_file_raises_short_read(tmp_path):
    save_flushed(tmp_path)
    path = data_file(tmp_path)
    os.truncate(path, os.path.getsize(path) // 2)
    mgr = manager(tmp_path)
    with pytest.raises(IOError, match="short PFS read"):
        mgr._restore_from_pfs(3, np_target())
    mgr.close()


# ---------------------------------------------------------------------------
# restored leaves are the caller's own, from every level
# ---------------------------------------------------------------------------


def _mutate_all(tree):
    for a in jax.tree_util.tree_leaves(tree):
        assert a.flags.writeable
        a[...] = 0


@pytest.mark.parametrize("level", ["l0", "local", "pfs"])
def test_mutating_a_restored_tree_reaches_no_other(tmp_path, level):
    mgr = manager(tmp_path)
    mgr.save(3, mixed_tree(3))
    mgr.wait()
    if level == "l0":
        twin = bytes(mgr._l0.stream)
    else:
        mgr.close()
        if level == "local":
            shutil.rmtree(tmp_path / "pfs")
        else:
            shutil.rmtree(tmp_path / "local")
        mgr = manager(tmp_path)
    _, first = mgr.restore(np_target(), step=3)
    if level == "pfs":
        assert mgr.last_read_result.in_place
    # leaves are disjoint: zeroing one leaves the others as saved
    leaves = jax.tree_util.tree_leaves(first)
    want = jax.tree_util.tree_leaves(np_target(3))
    leaves[0][...] = -1
    for a, w in zip(leaves[1:], want[1:]):
        assert a.tobytes() == w.tobytes()
    _mutate_all(first)
    _, second = mgr.restore(np_target(), step=3)
    if level == "l0":
        assert bytes(mgr._l0.stream) == twin
    mgr.close()
    assert_bit_equal(second, np_target(3))


# ---------------------------------------------------------------------------
# every other manifest keeps the allocating path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case", ["zstd", "zstd+delta", "hedged", "subtree"])
def test_other_restores_keep_the_allocating_read(tmp_path, case):
    kw = {}
    if case in ("zstd", "zstd+delta"):
        kw["codec"] = case
    if case == "hedged":
        kw["hedged_reads"] = True
    mgr = manager(tmp_path, **kw)
    mgr.save(2, mixed_tree(2))
    mgr.save(3, mixed_tree(3))  # a delta of step 2 under zstd+delta
    mgr.wait()
    mgr.close()
    if case == "zstd+delta":
        mgr = manager(tmp_path, **kw)
        assert mgr._manifest_pfs(3).base_step == 2
        mgr.close()
    if case != "hedged":  # the hedge needs a surviving L1 copy to read
        shutil.rmtree(tmp_path / "local")
    mgr = manager(tmp_path, **kw)
    if case == "subtree":
        _, got = mgr.restore_subtree(np.asarray(mixed_tree()["e_f64"]), "['e_f64']",
                                     step=3)
        np.testing.assert_array_equal(got, np.asarray(mixed_tree(3)["e_f64"]))
    else:
        _, got = mgr.restore(np_target(), step=3)
        assert_bit_equal(got, np_target(3))
    rr = mgr.last_read_result
    mgr.close()
    assert rr is not None and rr.bytes_read > 0 and not rr.in_place
