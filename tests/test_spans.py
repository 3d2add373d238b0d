"""Spans of the checkpoint path (``repro.core.spans``): recorded only
under a profiler session, linked to their parents across threads, the
same intervals in the ring and in the profiler's XSpace, and the source
of the telemetry fields."""
import glob
import shutil
import sys
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CheckpointConfig, CheckpointManager, spans, theta_like


def state_tree():
    return {"w": jnp.arange(48 * 1024, dtype=jnp.float32).reshape(48, 1024),
            "b": jnp.ones((4096,), jnp.bfloat16),
            "n": jnp.array(3, jnp.int32)}


def np_target():
    return jax.tree_util.tree_map(np.asarray, state_tree())


def manager(root, **kw):
    return CheckpointManager(CheckpointConfig(
        root=str(root), cluster=theta_like(2, 4), strategy="stripe_aligned", **kw))


def save_flush_restore(root):
    """A save with its asynchronous flush, then a restore from the PFS
    alone in a fresh manager.  Returns (SaveStats, ReadResult)."""
    mgr = manager(root)
    st = mgr.save(5, state_tree())
    mgr.wait()
    mgr.close()
    shutil.rmtree(root / "local")
    mgr = manager(root, async_flush=False)
    step, tree = mgr.restore(np_target(), step=5)
    assert step == 5
    jax.tree_util.tree_map(np.testing.assert_array_equal, tree, np_target())
    rr = mgr.last_read_result
    mgr.close()
    return st, rr


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One save, flush and PFS restore under ``jax.profiler.trace``: the
    ring's records, the XSpace's ``ckpt.*`` events and the telemetry."""
    from jax.profiler import ProfileData

    root = tmp_path_factory.mktemp("ckpt")
    out = tmp_path_factory.mktemp("trace")
    spans.clear()
    # A thread forced to hand the interpreter lock over between its clock
    # read and its annotation's start or end would carry that wait into
    # one interval only; no thread here needs the lock for long.
    interval = sys.getswitchinterval()
    sys.setswitchinterval(10.0)
    try:
        with jax.profiler.trace(str(out)):
            st, rr = save_flush_restore(root)
    finally:
        sys.setswitchinterval(interval)
    recs = spans.spans()
    assert spans.dropped() == 0
    events = []
    path = sorted(glob.glob(f"{out}/**/*.xplane.pb", recursive=True))[-1]
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events += [(e.name, e.duration_ns, dict(e.stats)) for e in line.events
                       if e.name.startswith("ckpt.")]
    spans.clear()
    return {"recs": recs, "events": events, "stats": st, "read": rr}


def by_name(recs, name):
    return [r for r in recs if r.name == name]


def test_nothing_recorded_without_a_profile(tmp_path):
    spans.clear()
    st, rr = save_flush_restore(tmp_path)
    assert spans.spans() == [] and spans.dropped() == 0
    assert st.encode_time > 0 and st.local_time > 0
    assert st.flush is not None and st.flush.duration > 0
    assert rr.duration > 0


# span -> (its parent's name, the prefix of the thread it runs on, count)
TABLE = {
    "ckpt.manager.open": (None, "MainThread", 2),
    "ckpt.save": (None, "MainThread", 1),
    "ckpt.save.encode": ("ckpt.save", "MainThread", 1),
    "ckpt.save.gather": ("ckpt.save.encode", "MainThread", 1),
    "ckpt.save.copy": ("ckpt.save.encode", "MainThread", 1),
    "ckpt.save.ranks": ("ckpt.save.encode", "MainThread", 1),
    "ckpt.save.crc": ("ckpt.save.ranks", "ckpt-local", 8),
    "ckpt.save.l1_write": ("ckpt.save.ranks", "ckpt-local", 8),
    "ckpt.save.l1_commit": ("ckpt.save", "MainThread", 1),
    "ckpt.save.enqueue": ("ckpt.save", "MainThread", 1),
    "ckpt.flush.queued": ("ckpt.save", "active-backend", 1),
    "ckpt.flush": ("ckpt.save", "active-backend", 1),
    "ckpt.flush.partial": ("ckpt.flush", "active-backend", 1),
    "ckpt.flush.write": ("ckpt.flush", "active-backend", 1),
    "ckpt.flush.open": ("ckpt.flush.write", "active-backend", 1),
    "ckpt.flush.extent": ("ckpt.flush.write", "ckpt-io", None),
    "ckpt.flush.l1_read": ("ckpt.flush.extent", "ckpt-io", None),
    "ckpt.flush.pwrite": ("ckpt.flush.extent", "ckpt-io", None),
    "ckpt.flush.fsync": ("ckpt.flush.write", "active-backend", 1),
    "ckpt.flush.commit": ("ckpt.flush", "active-backend", 1),
    "ckpt.restore": (None, "MainThread", 1),
    "ckpt.restore.read": ("ckpt.restore", "MainThread", 1),
    "ckpt.restore.crc": ("ckpt.restore", "ckpt-io", 8),
    "ckpt.restore.decode": ("ckpt.restore", "MainThread", 1),
}


@pytest.mark.parametrize("name", sorted(TABLE))
def test_span_recorded_with_parent_and_thread(traced, name):
    recs = traced["recs"]
    ids = {r.id: r for r in recs}
    parent, thread, count = TABLE[name]
    hits = by_name(recs, name)
    assert hits, name
    if count is not None:
        assert len(hits) == count
    for r in hits:
        assert r.thread.startswith(thread), (name, r.thread)
        assert r.t1_ns >= r.t0_ns
        if parent is None:
            assert r.parent is None
        else:
            assert ids[r.parent].name == parent
        if name != "ckpt.manager.open":
            assert r.attrs["step"] == 5


def test_flush_extents_match_the_flush(traced):
    recs = traced["recs"]
    flush = by_name(recs, "ckpt.flush")[0]
    extents = by_name(recs, "ckpt.flush.extent")
    assert len(extents) == flush.attrs["extents"] == len(by_name(recs, "ckpt.flush.pwrite"))
    assert sum(e.attrs["bytes"] for e in extents) == flush.attrs["bytes"]
    # one data file (stripe_aligned), the manifest and the flush journal
    assert flush.attrs["files"] == 3
    assert by_name(recs, "ckpt.flush.open")[0].attrs["files"] == 1
    assert {e.thread for e in extents} != {flush.thread}


def test_save_counts(traced):
    recs, st = traced["recs"], traced["stats"]
    root = by_name(recs, "ckpt.save")[0]
    assert root.attrs["raw_bytes"] == st.raw_bytes
    assert root.attrs["stored_bytes"] == st.stored_bytes
    gather = by_name(recs, "ckpt.save.gather")[0]
    assert gather.attrs == {"step": 5, "leaves": 3, "bytes": st.raw_bytes}
    assert by_name(recs, "ckpt.save.ranks")[0].attrs["ranks"] == 8
    assert sum(r.attrs["bytes"] for r in by_name(recs, "ckpt.save.crc")) == st.stored_bytes
    restore = by_name(recs, "ckpt.restore")[0]
    assert restore.attrs["level"] == "pfs"
    read = by_name(recs, "ckpt.restore.read")[0]
    assert read.attrs["bytes"] == traced["read"].bytes_read
    assert read.attrs["in_place"] == 1  # codec none: read into the stream


def test_every_span_once_in_the_xspace(traced):
    recs, events = traced["recs"], traced["events"]
    by_id = {}
    for name, dur, stats in events:
        assert "id" in stats, name
        assert stats["id"] not in by_id, "an id appears twice"
        by_id[stats["id"]] = (name, dur, stats)
    assert set(by_id) == {r.id for r in recs}
    for r in recs:
        name, dur, stats = by_id[r.id]
        assert name == r.name
        assert stats.get("parent", 0) == (r.parent or 0)
        ring = r.t1_ns - r.t0_ns
        assert abs(dur - ring) <= max(50_000, 0.02 * ring), (name, dur, ring)


@pytest.mark.parametrize("field,name", [
    ("encode_time", "ckpt.save.encode"),
    ("local_time", "ckpt.save.l1_commit"),
    ("flush.duration", "ckpt.flush.write"),
    ("read.duration", "ckpt.restore.read"),
])
def test_fields_are_their_spans(traced, field, name):
    values = {"encode_time": traced["stats"].encode_time,
              "local_time": traced["stats"].local_time,
              "flush.duration": traced["stats"].flush.duration,
              "read.duration": traced["read"].duration}
    (r,) = by_name(traced["recs"], name)
    assert values[field] == (r.t1_ns - r.t0_ns) / 1e9


def test_ring_drops_the_oldest_and_counts(monkeypatch):
    monkeypatch.setattr(spans, "_ring", deque(maxlen=4))
    monkeypatch.setattr(spans, "RING", 4)
    monkeypatch.setattr(spans, "_capturing", lambda: True)
    spans.clear()
    for i in range(7):
        with spans.span("ckpt.probe", i=i):
            pass
    assert [r.attrs["i"] for r in spans.spans()] == [3, 4, 5, 6]
    assert spans.dropped() == 3
    spans.clear()
    assert spans.spans() == [] and spans.dropped() == 0


def test_begin_ends_on_another_thread_and_inherits_step(tmp_path):
    import threading

    spans.clear()
    with jax.profiler.trace(str(tmp_path)):
        with spans.span("ckpt.outer", step=9) as outer:
            waiting = spans.begin("ckpt.wait", parent=outer)
            with spans.span("ckpt.inner") as inner:
                assert spans.current() is inner
        t = threading.Thread(target=waiting.end, name="taker")
        t.start()
        t.join()
    recs = {r.name: r for r in spans.spans()}
    assert recs["ckpt.wait"].thread == "taker"
    assert recs["ckpt.wait"].parent == recs["ckpt.outer"].id
    assert recs["ckpt.inner"].parent == recs["ckpt.outer"].id
    assert recs["ckpt.inner"].attrs["step"] == recs["ckpt.wait"].attrs["step"] == 9
    assert spans.current() is None
    assert waiting.seconds > 0 and inner.seconds > 0
    spans.clear()
