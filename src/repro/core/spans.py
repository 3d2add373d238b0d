"""Spans: named, timed intervals of the checkpoint path.

``with span(name, parent=None, **attrs) as sp`` reads
``time.perf_counter_ns()`` at entry and exit and exposes ``sp.seconds``,
always: the telemetry fields (``SaveStats.encode_time``,
``FlushResult.duration``, ...) take their values from it.

The span is *recorded* only while a JAX profiler session captures
(``jax.profiler.trace``, xprof, TensorBoard), checked once at entry.
A recorded span is

* emitted as a ``jax.profiler.TraceAnnotation`` carrying ``id``,
  ``parent`` and its attributes, so it lands in the same XSpace as the
  device's ops, on the profiler's clock and the thread that did the
  work; and
* appended to a bounded in-memory ring: :func:`spans` returns a copy,
  :func:`dropped` counts the records the ring evicted.

The ring's clock reads come right after the annotation's start and
right before its end, so the two intervals agree to microseconds unless
the thread is made to wait (for the interpreter lock, or a CPU) in
between.

Counts (bytes, leaves, files, ...) are attributes, set at entry or with
``sp.set(...)`` before exit.  The parent is the innermost recorded span
open on the same thread, or the ``parent`` passed in when the work moved
to another thread.  A child without a ``step`` attribute inherits its
parent's, so the spans of one save or restore share it.
:func:`begin` starts a span that another thread may :meth:`Span.end`
(a queue wait: put on one thread, taken on another).
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Any, Deque, Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

RING = 65536


class Record(NamedTuple):
    id: int
    parent: Optional[int]
    name: str
    thread: str
    t0_ns: int
    t1_ns: int
    attrs: Dict[str, Any]


_ids = itertools.count(1)
_ring: Deque[Record] = deque(maxlen=RING)
_lock = threading.Lock()
_dropped = 0
_tls = threading.local()
_now = time.perf_counter_ns
_capturing = TraceAnnotation.is_enabled


def _stack() -> List["Span"]:
    s = getattr(_tls, "stack", None)
    if s is None:
        s = _tls.stack = []
    return s


def current() -> Optional["Span"]:
    """The innermost recorded span open on this thread."""
    s = getattr(_tls, "stack", None)
    return s[-1] if s else None


class Span:
    """``span(name, *, parent=None, **attrs)``: use it as a context
    manager, or start it with :func:`begin`."""

    __slots__ = ("name", "attrs", "parent", "id", "t0_ns", "t1_ns", "_ann", "_push")

    def __init__(self, name: str, *, parent: Optional["Span"] = None, **attrs: Any):
        self.name, self.parent, self.attrs = name, parent, attrs
        self.id = self.t1_ns = 0  # id nonzero once recorded

    def __enter__(self) -> "Span":
        if _capturing():
            self._record(push=True)
        else:
            self.t0_ns = _now()
        return self

    def __exit__(self, typ: Any, val: Any, tb: Any) -> None:
        self.t1_ns = _now()
        if self.id:
            self._ann.__exit__(None, None, None)
            self._finish()

    def end(self) -> None:
        self.__exit__(None, None, None)

    def _record(self, push: bool) -> None:
        p = self.parent if self.parent is not None else current()
        self.parent = p = p if p is not None and p.id else None
        if p is not None and "step" not in self.attrs and "step" in p.attrs:
            self.attrs["step"] = p.attrs["step"]
        self.id = next(_ids)
        self._push = push
        if push:
            _stack().append(self)
        # the annotation reads the profiler's clock as it is built: read
        # ours right after it, so the two intervals bracket the same work
        self._ann = TraceAnnotation(self.name, id=self.id, parent=p.id if p else 0,
                                    **self.attrs)
        self.t0_ns = _now()

    def _finish(self) -> None:
        global _dropped
        if self._push:
            s = _stack()
            if s and s[-1] is self:
                s.pop()
            elif self in s:
                s.remove(self)
        rec = Record(self.id, self.parent.id if self.parent is not None else None,
                     self.name, threading.current_thread().name,
                     self.t0_ns, self.t1_ns, dict(self.attrs))
        with _lock:
            if len(_ring) == RING:
                _dropped += 1
            _ring.append(rec)

    def set(self, **attrs: Any) -> None:
        """Attributes known only later in the span (counts, outcome)."""
        self.attrs.update(attrs)
        if self.id:
            self._ann.set_metadata(**attrs)

    @property
    def seconds(self) -> float:
        return ((self.t1_ns or _now()) - self.t0_ns) / 1e9


span = Span


def begin(name: str, *, parent: Optional[Span] = None, **attrs: Any) -> Span:
    """A started span, not nested on this thread; any thread may
    :meth:`Span.end` it."""
    sp = Span(name, parent=parent, **attrs)
    if _capturing():
        sp._record(push=False)
    else:
        sp.t0_ns = _now()
    return sp


def spans() -> List[Record]:
    """A copy of the recorded spans, oldest first."""
    with _lock:
        return list(_ring)


def dropped() -> int:
    """Records evicted from the ring since the last :func:`clear`."""
    return _dropped


def clear() -> None:
    global _dropped
    with _lock:
        _ring.clear()
        _dropped = 0
