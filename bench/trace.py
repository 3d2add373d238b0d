"""Profiler capture of the measured window and its reduction to numbers.

The benchmark marks what the host is doing with ``span(name)``
(``jax.profiler.TraceAnnotation``, names starting ``bench.``) and the
whole window with ``bench.window``.  ``extract`` keeps from the
profiler's XSpace the device operations (the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane) and the benchmark's host spans; ``reduce``
turns that into the device's busy seconds, the idle gaps attributed to
the host span they fall in, and the operations that took most time.
"""
from __future__ import annotations

import glob
from contextlib import contextmanager
from typing import Any, Dict, Iterator, List, Optional, Tuple

import jax

WINDOW = "bench.window"
TOP = 10

Event = Tuple[str, float, float]  # name, start ns, duration ns


@contextmanager
def span(name: str) -> Iterator[None]:
    with jax.profiler.TraceAnnotation(name):
        yield


def extract(xplane_path: str) -> Dict[str, Any]:
    """The device op events per device and the benchmark's host spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    devices: List[List[Event]] = []
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            ops = lines.get("XLA Ops")
            if ops is not None:
                devices.append([(_short(e.name), e.start_ns, e.duration_ns)
                                for e in ops.events])
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in ln.events if e.name.startswith("bench."))
    return {"devices": devices, "host": host}


def _short(op: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return op.split(" = ", 1)[0].lstrip("%")


def _merge(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def reduce(ex: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Busy and idle seconds of the window, averaged over the devices,
    the longest idle gaps named by the host span that covers most of
    each, and the device operations with the most time.  None when the
    trace holds no window or no device operation."""
    win = [(s, s + d) for n, s, d in ex["host"] if n == WINDOW]
    if not win or not any(ex["devices"]):
        return None
    w0, w1 = win[0]
    spans = [(n, s, s + d) for n, s, d in ex["host"] if n != WINDOW]
    busy_total, gaps, ops = 0.0, [], {}
    for events in ex["devices"]:
        iv = []
        for name, s, d in events:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                iv.append((a, b))
                ops[name] = ops.get(name, 0.0) + (b - a)
        merged = _merge(iv)
        busy_total += sum(b - a for a, b in merged)
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((_cover(spans, a, b), b - a))
    n = len([e for e in ex["devices"] if e])
    window_s = (w1 - w0) / 1e9
    busy_s = busy_total / n / 1e9
    gaps.sort(key=lambda g: -g[1])
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "idle_gaps": [[name, ns / 1e9] for name, ns in gaps[:TOP]],
        "device_ops": [[name, ns / n / 1e9] for name, ns in top_ops],
    }


def _cover(spans: List[Tuple[str, float, float]], a: float, b: float) -> str:
    best, name = 0.0, "host.other"
    for n, s, e in spans:
        o = min(b, e) - max(a, s)
        if o > best:
            best, name = o, n
    return name


class Tracer:
    """Profiles the window when ``enabled``; ``result`` is the reduction."""

    def __init__(self, enabled: bool, out_dir: str):
        self.enabled, self.out_dir = enabled, out_dir
        self.result: Optional[Dict[str, Any]] = None

    @contextmanager
    def window(self) -> Iterator[None]:
        if not self.enabled:
            with span(WINDOW):
                yield
            return
        jax.profiler.start_trace(self.out_dir)
        try:
            with span(WINDOW):
                yield
        finally:
            jax.profiler.stop_trace()
        paths = glob.glob(f"{self.out_dir}/**/*.xplane.pb", recursive=True)
        if paths:
            self.result = reduce(extract(sorted(paths)[-1]))
