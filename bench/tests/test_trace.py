"""The reduction from a profiler trace to busy time, idle gaps and ops."""
from __future__ import annotations

import gzip
import json
from pathlib import Path

import pytest

from bench.trace import reduce

DATA = Path(__file__).parent / "data" / "trace_v5e_save.json.gz"


def test_hand_made_trace():
    ms = 1_000_000
    ex = {"host": [("bench.window", 0, 100 * ms), ("bench.train_step", 0, 40 * ms),
                   ("bench.save", 40 * ms, 50 * ms), ("other", 95 * ms, 5 * ms)],
          "devices": [[("a", 5 * ms, 20 * ms), ("b", 10 * ms, 20 * ms),   # overlap
                       ("a", 90 * ms, 20 * ms)]]}                        # runs past the window
    r = reduce(ex)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.035)          # 5-30 ms and 90-100 ms
    assert r["idle_share"] == pytest.approx(0.65)
    assert r["idle_gaps"][0] == ["bench.save", pytest.approx(0.06)]
    assert r["idle_gaps"][1] == ["bench.train_step", pytest.approx(0.005)]
    assert dict(r["device_ops"]) == {"a": pytest.approx(0.03), "b": pytest.approx(0.02)}


def test_two_devices_are_averaged():
    ms = 1_000_000
    ex = {"host": [("bench.window", 0, 10 * ms)],
          "devices": [[("x", 0, 10 * ms)], [("x", 0, 5 * ms)]]}
    r = reduce(ex)
    assert r["busy_s"] == pytest.approx(0.0075)
    assert r["idle_gaps"] == [["host.other", pytest.approx(0.005)]]


def test_no_window_or_no_device_reads_nothing():
    assert reduce({"host": [], "devices": [[("x", 0, 1)]]}) is None
    assert reduce({"host": [("bench.window", 0, 10)], "devices": [[]]}) is None


def test_recorded_v5e_trace():
    """A window of three train steps, one save() and two more steps of
    qwen1.5-0.5b.save's job, recorded on a TPU v5e."""
    ex = json.loads(gzip.decompress(DATA.read_bytes()))
    r = reduce(ex)
    assert 3.5 < r["window_s"] < 4.5
    assert 0 < r["busy_s"] < r["window_s"]
    # the device waits through save(): the longest gap is inside it
    name, secs = r["idle_gaps"][0]
    assert name == "bench.save" and secs > 1.0
    assert len(r["device_ops"]) == 10 and len(r["idle_gaps"]) == 10
    assert all(not n.startswith("%") and " " not in n for n, _ in r["device_ops"])
