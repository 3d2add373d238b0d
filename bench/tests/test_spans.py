"""The per-layer metrics that read the program's own spans: on the CPU
through the harness, and bench/spans.py on hand-made records."""
from __future__ import annotations

import re

import pytest
from conftest import run_cell

SAVE = ["save_gather_s", "save_copy_s", "save_crc_l1_s", "flush_wait_s",
        "flush_fsync_s", "pfs_files_per_save"]
RESUME = ["restore_crc_s", "restore_decode_s"]


@pytest.mark.parametrize("workload,names", [("tiny.save", SAVE), ("tiny.resume", RESUME)])
def test_traced_run_reports_the_span_metrics(checkout, capsys, workload, names):
    from repro.core import spans

    spans.clear()
    res, err = run_cell(checkout, workload, capsys, trace=1, seconds=1)
    assert res["correct"], err
    got = res["metrics"]
    for name in names:
        assert got[name]["value"] > 0, name
    if workload == "tiny.save":
        files = got["pfs_files_per_save"]["value"]
        assert files == int(files) == 3  # one data file, the manifest, the journal
        # the save-phase spans lie inside save(): never more than its stall
        stalls = [float(x) for x in re.findall(r"stall_s=([0-9.]+)", err)]
        assert stalls
        inside = sum(got[n]["value"] for n in ("save_gather_s", "save_copy_s",
                                               "save_crc_l1_s"))
        assert inside <= sum(stalls) / len(stalls)


def test_untraced_run_records_no_spans(checkout, capsys):
    from repro.core import spans

    spans.clear()
    res, err = run_cell(checkout, "tiny.save", capsys, trace=0, seconds=1)
    assert res["correct"], err
    assert spans.spans() == []
    assert not set(SAVE) & set(res["metrics"])


class R:
    """A hand-made span record."""

    def __init__(self, id, parent, name, t0, t1, **attrs):
        self.id, self.parent, self.name = id, parent, name
        self.t0_ns, self.t1_ns, self.attrs = t0, t1, attrs


def forest():
    ms = 1_000_000
    return [
        R(1, None, "ckpt.save", 0, 100 * ms, step=1),
        R(2, 1, "ckpt.save.encode", 0, 80 * ms),
        R(3, 2, "ckpt.save.gather", 0, 30 * ms),
        R(4, 1, "ckpt.flush", 100 * ms, 300 * ms, files=3),
        R(5, 4, "ckpt.flush.extent", 110 * ms, 150 * ms),
        R(6, 4, "ckpt.flush.extent", 120 * ms, 140 * ms),  # another thread
        R(7, None, "ckpt.save", 400 * ms, 500 * ms, step=2),
        R(8, 7, "ckpt.save.gather", 400 * ms, 450 * ms),
        R(9, None, "ckpt.manager.open", 0, 1),
    ]


def test_trees_group_each_root_with_its_descendants():
    from bench.spans import mean, per_tree, trees

    f = trees({"saves": [{}, {}]}, "ckpt.save", "saves", records=forest())
    assert [sorted(s.id for s in t) for t in f] == [[1, 2, 3, 4, 5, 6], [7, 8]]
    assert per_tree(f, "ckpt.save.gather") == pytest.approx([0.03, 0.05])
    assert mean(per_tree(f, "ckpt.flush.extent")) == pytest.approx(0.06)  # one tree has them
    assert per_tree(f, "ckpt.flush", "files") == [3.0]
    assert mean(per_tree(f, "ckpt.restore.crc")) is None


@pytest.mark.parametrize("case", ["dropped", "fewer_saves", "more_saves", "no_record"])
def test_trees_read_nothing_when_they_cannot_match_the_record(case):
    from bench.spans import trees

    rec = {"saves": [{}] * {"fewer_saves": 1, "more_saves": 3}.get(case, 2)}
    kw = {"records": forest(), "dropped": 1 if case == "dropped" else 0}
    if case == "no_record":
        rec = {}
    assert trees(rec, "ckpt.save", "saves", **kw) is None
