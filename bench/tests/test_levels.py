"""Where a run's checkpoint levels live and what they write: one save a
window, L1 on the disk or in RAM, the space each level asks for before
anything is written, the ``written`` line, and the refusals of a
checkpoint block the harness does not know."""
from __future__ import annotations

import collections
import re
import shutil
import types

import pytest

from conftest import TINY, TINY_CELLS, add_cell, run_cell

Usage = collections.namedtuple("Usage", "total used free")


def _files(directory):
    return [p for p in directory.rglob("*") if p.is_file()]


def _record_saves(monkeypatch):
    """Wrap ``CheckpointManager.save``: returns the list of its stats, each
    with the files under the RAM root once it returned."""
    from bench import ckpt
    from repro.core.engine import CheckpointManager

    real, saved = CheckpointManager.save, []

    def save(self, step, tree):
        st = real(self, step, tree)
        saved.append((st, len(_files(ckpt.RAM_ROOT))))
        return st

    monkeypatch.setattr(CheckpointManager, "save", save)
    return saved


def test_one_save_window_short_of_the_endpoint_is_not_correct(checkout, capsys):
    """The digest falls 10**6 steps after the save, past the window's
    end: no endpoint, so the replay counts every leaf as differing."""
    add_cell(checkout, "tiny.save_far", "save",
             {"save_every_steps": 10**6, "max_saves": 1}, {})
    res, _ = run_cell(checkout, "tiny.save_far", capsys)
    assert not res["correct"]
    checks = res["checks"]
    assert checks["replay_leaves_differ"]["value"] > 0
    assert checks["l1_leaves_differ"]["value"] == checks["pfs_leaves_differ"]["value"] == 0


def test_resume_drops_l1_in_ram(checkout, capsys, monkeypatch):
    """Each drop empties the RAM directory that holds L1, not the link to
    it, and every restore from the PFS is still correct."""
    from bench import ckpt

    real, seen = ckpt.drop_l1, []

    def drop(root):
        l1 = (root / "local").resolve()
        before = len(_files(l1))
        real(root)
        seen.append((l1, before, len(_files(l1))))

    monkeypatch.setattr(ckpt, "drop_l1", drop)
    res, err = run_cell(checkout, "tiny.resume_ram", capsys)
    assert res["correct"], err
    assert len(seen) == res["attempted"] == 2
    assert all(l1.is_relative_to(ckpt.RAM_ROOT) for l1, _, _ in seen)
    assert seen[0][1] > 0 and all(after == 0 for _, _, after in seen)


def test_drop_l1_raises_if_a_file_is_left(tmp_path, monkeypatch):
    from bench import ckpt

    (tmp_path / "local" / "node_0000").mkdir(parents=True)
    (tmp_path / "local" / "node_0000" / "blob").write_bytes(b"x")
    monkeypatch.setattr(shutil, "rmtree", lambda p, **kw: None)
    with pytest.raises(RuntimeError, match="still holds 1 files"):
        ckpt.drop_l1(tmp_path)


def test_nothing_left_in_ram_after_a_run_that_raises(checkout, capsys, monkeypatch):
    from bench import ckpt, harness
    from repro.core.engine import CheckpointManager

    real = CheckpointManager.save

    def save(self, step, tree):
        real(self, step, tree)
        assert _files(ckpt.RAM_ROOT), "L1 was not written to the RAM root"
        raise RuntimeError("planted after the save")

    monkeypatch.setattr(CheckpointManager, "save", save)
    with pytest.raises(RuntimeError, match="planted"):
        harness.main(["--workload", "tiny.save_ram", "--seed", "7", "--seconds", "1"])
    assert list(ckpt.RAM_ROOT.iterdir()) == []
    assert not harness.RUNS.exists()


@pytest.mark.parametrize("cell, short, words", [
    ("tiny.save", "ckpt", r"disk \(pfs \+ l1\) holds"),
    ("tiny.save_ram", "ram", r"RAM root .* \(l1\) holds"),
])
def test_space_short_is_refused_before_the_first_save(checkout, capsys, monkeypatch,
                                                     cell, short, words):
    from bench import ckpt, harness

    real = shutil.disk_usage
    short_root = harness.RUNS if short == "ckpt" else ckpt.RAM_ROOT

    def usage(path):
        if str(path).startswith(str(short_root)):
            return Usage(10**6, 10**6 - 1, 1)
        return real(path)

    monkeypatch.setattr(shutil, "disk_usage", usage)
    saved = _record_saves(monkeypatch)
    with pytest.raises(harness.BenchError, match=words):
        harness.main(["--workload", cell, "--seed", "7", "--seconds", "1"])
    assert saved == []


@pytest.mark.parametrize("ram, free, refused", [
    (False, 439, "disk (pfs + l1)"), (False, 441, None),
    (True, 219, "disk (pfs)"), (True, 221, None),
])
def test_require_disk_counts_the_levels_on_the_disk(tmp_path, monkeypatch, ram, free,
                                                   refused):
    """100 bytes of state, 2 saves, 1.1 times that a level: two levels on
    the disk with L1 there, one with L1 in RAM (which has room)."""
    from bench import harness

    config = dict(TINY, checkpoint=dict(TINY["checkpoint"], l1="ram" if ram else "disk"))
    ctx = harness.Context(cell=types.SimpleNamespace(config=config), seed=0, seconds=1.0,
                          devices=[], tracer=None, t_start=0.0,
                          ckpt_root=tmp_path / "disk",
                          l1_ram=tmp_path / "ram" if ram else None)
    monkeypatch.setattr(shutil, "disk_usage", lambda p: Usage(
        10**9, 0, free if str(p) == str(tmp_path / "disk") else 10**9))
    if refused:
        with pytest.raises(harness.BenchError, match=re.escape(refused)):
            ctx.require_space(100, 2)
    else:
        ctx.require_space(100, 2)


@pytest.mark.parametrize("cell", list(TINY_CELLS))
def test_written_line_sums_the_saves_own_bytes(checkout, capsys, monkeypatch, cell):
    from bench import harness

    saved = _record_saves(monkeypatch)
    recs = []
    real = harness.read_metrics
    monkeypatch.setattr(harness, "read_metrics",
                        lambda metrics, rec, loader: recs.append(rec) or real(metrics, rec,
                                                                              loader))
    res, err = run_cell(checkout, cell, capsys)
    assert res["correct"], err
    kind, traffic, checkpoint = TINY_CELLS[cell]
    medium = checkpoint.get("l1", "disk")
    stats = [st for st, _ in saved]
    assert len(stats) == (traffic["max_saves"] if kind == "save" else 1)
    assert all(st.flush is not None for st in stats)
    l1 = sum(st.stored_bytes for st in stats) / 1e9
    pfs = sum(st.flush.bytes_written for st in stats) / 1e9
    want = {"l1_GB": l1, "l1_medium": medium, "pfs_GB": pfs,
            "disk_GB": pfs + (l1 if medium == "disk" else 0.0)}
    assert recs[0]["written"] == want
    line = [x for x in err.splitlines() if x.startswith("written ")]
    assert line == ["written " + " ".join(f"{k}={v}" for k, v in want.items())]
    # L1's blobs went where the configuration put them
    in_ram = [n for _, n in saved]
    assert all(n > 0 for n in in_ram) if medium == "ram" else in_ram == [0] * len(saved)


@pytest.mark.parametrize("checkpoint, words", [
    ({"l1_medium": "ram"}, r"checkpoint keys \['l1_medium'\]"),
    ({"l1": "tmpfs"}, r"checkpoint l1 'tmpfs'"),
])
def test_unknown_checkpoint_setting_is_refused(checkout, capsys, monkeypatch,
                                              checkpoint, words):
    from bench import harness

    add_cell(checkout, "tiny.save_typo", "save",
             {"save_every_steps": 3, "max_saves": 1}, checkpoint)
    saved = _record_saves(monkeypatch)
    with pytest.raises(ValueError, match=words):
        harness.main(["--workload", "tiny.save_typo", "--seed", "7", "--seconds", "1"])
    assert saved == []
