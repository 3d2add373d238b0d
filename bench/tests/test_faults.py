"""A run whose timed path is broken underneath comes out not correct.

Each test drives a whole run of a tiny cell on the CPU (the look for a
chip skipped) with one fault planted in the program: a step that returns
its state unchanged, half of the batch left out with the mean taken over
the rest, a value altered where the checkpoint path produces it, and the
live state altered in the step after a save.  A sound run of the same
cell is correct, with one save a window or two, and with L1 on disk or
in RAM."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TINY_CELLS, run_cell


def _wrap_build(monkeypatch, wrap):
    import bench.job as job_mod

    real = job_mod.build

    def build(*a, **kw):
        job = real(*a, **kw)
        job.step = wrap(job, job.step)
        return job

    monkeypatch.setattr(job_mod, "build", build)


def unchanged(job, step):
    def frozen(state, batch):
        _, m = step(jax.tree_util.tree_map(jnp.copy, state), batch)
        return state, m
    return frozen


def half_batch(job, step):
    from repro.models import get_model
    from repro.train import OptConfig, TrainConfig, make_train_step

    half = job.global_batch // 2
    fn, _, _ = make_train_step(
        get_model(job.mcfg), TrainConfig(opt=OptConfig(**job.opt)), job.mesh,
        {"tokens": jax.ShapeDtypeStruct((half, job.seq_len), jnp.int32)})
    return lambda state, batch: fn(state, {"tokens": batch["tokens"][:half]})


@pytest.mark.parametrize("cell", list(TINY_CELLS))
def test_sound_run_is_correct(checkout, capsys, cell):
    from bench import ckpt

    res, err = run_cell(checkout, cell, capsys)
    assert res["correct"], err
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())
    assert list(ckpt.RAM_ROOT.iterdir()) == []


@pytest.mark.parametrize("fault,number", [(unchanged, "change_gap"),
                                          (half_batch, "loss_gap")])
@pytest.mark.parametrize("cell", ["tiny.save", "tiny.resume"])
def test_broken_step_is_not_correct(checkout, capsys, monkeypatch, cell, fault, number):
    _wrap_build(monkeypatch, fault)
    res, err = run_cell(checkout, cell, capsys)
    assert not res["correct"]
    assert res["checks"][number]["value"] > res["checks"][number]["limit"]


def test_altered_save_is_not_correct(checkout, capsys, monkeypatch):
    from repro.core.engine import CheckpointManager

    real = CheckpointManager.save

    def save(self, step, tree):
        leaf = np.array(tree["train"]["params"]["embed"])
        leaf[0, 0] += 1.0
        tree = {**tree, "train": {**tree["train"],
                                  "params": {**tree["train"]["params"], "embed": leaf}}}
        return real(self, step, tree)

    monkeypatch.setattr(CheckpointManager, "save", save)
    res, _ = run_cell(checkout, "tiny.save", capsys)
    assert not res["correct"]
    assert res["checks"]["l1_leaves_differ"]["value"] > 0
    assert res["checks"]["pfs_leaves_differ"]["value"] > 0


@pytest.mark.parametrize("cell", ["tiny.save", "tiny.save_one"])
def test_state_altered_after_a_save_is_not_correct(checkout, capsys, monkeypatch, cell):
    """Each level reads back what was saved; only the replay of the
    window's steps from the first save to the second, or to the digest
    taken where the second would fall, sees the altered state."""
    from repro.core.engine import CheckpointManager

    real, pending = CheckpointManager.save, []

    def save(self, step, tree):
        pending.append(step)
        return real(self, step, tree)

    def after_save(job, step):
        def altered(state, batch):
            state, m = step(state, batch)
            if pending:
                pending.clear()
                embed = state["params"]["embed"].at[0, 0].add(1.0)
                state = {**state, "params": {**state["params"], "embed": embed}}
            return state, m
        return altered

    monkeypatch.setattr(CheckpointManager, "save", save)
    _wrap_build(monkeypatch, after_save)
    res, _ = run_cell(checkout, cell, capsys)
    assert not res["correct"]
    assert res["checks"]["replay_leaves_differ"]["value"] > 0
    assert res["checks"]["l1_leaves_differ"]["value"] == 0
    assert res["checks"]["pfs_leaves_differ"]["value"] == 0


def test_altered_restore_is_not_correct(checkout, capsys, monkeypatch):
    from repro.core.engine import CheckpointManager

    real = CheckpointManager.restore

    def restore(self, target, step=None, **kw):
        got, tree = real(self, target, step, **kw)
        tree["train"]["opt"]["nu"]["embed"] = np.array(tree["train"]["opt"]["nu"]["embed"])
        tree["train"]["opt"]["nu"]["embed"][1, 2] += 1e-3
        return got, tree

    monkeypatch.setattr(CheckpointManager, "restore", restore)
    res, _ = run_cell(checkout, "tiny.resume", capsys)
    assert not res["correct"]
    assert res["checks"]["restored_leaves_differ"]["value"] > 0
