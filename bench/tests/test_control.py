"""The control: the plain reference computed with float8 matmul operands,
put in the program's place, fails the training comparison of the
qwen1.5-0.5b cells, while the program itself passes it.  The same
readings at the cells' own size on the chip set the limits
(``bench/calibrate.py``); here they run at a size a test can hold."""
from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import checks
from bench.job import build, first_steps, reference_readings

ROOT = Path(__file__).resolve().parents[2]
CONFIG = json.loads((ROOT / "bench/configs/qwen1.5-0.5b.json").read_text())
SMALL = dict(CONFIG, hidden_size=256, num_attention_heads=4, num_key_value_heads=4,
             intermediate_size=704, num_hidden_layers=2, vocab_size=1024)
TRAFFIC = {"seq_len": 128, "global_batch": 4}


@pytest.fixture(scope="module")
def readings():
    seed = 2**31 + 17
    job = build(SMALL, TRAFFIC, seed, jax.devices("cpu")[:1])
    state, prog = first_steps(job, job.init_state(seed), seed, 3)
    del state
    ref = reference_readings(job, seed, 3)
    ctl = reference_readings(job, seed, 3, matmul_dtype=jnp.float8_e4m3fn)
    return checks.training_gaps(prog, ref), checks.training_gaps(ctl, ref)


@pytest.mark.parametrize("cell", ["qwen1.5-0.5b.save", "qwen1.5-0.5b.resume"])
def test_control_fails_where_the_program_passes(readings, cell):
    """On the loss and the first gradient; ``change_gap`` reads higher at
    this small size for the program too (fewer elements per leaf), and
    its limit holds at the cells' size."""
    limits = json.loads((ROOT / f"bench/limits/{cell}.json").read_text())
    prog, ctl = readings
    for k in ("loss_gap", "grad_gap"):
        assert prog[k] <= limits[k], prog
    assert any(ctl[k] > limits[k] for k in ("loss_gap", "grad_gap")), ctl
