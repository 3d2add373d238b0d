"""chip_smoke.py off the chip: it refuses to run without a TPU, and its
train/save/restore and device pre-codec phases pass at a smoke size on
CPU devices (one, and four for the sharded path).  Every run here is a
child process pinned to the CPU."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


def _run(args, *, cwd, devices=1, pythonpath=True):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env.pop("PYTHONPATH", None)
    if pythonpath:
        env["PYTHONPATH"] = str(ROOT / "src")
    return subprocess.run(
        [sys.executable] + args, capture_output=True, text=True, timeout=600,
        env=env, cwd=str(cwd),
    )


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_fails_without_tpu(where, tmp_path):
    if where == "alone":  # the script with nothing else of the repo
        shutil.copy(ROOT / "chip_smoke.py", tmp_path)
        r = _run([str(tmp_path / "chip_smoke.py")], cwd=tmp_path, pythonpath=False)
    else:
        r = _run([str(ROOT / "chip_smoke.py")], cwd=ROOT)
        assert "TPU" in r.stderr
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


PHASES = """
import sys
from pathlib import Path
sys.path.insert(0, {root!r})
import chip_smoke as cs
from repro.configs import get_smoke_config

cs.peak_hbm = lambda: 0  # the CPU reports no memory stats
job = cs.build_job(get_smoke_config(cs.ARCH), 0, 8, 32, cs.STEPS)
cs.resume_phase(job, Path({tmp!r}) / "train")
if {devices} == 1:
    cs.precodec_phase(job, Path({tmp!r}) / "precodec")
"""


@pytest.mark.parametrize("devices", [1, 4])
def test_chip_smoke_phases_on_cpu(devices, tmp_path):
    code = PHASES.format(root=str(ROOT), tmp=str(tmp_path), devices=devices)
    r = _run(["-c", code], cwd=ROOT, devices=devices)
    assert r.returncode == 0, r.stderr[-3000:]
    assert f"devices={devices}" in r.stdout
    assert "level=pfs" in r.stdout
    assert "losses_bit_identical=True" in r.stdout
    if devices == 1:
        assert "restored_byte_identical=True" in r.stdout
