"""End-to-end CLI driver test: train -> kill -> resume, via subprocess."""
import os
import subprocess
import sys
import tempfile
from pathlib import Path


def _run(args, env):
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.train"] + args,
        capture_output=True, text=True, timeout=560, env=env,
        cwd=str(Path(__file__).resolve().parents[1]),
    )


def test_train_cli_checkpoints_and_resumes():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    with tempfile.TemporaryDirectory() as root:
        common = [
            "--arch", "tinyllama-1.1b", "--smoke", "--global-batch", "4",
            "--seq-len", "32", "--ckpt-every", "3", "--root", root,
            "--strategy", "stripe_aligned", "--codec", "zstd",
        ]
        first = _run(common + ["--steps", "6"], env)
        assert first.returncode == 0, first.stderr[-2000:]
        assert "step     6" in first.stdout
        assert "[ckpt]" in first.stdout

        second = _run(common + ["--steps", "9", "--resume"], env)
        assert second.returncode == 0, second.stderr[-2000:]
        assert "[resume] restored step 6" in second.stdout
        assert "step     7" in second.stdout  # continued, not restarted
        assert "step     9" in second.stdout


CACHE_CHILD = """
import importlib.util, sys
import jax, jax.numpy as jnp
spec = importlib.util.spec_from_file_location("compile_cache", sys.argv[1])
mod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mod)
print(mod.use_compile_cache())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: x * 3 + 1)(jnp.arange(8.0)).block_until_ready()
"""


def _cache_files(d):
    return [p for p in Path(d).rglob("*") if p.is_file()] if Path(d).exists() else []


def test_compile_cache_placement(tmp_path):
    """The entry points' compile cache: ``JAX_COMPILATION_CACHE_DIR`` when
    set (and nothing in the checkout), else ``<checkout>/.jax_cache``."""
    src = Path(__file__).resolve().parents[1] / "src/repro/launch/compile_cache.py"
    checkout = tmp_path / "checkout"
    helper = checkout / "src/repro/launch/compile_cache.py"
    helper.parent.mkdir(parents=True)
    helper.write_text(src.read_text())
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)

    def child(env):
        r = subprocess.run(
            [sys.executable, "-c", CACHE_CHILD, str(helper)],
            capture_output=True, text=True, timeout=120, env=env, cwd=str(tmp_path),
        )
        assert r.returncode == 0, r.stderr[-2000:]
        return r.stdout.strip().splitlines()[-1]

    given = tmp_path / "given"
    assert child(dict(env, JAX_COMPILATION_CACHE_DIR=str(given))) == str(given)
    assert _cache_files(given)
    assert not (checkout / ".jax_cache").exists()

    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    assert child(env) == str(checkout / ".jax_cache")
    assert _cache_files(checkout / ".jax_cache")
