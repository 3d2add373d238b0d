"""The configuration loader: published keys, ``program`` fields, the
``mesh`` layout and the ``checkpoint`` block of a configuration's file,
the FLOP count it is measured by, and the bytes a run of each cell
writes to the disk."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import types
from pathlib import Path

import pytest

from bench import ckpt, flops
from bench.job import HF_KEYS, load_module, make_mesh, model_config, state_shape
from repro.configs import get_config
from repro.models.config import MoEConfig

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
QWEN = json.loads((ROOT / "bench/configs/qwen1.5-0.5b.json").read_text())
MOE = {"registry": "qwen2-moe-a2.7b"}
# what a run may write to the disk: six runs stay under the 30 GiB at
# which a check moves to a fresh machine
RUN_WRITES_AT_MOST = 5 * 2**30


def test_published_keys_alone_build_what_they_built():
    bare = get_config(QWEN["registry"]).replace(
        **{f: QWEN[k] for k, f in HF_KEYS.items() if k in QWEN})
    assert model_config(QWEN) == bare
    assert (bare.n_layers, bare.vocab_size, bare.chunk_q, bare.moe) == (4, 18992, 1024, None)


@pytest.mark.parametrize("program, want", [
    ({"moe": {"n_experts": 4, "top_k": 2}},
     {"moe": MoEConfig(n_experts=4, top_k=2, n_shared=4, expert_d_ff=1408,
                       shared_d_ff=5632)}),
    ({"chunk_q": 16, "window": 512}, {"chunk_q": 16, "window": 512}),
    ({"block_pattern": ["attn", "attn"]}, {"block_pattern": ("attn", "attn")}),
])
def test_program_fields_laid_over_the_entry(program, want):
    got = model_config(dict(MOE, program=program))
    base = get_config(MOE["registry"])
    for name, value in vars(base).items():
        assert getattr(got, name) == want.get(name, value), name


@pytest.mark.parametrize("config, words", [
    (dict(MOE, program={"n_expert": 8}), "'n_expert'"),
    (dict(MOE, program={"moe": {"n_experts": 8, "experts_held": 8}}), "'experts_held'"),
    (dict(QWEN, program={"moe": {"n_experts": 8, "top_k": 2}}), "'moe' is an object"),
    (dict(QWEN, program={"n_layers": 2}), "'n_layers' is set by a published key"),
])
def test_program_field_refused(config, words):
    with pytest.raises(ValueError, match=words):
        model_config(config)


def test_mesh_on_one_device():
    import jax

    dev = jax.devices("cpu")[:1]
    assert make_mesh(QWEN, dev).devices.shape == (1, 1)
    assert make_mesh(dict(QWEN, mesh={"model": 1}), dev).devices.shape == (1, 1)
    for layout in ({"model": 2}, {"model": 0}, {"data": 1}):
        with pytest.raises(ValueError):
            make_mesh(dict(QWEN, mesh=layout), dev)


FOUR = '''
import json, sys
import jax
from bench.job import build, make_mesh
cfg, traffic = json.loads(sys.argv[1]), json.loads(sys.argv[2])
devs = jax.devices()
out = {"count": len(devs)}
for name, layout in (("default", None), ("model2", {"model": 2}), ("model3", {"model": 3})):
    c = dict(cfg, mesh=layout) if layout else cfg
    try:
        out[name] = list(make_mesh(c, devs).devices.shape)
    except ValueError as e:
        out[name] = str(e)
job = build(dict(cfg, mesh={"model": 2}), traffic, 3, devs)
state, m = job.step(job.init_state(3), job.batch(0))
out["built"] = [list(job.mesh.devices.shape), list(job.mesh.axis_names)]
out["loss_finite"] = bool(jax.numpy.isfinite(m["loss"]))
print(json.dumps(out))
'''


def test_mesh_on_four_devices():
    from conftest import TINY, TINY_TRAFFIC

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    r = subprocess.run([sys.executable, "-c", FOUR, json.dumps(TINY),
                        json.dumps(TINY_TRAFFIC)], capture_output=True, text=True,
                       timeout=300, env=env, cwd=str(ROOT))
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["count"] == 4
    assert out["default"] == [4, 1] and out["model2"] == [2, 2]
    assert "model=3 does not divide the cell's 4 chips" in out["model3"]
    assert out["built"] == [[2, 2], ["data", "model"]] and out["loss_finite"]


def test_flops_from_the_reference_or_dense():
    dense = flops.train_flops_per_token(QWEN, 2048)
    decoder = load_module(ROOT / "bench/refs/decoder.py")
    assert not hasattr(decoder, "train_flops_per_token")
    assert flops.flops_per_token(QWEN, decoder, 2048) == dense == 525_631_488
    own = types.SimpleNamespace(train_flops_per_token=lambda cfg, s: 7 * s + cfg["x"])
    assert flops.flops_per_token({"x": 1}, own, 2048) == 14_337.0


def test_checkpoint_block_of_the_published_config():
    assert ckpt.settings(QWEN) == dict(QWEN["checkpoint"], l1="disk")
    assert ckpt.levels_on_disk(QWEN) == ["pfs", "l1"]
    assert ckpt.levels_on_disk(dict(QWEN, checkpoint=dict(QWEN["checkpoint"], l1="ram"))) \
        == ["pfs"]


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_a_run_writes_at_most_5_gib_to_the_disk(w):
    """The state's bytes, from its shapes alone, times the saves a run of
    the traffic makes, times the levels on the disk."""
    c = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    config = json.loads((ROOT / c["file"]).read_text())
    traffic = json.loads((ROOT / f"bench/traffic/{w['traffic']}.json").read_text())
    mix = load_module(ROOT / f"bench/mixes/{traffic['kind']}.py")
    state = ckpt.tree_bytes(state_shape(config))
    disk = state * mix.saves_per_run(traffic) * len(ckpt.levels_on_disk(config))
    assert disk <= RUN_WRITES_AT_MOST, f"{disk / 2**30:.2f} GiB a run"
