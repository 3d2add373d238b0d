"""Shared fixtures: a temporary checkout of the benchmark with a tiny
configuration, run on the CPU with the harness's look for a chip
skipped.  Run with ``python -m pytest bench/tests`` from the root."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {
    "name": "tiny", "source": "https://huggingface.co/Qwen/Qwen1.5-0.5B",
    "registry": "qwen1.5-0.5b", "reference": "decoder", "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 256,
    "rms_norm_eps": 1e-06, "rope_theta": 1000000.0, "tie_word_embeddings": False,
    "qkv_bias": True, "param_dtype": "float32", "compute_dtype": "float32",
    "optimizer": {"lr": 0.0003, "beta1": 0.9, "beta2": 0.95, "eps": 1e-08,
                  "weight_decay": 0.1, "grad_clip": 1.0, "warmup_steps": 100,
                  "total_steps": 10000, "schedule": "cosine", "min_lr_ratio": 0.1},
    "checkpoint": {"strategy": "stripe_aligned", "codec": "none",
                   "nodes": 2, "procs_per_node": 2},
}
TINY_TRAFFIC = {"seq_len": 32, "global_batch": 4, "warm_steps": 3,
                "flush_timeout_s": 60}
TINY_LIMITS = {"loss_gap": 1e-4, "grad_gap": 1e-3, "change_gap": 1e-3}


def make_checkout(dest: Path) -> Path:
    """BENCHMARK.json and bench/ copied to ``dest``, plus a tiny config and
    its two cells (``tiny.save``, ``tiny.resume``)."""
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns(".runs", "__pycache__"))
    bench = json.loads((dest / "BENCHMARK.json").read_text())
    (dest / "bench/configs/tiny.json").write_text(json.dumps(TINY))
    bench["configs"].append({"name": "tiny", "source": TINY["source"],
                             "file": "bench/configs/tiny.json", "reduced": [],
                             "why": "test size"})
    for kind, extra in (("save", {"save_every_steps": 3, "max_saves": 2}),
                        ("resume", {"max_cycles": 2})):
        (dest / f"bench/traffic/tiny_{kind}.json").write_text(
            json.dumps({"kind": kind, **TINY_TRAFFIC, **extra}))
        (dest / f"bench/limits/tiny.{kind}.json").write_text(json.dumps(TINY_LIMITS))
        bench["workloads"].append({"name": f"tiny.{kind}", "config": "tiny",
                                   "traffic": f"tiny_{kind}", "chips": 1,
                                   "why": "test size"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if any(w.endswith(f".{kind}") for w in m.get("workloads", [])):
                m["workloads"].append(f"tiny.{kind}")
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    return dest


@pytest.fixture
def checkout(tmp_path, monkeypatch):
    """A temporary checkout whose harness runs on the CPU."""
    import jax

    import repro.launch.compile_cache as cc

    dest = make_checkout(tmp_path)
    monkeypatch.setattr(cc, "use_compile_cache", lambda: "")
    sys.modules.pop("bench", None)
    for name in [m for m in sys.modules if m.startswith("bench.")]:
        sys.modules.pop(name)
    monkeypatch.syspath_prepend(str(dest))
    from bench import harness

    # the look for a chip skipped: CPU devices, measured against the v5e's peaks
    v5e = harness.load_json(dest / "bench/peaks.json")["devices"]["TPU v5 lite"]
    monkeypatch.setattr(harness, "require_chips", lambda n: jax.devices("cpu")[:n])
    monkeypatch.setattr(harness, "peaks_for", lambda kind: v5e)
    yield dest
    for name in [m for m in sys.modules if m == "bench" or m.startswith("bench.")]:
        sys.modules.pop(name)


def run_cell(checkout: Path, workload: str, capsys, seed: int = 7, trace: int = 0,
             seconds: float = 1.0):
    """Drive one run of ``workload`` on the CPU; returns (result, stderr)."""
    from bench import harness

    rc = harness.main(["--workload", workload, "--seed", str(seed), "--seconds",
                       str(seconds), "--trace", str(trace)])
    out, err = capsys.readouterr()
    assert rc == 0
    return json.loads(out.strip().splitlines()[-1]), err
