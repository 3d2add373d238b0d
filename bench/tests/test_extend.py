"""A configuration, a traffic kind and a metric added as new files only:
the harness finds each by its name in BENCHMARK.json."""
from __future__ import annotations

import json

from conftest import TINY, TINY_LIMITS, TINY_TRAFFIC, run_cell

PROBE_MIX = '''
from bench.job import load_module
from pathlib import Path

def run(ctx):
    rec = load_module(Path(__file__).parent / "save.py").run(ctx)
    rec["probe"] = len(rec["saves"])
    return rec
'''
PROBE_METRIC = '''
def read(rec):
    return rec.get("probe")
'''


def test_new_config_mix_and_metric_need_no_edit(checkout, capsys):
    (checkout / "bench/mixes/probe.py").write_text(PROBE_MIX)
    (checkout / "bench/metrics/probe_saves.py").write_text(PROBE_METRIC)
    traffic = json.loads((checkout / "bench/traffic/tiny_save.json").read_text())
    (checkout / "bench/traffic/tiny_probe.json").write_text(
        json.dumps(dict(traffic, kind="probe")))
    (checkout / "bench/limits/tiny.probe.json").write_text(
        (checkout / "bench/limits/tiny.save.json").read_text())
    bench = json.loads((checkout / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny.probe", "config": "tiny",
                               "traffic": "tiny_probe", "chips": 1, "why": "test"})
    bench["end_to_end"][0]["workloads"].append("tiny.probe")
    bench["per_layer"].append({"name": "probe_saves", "unit": "saves",
                               "better": "higher", "source": "program_counter",
                               "layer": "save: gather + encode + L1",
                               "moves": bench["end_to_end"][0]["name"],
                               "workloads": ["tiny.probe"]})
    (checkout / "BENCHMARK.json").write_text(json.dumps(bench))

    res, err = run_cell(checkout, "tiny.probe", capsys, trace=1, seconds=1)
    assert res["correct"], err
    assert res["metrics"]["probe_saves"]["value"] >= 1
    assert res["device"]["count"] == 1 and "window_s" in res["device"]


COUNTED_REF = '''
"""decoder.py's model, with a FLOP count of its own."""
from bench.refs.decoder import *  # noqa: F401,F403
from bench.refs.decoder import dims


def train_flops_per_token(cfg, seq_len):
    m = dims(cfg)
    return 6.0 * m["layers"] * m["d"] * m["ff"] + seq_len
'''
FLOPS_METRIC = '''
def read(rec):
    return rec.get("flops_per_token")
'''


def test_new_config_with_program_fields_and_own_flops_needs_no_edit(
        checkout, capsys, monkeypatch):
    """A copy of ``tiny`` whose ``program`` block sets ``chunk_q`` to 16, so
    the 32-token rows run attention in two chunks, and whose reference
    counts its own FLOPs: new files and new entries only."""
    cfg = dict(TINY, name="tiny_chunked", reference="counted", program={"chunk_q": 16})
    (checkout / "bench/configs/tiny_chunked.json").write_text(json.dumps(cfg))
    (checkout / "bench/refs/counted.py").write_text(COUNTED_REF)
    (checkout / "bench/metrics/probe_flops.py").write_text(FLOPS_METRIC)
    (checkout / "bench/limits/tiny_chunked.save.json").write_text(json.dumps(TINY_LIMITS))
    bench = json.loads((checkout / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny_chunked", "source": TINY["source"],
                             "file": "bench/configs/tiny_chunked.json", "reduced": [],
                             "why": "test"})
    bench["workloads"].append({"name": "tiny_chunked.save", "config": "tiny_chunked",
                               "traffic": "tiny_save", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "probe_flops", "unit": "FLOPs",
                               "better": "higher", "source": "host_clock",
                               "layer": "train step", "moves": "train_tokens_per_s",
                               "workloads": ["tiny_chunked.save"]})
    (checkout / "BENCHMARK.json").write_text(json.dumps(bench))
    from bench import job

    built, build = [], job.build

    def recording_build(*args):
        built.append(build(*args))
        return built[-1]

    monkeypatch.setattr(job, "build", recording_build)

    res, err = run_cell(checkout, "tiny_chunked.save", capsys, trace=1, seconds=1)
    assert res["correct"], err
    assert [j.mcfg.chunk_q for j in built] == [16]
    assert TINY_TRAFFIC["seq_len"] // built[0].mcfg.chunk_q == 2
    assert res["metrics"]["probe_flops"]["value"] == 6.0 * 2 * 64 * 128 + 32
