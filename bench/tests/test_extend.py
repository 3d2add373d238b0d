"""A configuration, a traffic kind and a metric added as new files only:
the harness finds each by its name in BENCHMARK.json."""
from __future__ import annotations

import json

from conftest import run_cell

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
