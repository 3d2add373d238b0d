"""Runs one cell of ``BENCHMARK.json`` once and prints its result line.

Everything that belongs to one cell is found by name:

* the configuration: the file its ``configs`` entry names, with its
  plain reference at ``bench/refs/<reference>.py``.  The file's published
  keys, its ``program`` fields of the program's ``ModelConfig`` (experts,
  a window or layer pattern, ...) and its ``mesh`` layout (``{"model":
  m}`` for a four-chip cell that splits experts or heads) build the job
  (``bench/job.py``); the reference makes the weights, follows the first
  steps for ``correct``, and may count its own FLOPs a token
  (``train_flops_per_token``, else ``bench/flops.py``'s dense count);
  its ``checkpoint`` block sets the manager and whether L1 is kept on
  the disk or in host RAM (``bench/ckpt.py``);
* the traffic: ``bench/traffic/<traffic>.json``, whose ``kind`` names
  the generator ``bench/mixes/<kind>.py``;
* the limits of the comparison that decides ``correct``:
  ``bench/limits/<workload>.json``;
* each metric: its reader ``bench/metrics/<metric>.py``.

So a new cell, configuration, traffic file or metric is new files and
new ``BENCHMARK.json`` entries, with no edit here.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
RUNS = BENCH / ".runs"  # checkpoints and traces of the current run
SPACE_MARGIN = 1.1  # free space asked for a level, over the state's bytes


class BenchError(SystemExit):
    """The run cannot measure: exits nonzero and prints no result."""

    def __init__(self, msg: str):
        super().__init__(f"bench: {msg}")


def load_json(path: Path) -> Any:
    return json.loads(Path(path).read_text())


def _by_name(entries: List[Dict[str, Any]], name: str, what: str) -> Dict[str, Any]:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"no {what} named {name!r} in BENCHMARK.json")


@dataclass
class Cell:
    """One workload of BENCHMARK.json with everything its name leads to."""

    name: str
    entry: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    mix_path: Path
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @classmethod
    def load(cls, root: Path, name: str) -> "Cell":
        bench = load_json(root / "BENCHMARK.json")
        w = _by_name(bench["workloads"], name, "workload")
        c = _by_name(bench["configs"], w["config"], "config")
        traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
        e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
        reported = {m["name"] for m in e2e}
        per_layer = [m for m in bench["per_layer"]
                     if (name in m["workloads"] if "workloads" in m
                         else m["moves"] in reported)]
        return cls(
            name=name, entry=w, config=load_json(root / c["file"]), traffic=traffic,
            limits=load_json(root / "bench" / "limits" / f"{name}.json"),
            mix_path=root / "bench" / "mixes" / f"{traffic['kind']}.py",
            end_to_end=e2e, per_layer=per_layer,
        )


def require_chips(n: int) -> List[Any]:
    """The first ``n`` TPU chips; anything else ends the run."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found platform {devs[0].platform!r}")
    if len(devs) < n:
        raise BenchError(f"the cell asks for {n} chips, JAX found {len(devs)}")
    return devs[:n]


def peaks_for(kind: str) -> Dict[str, float]:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"no peaks known for device_kind {kind!r}")
    return table[kind]


@dataclass
class Context:
    """What a mix gets: the cell, the run's arguments, the chips, and
    hooks for the set-up clock, the traced window and the checks."""

    cell: Cell
    seed: int
    seconds: float
    devices: List[Any]
    tracer: Any
    t_start: float
    ckpt_root: Path
    l1_ram: Optional[Path] = None  # the run's L1 directory in RAM, if it has one
    setup_s: Optional[float] = None
    checks: List = field(default_factory=list)
    marks: List = field(default_factory=list)

    def mark(self, phase: str) -> None:
        """End of a set-up phase, for the split of ``setup_s``."""
        self.marks.append((phase, time.perf_counter() - self.t_start))

    def open_window(self) -> None:
        self.mark("window_opens")
        self.setup_s = self.marks[-1][1]

    def check(self, name: str, value: float, limit: float) -> None:
        self.checks.append((name, float(value), float(limit)))

    def require_space(self, state_bytes: float, saves: int) -> None:
        """Room for ``saves`` saves of a state of ``state_bytes`` on each
        level, checked before anything is written."""
        self.require_disk(state_bytes, saves)
        self.require_ram(state_bytes, saves)

    def require_disk(self, state_bytes: float, saves: int) -> None:
        """The levels on the disk: the PFS, and L1 when it is there."""
        from bench.ckpt import levels_on_disk

        levels = levels_on_disk(self.cell.config)
        _require_free(self.ckpt_root, SPACE_MARGIN * state_bytes * saves * len(levels),
                      f"disk ({' + '.join(levels)})")

    def require_ram(self, state_bytes: float, saves: int) -> None:
        """An L1 in RAM, under its RAM root."""
        if self.l1_ram is not None:
            _require_free(self.l1_ram, SPACE_MARGIN * state_bytes * saves,
                          f"RAM root {self.l1_ram.parent} (l1)")

    def peak_bytes(self) -> Dict[str, int]:
        stats = [d.memory_stats() or {} for d in self.devices]
        return {"peak": max(s.get("peak_bytes_in_use", 0) for s in stats),
                "limit": min(s.get("bytes_limit", 0) for s in stats)}


def _require_free(path: Path, need_bytes: float, level: str) -> None:
    free = shutil.disk_usage(path).free
    if free < need_bytes:
        raise BenchError(f"{level} holds {free / 1e9:.2f} GB free, the cell's "
                         f"checkpoints there need {need_bytes / 1e9:.2f} GB")


def read_metrics(metrics: List[Dict[str, Any]], rec: Dict[str, Any],
                 loader: Callable[[Path], Any]) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in metrics:
        value = loader(BENCH / "metrics" / f"{m['name']}.py").read(rec)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    return args


def main(argv=None, *, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse_args(argv)
    cell = Cell.load(ROOT, args.workload)
    devices = require_chips(cell.entry["chips"])

    import jax
    from bench import checks as chk
    from bench import ckpt
    from bench.job import load_module
    from bench.trace import Tracer
    from repro.launch.compile_cache import use_compile_cache

    kind = devices[0].device_kind
    peaks = peaks_for(kind)
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    shutil.rmtree(RUNS, ignore_errors=True)
    (RUNS / "ckpt").mkdir(parents=True)
    ram = None
    try:
        ram = ckpt.place_l1(RUNS / "ckpt", cell.config)
        ctx = Context(cell=cell, seed=args.seed, seconds=args.seconds, devices=devices,
                      tracer=Tracer(bool(args.trace), str(RUNS / "trace")),
                      t_start=t_start, ckpt_root=RUNS / "ckpt",
                      l1_ram=ram / "local" if ram else None)
        mix = load_module(cell.mix_path)
        rec = mix.run(ctx)
    finally:
        shutil.rmtree(RUNS, ignore_errors=True)
        if ram is not None:
            shutil.rmtree(ram, ignore_errors=True)
    rec["setup_s"] = ctx.setup_s
    rec["peaks"] = peaks
    rec["chips"] = len(devices)
    rec["trace"] = ctx.tracer.result
    metrics = read_metrics(cell.per_layer if args.trace else cell.end_to_end,
                           rec, load_module)
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": rec["peak_bytes"]}
    result: Dict[str, Any] = {
        "correct": chk.verdict(ctx.checks),
        "attempted": rec["attempted"],
        "failed": rec["failed"],
        "metrics": metrics,
        "device": device,
    }
    if args.trace:
        tr = rec["trace"] or {}
        device["busy_s"] = tr.get("busy_s", 0.0)
        device["window_s"] = tr.get("window_s", rec["window_s"])
        if tr:
            result["breakdown"] = {"device_ops": tr["device_ops"],
                                   "idle_gaps": tr["idle_gaps"]}
    result["checks"] = chk.format_checks(ctx.checks)
    if "written" in rec:
        print("written " + " ".join(f"{k}={v}" for k, v in rec["written"].items()),
              file=sys.stderr)
    print("setup " + " ".join(f"{p}={t:.3f}" for p, t in ctx.marks), file=sys.stderr)
    for name, value, limit in ctx.checks:
        print(f"check {name} {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
