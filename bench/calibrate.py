#!/usr/bin/env python3
"""Readings that set the limits of the training comparison.

    python3 bench/calibrate.py --workload <cell> --seeds 12 [--first 1000]

For each seed, in one process: the program's first steps against the
plain reference (the lower readings), the reference computed with float8
matmul operands in the program's place (the control), and the reference
fed half of each batch (a planted fault: half the batch left out, the
mean over the rest).  A step that returns its state unchanged reads 1 on
``change_gap`` by the measure itself and needs no run.  One JSON line per
seed and reading; the benchmark's own runs never run this.
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

import argparse  # noqa: E402
import json  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import checks  # noqa: E402
from bench.harness import Cell, require_chips  # noqa: E402
from bench.job import build, first_steps, reference_readings  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first", type=int, default=2**31 + 1000)
    ap.add_argument("--controls", type=int, default=3,
                    help="seeds (the first ones) that also read the control and fault")
    args = ap.parse_args(argv)
    cell = Cell.load(ROOT, args.workload)
    devices = require_chips(cell.entry["chips"])
    use_compile_cache()
    tr, n = cell.traffic, cell.traffic["warm_steps"]
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    base = build(cell.config, tr, args.first, devices)
    for i in range(args.seeds):
        seed = args.first + i
        job = base.for_seed(seed)
        state = job.init_state(seed)
        state, prog = first_steps(job, state, seed, n)
        del state
        ref = reference_readings(job, seed, n)
        rows = {"program": prog}
        if i < args.controls:
            rows["control_fp8"] = reference_readings(
                job, seed, n, matmul_dtype=jnp.float8_e4m3fn)
            rows["half_batch"] = reference_readings(
                job, seed, n, rows=slice(0, tr["global_batch"] // 2))
        for what, r in rows.items():
            print(json.dumps({"seed": seed, "reading": what,
                              **checks.training_gaps(r, ref),
                              "losses": r.losses.tolist(),
                              "ref_losses": ref.losses.tolist()}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
