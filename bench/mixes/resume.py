"""Traffic kind ``resume``: a job lost with its node, resumed from the PFS.

Set-up runs the first ``warm_steps`` steps (their readings go to the
comparison with the reference), saves the state, waits for its flush to
reach ``flush_done``, and takes one more step: the job that was never
interrupted.  The window runs restore cycles while they fit, at least
one and at most ``max_cycles``.  Each cycle drops the L1 level (the lost
node), evicts the PFS files from the page cache, restores the step in a
fresh ``CheckpointManager``, places the state on the mesh and runs the
first step after it.  Every cycle's placed state is compared leaf by
leaf with the state that was saved, and its step's loss with the
uninterrupted job's, bit for bit.
"""
from __future__ import annotations

import shutil
import time

import jax
import numpy as np

from bench import checks, ckpt
from bench.job import build, device_digest, first_steps, reference_readings
from bench.trace import span
from repro.launch.train import place_state


def run(ctx):
    tr, config, seed = ctx.cell.traffic, ctx.cell.config, ctx.seed
    ctx.mark("started")
    job = build(config, tr, seed, ctx.devices)
    ctx.mark("compiled")
    state = job.init_state(seed)
    k = tr["warm_steps"]
    state, prog = first_steps(job, state, seed, k)
    ctx.mark("first_steps")
    digest = jax.jit(device_digest)
    saved = np.asarray(digest(state))
    shape = jax.eval_shape(lambda: state)
    ctx.require_disk(2.2 * ckpt.tree_bytes(shape))
    mgr = ckpt.manager(ctx.ckpt_root, config)
    mgr.save(k, {"train": state, "data": {"batch_idx": np.asarray(k, np.int32)}})
    mgr.wait()
    status = mgr.step_status(k, "pfs")
    mgr.close()
    ctx.mark("setup_save_flushed")
    state, m = job.step(state, job.batch(k))
    uninterrupted = np.asarray(m["loss"]).tobytes()
    del state, m
    if status != "flush_done":
        raise RuntimeError(f"set-up save of step {k} ended at {status!r} on the PFS")

    cycles = []
    ctx.open_window()
    with ctx.tracer.window():
        t_start = time.perf_counter()
        while True:
            with span("bench.drop_l1"):
                shutil.rmtree(ctx.ckpt_root / "local", ignore_errors=True)
                ckpt.evict_from_page_cache(ctx.ckpt_root / "pfs")
            with span("bench.restore"):
                t0 = time.perf_counter()
                mgr = ckpt.manager(ctx.ckpt_root, config, async_flush=False)
                got, restored = mgr.restore(ckpt.target(shape), step=k)
                t1 = time.perf_counter()
            with span("bench.place_state"):
                st = jax.block_until_ready(place_state(restored["train"], job.mesh,
                                                       job.specs))
                t2 = time.perf_counter()
            placed = np.asarray(digest(st))
            with span("bench.first_step"):
                t3 = time.perf_counter()
                st, m = job.step(st, job.batch(k))
                loss = np.asarray(m["loss"])
                t4 = time.perf_counter()
            rr = mgr.last_read_result
            cycles.append({
                "resume_s": (t2 - t0) + (t4 - t3), "restore_s": t1 - t0,
                "place_s": t2 - t1, "step_s": t4 - t3,
                "read_s": rr.duration if rr is not None else None,
                "read_bytes": rr.bytes_read if rr is not None else None,
                "step": got, "cursor": int(restored["data"]["batch_idx"]),
                "differ": int(np.sum(np.any(placed != saved, axis=1))),
                "loss_bits": loss.tobytes(),
            })
            mgr.close()
            del st, m, restored
            now = time.perf_counter()
            if len(cycles) >= tr["max_cycles"] or now + (now - t0) - t_start > ctx.seconds:
                break
        t_end = time.perf_counter()
    mem = ctx.peak_bytes()

    ref = reference_readings(job, seed, k)
    for name, value in checks.training_gaps(prog, ref).items():
        ctx.check(name, value, ctx.cell.limits[name])
    ctx.check("restored_leaves_differ", sum(c["differ"] for c in cycles), 0)
    ctx.check("resumed_cursor_differs",
              sum(c["step"] != k or c["cursor"] != k for c in cycles), 0)
    ctx.check("resumed_loss_differs",
              sum(c["loss_bits"] != uninterrupted for c in cycles), 0)
    ctx.check("pfs_not_read", sum(c["read_s"] is None for c in cycles), 0)
    return {
        "kind": "resume",
        "window_s": t_end - t_start,
        "restores": [{key: c[key] for key in
                      ("resume_s", "restore_s", "place_s", "step_s", "read_s",
                       "read_bytes")} for c in cycles],
        "peak_bytes": mem["peak"],
        "bytes_limit": mem["limit"],
        "attempted": len(cycles),
        "failed": 0,
    }
