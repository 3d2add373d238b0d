"""Traffic kind ``save``: a training job that checkpoints as it trains.

Set-up compiles the step, builds the state from the seed and runs the
first ``warm_steps`` steps, whose readings go to the comparison with the
reference.  The window opens with a save and saves again after every
``save_every_steps`` steps, at most ``max_saves`` times, through
``CheckpointManager.save`` with its asynchronous flush to the PFS level;
training goes on while the flushes drain.  After the window the run
waits up to ``flush_timeout_s`` for every save to reach ``flush_done``,
then reads each saved step back from L1 alone and from the PFS alone
and compares it, leaf by leaf, with the state it was saved from.  Last,
it places the first saved step from L1 on the mesh and replays the
window's steps up to the second save with no save between: the state
it reaches has to be the second save's, bit for bit, so the window's
own steps, the ones that ran beside ``save()`` and the flush, are
checked as well.
"""
from __future__ import annotations

import sys
import time

import jax
import numpy as np

from bench import checks, ckpt
from bench.flops import train_flops_per_token
from bench.job import build, device_digest, first_steps, reference_readings
from bench.trace import span
from repro.launch.train import place_state


def run(ctx):
    tr, config, seed = ctx.cell.traffic, ctx.cell.config, ctx.seed
    ctx.mark("started")
    job = build(config, tr, seed, ctx.devices)
    ctx.mark("compiled")
    state = job.init_state(seed)
    state, prog = first_steps(job, state, seed, tr["warm_steps"])
    ctx.mark("first_steps")
    done = tr["warm_steps"]
    digest = jax.jit(device_digest)
    jax.block_until_ready(digest(state))
    shape = jax.eval_shape(lambda: state)
    ctx.require_disk(2.2 * ckpt.tree_bytes(shape) * tr["max_saves"])
    mgr = ckpt.manager(ctx.ckpt_root, config)
    durable = {}
    mgr.subscribe(lambda s: durable.setdefault(s, time.perf_counter()))
    saves, losses = [], []

    def save(step, state):
        d = digest(state)
        with span("bench.save"):
            t0 = time.perf_counter()
            st = mgr.save(step, {"train": state,
                                 "data": {"batch_idx": np.asarray(step, np.int32)}})
            t1 = time.perf_counter()
        saves.append({"step": step, "t_call": t0, "t_return": t1, "stats": st,
                      "digest": np.asarray(d)})

    K, cap = tr["save_every_steps"], tr["max_saves"]
    ctx.open_window()
    with ctx.tracer.window():
        t_start = time.perf_counter()
        save(done, state)
        since, steps, prev = 0, 0, None
        while True:
            with span("bench.train_step"):
                state, m = job.step(state, job.batch(done))
                if prev is not None:
                    losses.append(float(prev["loss"]))
            done, steps, since, prev = done + 1, steps + 1, since + 1, m
            over = time.perf_counter() - t_start >= ctx.seconds
            if over or (since >= K and len(saves) < cap):
                with span("bench.train_step"):
                    losses.append(float(prev["loss"]))
                prev = None
                if over:
                    break
                save(done, state)
                since = 0
        t_end = time.perf_counter()

    deadline = time.perf_counter() + tr["flush_timeout_s"]
    while (any(s["step"] not in durable for s in saves) and not mgr.flush_errors
           and time.perf_counter() < deadline):
        time.sleep(0.02)
    mem = ctx.peak_bytes()
    del state, m
    mgr.close()
    missing = [s["step"] for s in saves if s["step"] not in durable]

    # every saved step, read back from each level alone
    bad, first = {"l1": 0, "pfs": 0}, None
    for s in saves:
        for level, key in (("local", "l1"), ("pfs", "pfs")):
            tree = ckpt.read_back(ctx.ckpt_root, config, s["step"], shape, level)
            if tree is None or int(tree["data"]["batch_idx"]) != s["step"]:
                bad[key] += len(s["digest"])
            else:
                bad[key] += ckpt.leaves_differing(tree["train"], s["digest"])
                if s is saves[0] and level == "local":
                    first = tree["train"]
            del tree
    ctx.check("l1_leaves_differ", bad["l1"], 0)
    ctx.check("pfs_leaves_differ", bad["pfs"], 0)
    ctx.check("flushes_missing", len(missing), 0)
    ctx.check("replay_leaves_differ", replay(job, digest, first, saves), 0)

    # the program's first steps against the plain reference
    ref = reference_readings(job, seed, tr["warm_steps"])
    for name, value in checks.training_gaps(prog, ref).items():
        ctx.check(name, value, ctx.cell.limits[name])
    ctx.check("window_losses_not_finite", int(not np.all(np.isfinite(losses))), 0)

    records = []
    for s in saves:
        st, fl = s["stats"], s["stats"].flush
        records.append({
            "t_call": s["t_call"], "t_return": s["t_return"],
            "encode_s": st.encode_time, "local_s": st.local_time,
            "raw_bytes": st.raw_bytes, "durable_at": durable.get(s["step"]),
            "flush_s": fl.duration if fl is not None else None,
            "flush_bytes": fl.bytes_written if fl is not None else None,
        })
        r = records[-1]
        print(f"save step={s['step']} at_s={r['t_call'] - t_start:.3f} "
              f"stall_s={r['t_return'] - r['t_call']:.4f} encode_s={r['encode_s']:.4f} "
              f"local_s={r['local_s']:.4f} flush_s={r['flush_s']} "
              f"durable_s={(r['durable_at'] or float('nan')) - r['t_call']:.4f}",
              file=sys.stderr)
    return {
        "kind": "save",
        "window_s": t_end - t_start,
        "steps": steps,
        "tokens": steps * job.tokens_per_step,
        "flops_per_token": train_flops_per_token(config, job.seq_len),
        "saves": records,
        "peak_bytes": mem["peak"],
        "bytes_limit": mem["limit"],
        "attempted": len(saves),
        "failed": len(missing),
    }


def replay(job, digest, first, saves) -> int:
    """Leaves that differ between the second save and the first save
    (read back from L1) driven through the window's steps between them
    with no save; every leaf when there is no such pair to compare."""
    if len(saves) < 2 or first is None:
        return len(saves[0]["digest"]) if saves else 1
    st = place_state(first, job.mesh, job.specs)
    for i in range(saves[0]["step"], saves[1]["step"]):
        st, _ = job.step(st, job.batch(i))
    return int(np.sum(np.any(np.asarray(digest(st)) != saves[1]["digest"], axis=1)))
