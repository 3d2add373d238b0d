"""End-to-end training driver with aggregated async checkpointing.

Example (CPU smoke scale):

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --smoke --steps 30 --ckpt-every 10 --strategy stripe_aligned \
        --root /tmp/ckpt_demo --nodes 4 --ppn 2

Restart resumes from the deepest complete checkpoint level, including
optimizer moments and the data-pipeline cursor (bit-exact batch replay).
"""
from __future__ import annotations

import argparse
import time
from pathlib import Path

import jax
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs import get_config, get_smoke_config
from repro.core import CheckpointConfig, CheckpointManager, theta_like
from repro.data import DataConfig, SyntheticTokens
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import get_model
from repro.train import OptConfig, TrainConfig, init_train_state, make_train_step


def build_argparser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    # checkpointing
    ap.add_argument("--root", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--strategy", default="stripe_aligned",
                    choices=["file_per_process", "posix", "mpiio",
                             "stripe_aligned", "gio_sync"])
    ap.add_argument("--codec", default="none",
                    choices=["none", "zstd", "zstd+delta"])
    ap.add_argument("--precodec", default="none", choices=["none", "int8"])
    ap.add_argument("--io-threads", type=int, default=2)
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--ppn", type=int, default=2)
    ap.add_argument("--keep", type=int, default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--partner-replication", action="store_true")
    return ap


def _shardings(mesh, specs):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )


def place_state(state, mesh, specs):
    """Put a train state (device or host arrays) onto ``mesh`` under the
    train step's partition ``specs``.  Host arrays go straight to their
    shards, never through one whole copy on the first device."""
    return jax.device_put(state, _shardings(mesh, specs))


def init_state(init, mesh, specs):
    """Run the train-state initialiser ``init`` straight into its shards:
    each device computes its own part, so no device ever holds the whole
    state (run eagerly, ``init`` would build all of it on the first)."""
    return jax.jit(init, out_shardings=_shardings(mesh, specs))()


def main(argv=None) -> int:
    args = build_argparser().parse_args(argv)
    use_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = get_model(cfg)
    mesh = make_host_mesh()

    data = SyntheticTokens(
        DataConfig(
            vocab_size=cfg.vocab_size,
            seq_len=args.seq_len,
            global_batch=args.global_batch,
            n_patches=cfg.n_patches,
            enc_seq=cfg.enc_seq if cfg.family == "audio" else 0,
            d_model=cfg.d_model,
            family=cfg.family,
        )
    )
    tcfg = TrainConfig(
        opt=OptConfig(lr=args.lr, total_steps=args.steps),
        microbatches=args.microbatches,
    )
    batch_struct = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), data.peek(0)
    )
    step_fn, state_specs, _ = make_train_step(model, tcfg, mesh, batch_struct)

    cluster = theta_like(args.nodes, args.ppn)
    mgr = CheckpointManager(
        CheckpointConfig(
            root=args.root,
            cluster=cluster,
            strategy=args.strategy,
            codec=args.codec,
            precodec=args.precodec,
            io_threads=args.io_threads,
            keep_n=args.keep,
            partner_replication=args.partner_replication,
        )
    )

    init = lambda: init_train_state(model, jax.random.PRNGKey(0), tcfg)
    state = None
    start = 0
    if args.resume:
        try:
            # shapes only: a restore target needs no initialised state
            target = {"train": jax.eval_shape(init), "data": data.state_tree()}
            step, restored = mgr.restore(target)
            state = place_state(restored["train"], mesh, state_specs)
            data.load_state(restored["data"])
            start = int(state["step"])
            print(f"[resume] restored step {step} (train step {start})")
        except FileNotFoundError:
            print("[resume] no checkpoint found; cold start")
    if state is None:
        state = init_state(init, mesh, state_specs)

    t_step_accum = 0.0
    for i in range(start, args.steps):
        batch = data.next()
        t0 = time.perf_counter()
        state, metrics = step_fn(state, batch)
        loss = float(metrics["loss"])
        dt = time.perf_counter() - t0
        t_step_accum += dt
        if (i + 1) % args.ckpt_every == 0 or (i + 1) == args.steps:
            st = mgr.save(i + 1, {"train": state, "data": data.state_tree()})
            print(
                f"step {i+1:5d} loss {loss:.4f} step_time {dt*1e3:7.1f} ms  "
                f"[ckpt local {st.local_time*1e3:.1f} ms, "
                f"{st.raw_bytes/1e6:.1f} MB raw -> {st.stored_bytes/1e6:.1f} MB]"
            )
        else:
            print(f"step {i+1:5d} loss {loss:.4f} step_time {dt*1e3:7.1f} ms")
    mgr.wait()
    if mgr.flush_errors:
        print("flush errors:", mgr.flush_errors)
        return 1
    flushes = [s for s in mgr.stats if s.flush is not None]
    if flushes:
        tot = sum(f.flush.bytes_written for f in flushes)
        dur = sum(f.flush.duration for f in flushes)
        print(
            f"[ckpt] {len(flushes)} flushes, {tot/1e6:.1f} MB, "
            f"avg flush {dur/len(flushes)*1e3:.1f} ms, "
            f"blocking overhead {sum(f.local_time for f in flushes)*1e3:.1f} ms "
            f"vs compute {t_step_accum*1e3:.1f} ms"
        )
    mgr.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
