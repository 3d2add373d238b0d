#!/usr/bin/env python3
"""Smoke run of the checkpointing runtime's main path on a TPU.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: phase 3 only, FSDP over (4, 1)

1. Refuse to run on anything but a TPU.  A ``JAX_PLATFORMS`` that leaves
   the TPU out is refused, not followed.
2. Kernels: the fused pre-codec pass over a 256 MiB stream in 1 MiB
   chunks, and int8 ``quantize``, each compiled for the chip
   (``tpu_custom_call`` in the lowered program) and checked against its
   numpy oracle.
3. Train, save, flush, restore: qwen1.5-0.5b at its published widths
   (random weights from ``--seed``) trains to step K, is saved through
   ``CheckpointManager`` (``stripe_aligned``, codec ``none``), flushed
   to the PFS level, and trains on to step N.  The node-local L1 copy
   is then deleted and a fresh manager restores step K from the PFS
   level.  The resumed run must repeat every loss and the final train
   state bit for bit.
4. Device pre-codec: the params tree and its one-step update are staged
   on the device and saved as an anchor and a delta (``zstd+delta``,
   1 MiB chunks); both restore byte-identical to a host-path save of the
   same trees.

Any failed phase or comparison raises, and the exit code is nonzero.
Times and sizes printed on ``smoke`` lines describe this one run; they
are not benchmark metrics.  The last line of standard output is a JSON
object naming the device.  Checkpoints live under ``.smoke_ckpt/`` in
the checkout and are deleted at exit.  If that disk cannot hold the L1
and PFS copies of the full state, the model's depth (never a width) is
cut, and the cut is printed.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Dict, List

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import CheckpointConfig, CheckpointManager, Manifest, theta_like  # noqa: E402
from repro.data import DataConfig, SyntheticTokens  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.train import init_state, place_state  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models import get_model  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.train import OptConfig, TrainConfig, init_train_state, make_train_step  # noqa: E402
from repro.utils.treelib import flatten_with_names  # noqa: E402

ARCH = "qwen1.5-0.5b"
CHUNK = 1 << 20            # the engine's default chunk_size
KERNEL_STREAM = 256 << 20  # bytes through the fused pass in phase 2
SAVE_AT, STEPS = 4, 10     # save after step K, train on to step N
BATCH, SEQ = 8, 256        # global batch x sequence length per step
NODES, PPN = 4, 2          # launch/train.py's default cluster
GB = 1e9


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def smoke(**kv: Any) -> None:
    print("smoke", " ".join(f"{k}={v}" for k, v in kv.items()), flush=True)


def tree_bytes(tree: Any) -> int:
    return sum(
        int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
        for x in jax.tree_util.tree_leaves(tree)
    )


def tree_digest(tree: Any) -> str:
    """sha256 over every leaf's name, dtype, shape and bytes, one leaf on
    the host at a time."""
    h = hashlib.sha256()
    for name, leaf in flatten_with_names(tree)[0]:
        a = np.ascontiguousarray(np.asarray(leaf))
        h.update(f"{name}|{a.dtype}|{a.shape}".encode())
        h.update(a.reshape(-1).view(np.uint8))
    return h.hexdigest()


def trees_equal(a: Any, b: Any) -> bool:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.asarray(x).dtype == np.asarray(y).dtype
        and np.array_equal(
            np.asarray(x).reshape(-1).view(np.uint8),
            np.asarray(y).reshape(-1).view(np.uint8),
        )
        for x, y in zip(la, lb)
    )


def peak_hbm() -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.local_devices()]
    check(all(p is not None for p in peaks), "device reports no peak_bytes_in_use")
    return max(peaks)


def require_tpu(chips: int) -> List[Any]:
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "tpu" not in plats.split(","):
        raise SystemExit(
            f"chip_smoke: JAX_PLATFORMS={plats!r} leaves out the TPU; "
            "this smoke runs only on a TPU"
        )
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} but {len(devs)} device(s)")
    return devs


# ---------------------------------------------------------------- phase 2


def kernel_phase(seed: int) -> None:
    from repro.kernels.fused import digests_from_meta, dirty_from_meta, fused_precodec, fused_ref
    from repro.kernels.quantize import quantize
    from repro.kernels.quantize.ref import quantize_ref

    cw = CHUNK // 4
    n = KERNEL_STREAM // 4
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    cur = jax.random.bits(k1, (n,), jnp.uint32)
    pos = jnp.arange(n, dtype=jnp.uint32)
    # every third chunk gets a few changed words; the rest stay clean
    flip = ((pos // cw) % 3 == 0) & (pos % 4099 == 7)
    base = jnp.where(flip, cur ^ jnp.uint32(0xDEADBEEF), cur)

    lowered = fused_precodec.lower(cur, base, chunk_words=cw, interpret=False)
    check("tpu_custom_call" in lowered.as_text(), "fused pass is not a TPU kernel")
    t0 = time.perf_counter()
    jax.block_until_ready(fused_precodec(cur, base, chunk_words=cw, interpret=False))
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    delta, meta = jax.block_until_ready(
        fused_precodec(cur, base, chunk_words=cw, interpret=False)
    )
    run_s = time.perf_counter() - t0

    c, b = np.asarray(cur), np.asarray(base)
    d = np.asarray(delta).reshape(-1, cw)
    meta = np.asarray(meta)
    dirty, digests = dirty_from_meta(meta), digests_from_meta(meta)
    n_chunks = n // cw
    check(d.shape[0] == n_chunks == len(dirty), "fused pass chunk count")
    for i in range(n_chunks):
        sl = slice(i * cw, (i + 1) * cw)
        rd, rc, rg = fused_ref(c[sl], b[sl], cw)
        check(np.array_equal(d[i], rd[0]), f"fused delta differs in chunk {i}")
        check(int(meta[i, 0]) == int(rc[0]), f"fused changed count differs in chunk {i}")
        check(bool(dirty[i]) == bool(rc[0] > 0), f"fused dirty mask differs in chunk {i}")
        check(int(digests[i]) == int(rg[0]), f"fused digest differs in chunk {i}")
    check(0 < int(dirty.sum()) < n_chunks, "kernel input has no mix of clean and dirty chunks")
    smoke(phase="kernels", fused_stream_bytes=KERNEL_STREAM, chunk_bytes=CHUNK,
          chunks=n_chunks, dirty_chunks=int(dirty.sum()),
          fused_first_call_s=first_s, fused_s=run_s)

    x = jax.random.normal(k2, (16 << 20,), jnp.float32) * 7
    lowered = quantize.lower(x, interpret=False)
    check("tpu_custom_call" in lowered.as_text(), "quantize is not a TPU kernel")
    q, s = jax.block_until_ready(quantize(x, interpret=False))
    rq, rs = quantize_ref(np.asarray(x).reshape(-1, 128))
    # the tests' bound: f32 division may differ by one ulp at rounding ties
    diff = np.abs(np.asarray(q).astype(np.int32) - rq.astype(np.int32))
    check(diff.max() <= 1 and (diff != 0).mean() < 1e-3, "quantize differs from its oracle")
    check(np.allclose(np.asarray(s), rs, rtol=1e-6, atol=0), "quantize scales differ")
    smoke(phase="kernels", quantize_elems=x.size, quantize_ulp_diffs=int((diff != 0).sum()))


# ---------------------------------------------------------------- the job


@dataclass
class Job:
    """One training job, built through launch/train.py's functions."""

    cfg: ModelConfig
    mesh: Any
    data_cfg: DataConfig
    specs: Any          # train-state partition specs
    step: Callable      # the compiled train step
    init: Callable      # -> fresh train state (random weights from the seed)
    batch_sharding: Any
    compile_s: float


def build_job(cfg: ModelConfig, seed: int, batch: int, seq: int, steps: int) -> Job:
    model = get_model(cfg)
    mesh = make_host_mesh()
    data_cfg = DataConfig(
        vocab_size=cfg.vocab_size, seq_len=seq, global_batch=batch, seed=seed,
        d_model=cfg.d_model, family=cfg.family,
    )
    data = SyntheticTokens(data_cfg)
    tcfg = TrainConfig(opt=OptConfig(lr=3e-4, total_steps=steps))
    bstruct = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), data.peek(0)
    )
    step_fn, specs, bspecs = make_train_step(model, tcfg, mesh, bstruct)
    init = lambda: init_train_state(model, jax.random.PRNGKey(seed), tcfg)
    state = jax.eval_shape(init)
    sh = lambda t, s: jax.tree_util.tree_map(
        lambda x, p: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=NamedSharding(mesh, p)), t, s
    )
    t0 = time.perf_counter()
    step = step_fn.lower(sh(state, specs), sh(bstruct, bspecs)).compile()
    compile_s = time.perf_counter() - t0
    bsh = jax.tree_util.tree_map(lambda p: NamedSharding(mesh, p), bspecs)
    return Job(cfg, mesh, data_cfg, specs, step, init, bsh, compile_s)


def check_placement(job: Job, state: Any) -> Dict[str, int]:
    """Bytes each device holds; a state sharded over several devices
    must really be spread over them, not all on the first."""
    held: Dict[str, int] = {}
    for leaf in jax.tree_util.tree_leaves(state):
        for sh in leaf.addressable_shards:
            held[str(sh.device.id)] = held.get(str(sh.device.id), 0) + sh.data.nbytes
    devs = job.mesh.devices.size
    check(len(held) == devs, f"state is on {len(held)} of {devs} devices")
    if devs > 1:
        total = tree_bytes(state)
        check(max(held.values()) < total / 2, "state is not sharded across devices")
    return held


def ckpt_config(root: Path, **kw: Any) -> CheckpointConfig:
    return CheckpointConfig(
        root=str(root), cluster=theta_like(NODES, PPN), strategy="stripe_aligned", **kw
    )


def train(job: Job, state: Any, data: SyntheticTokens, first: int, last: int,
          on_step: Callable[[int, Any], None] = lambda i, st: None):
    """Steps ``first..last``; returns (state, {step: loss bits}, step seconds)."""
    losses, times = {}, {}
    for i in range(first, last + 1):
        batch = jax.device_put(data.next(), job.batch_sharding)
        t0 = time.perf_counter()
        state, m = jax.block_until_ready(job.step(state, batch))
        loss = np.asarray(m["loss"])
        times[i] = time.perf_counter() - t0
        check(bool(np.isfinite(loss)), f"loss at step {i} is not finite")
        losses[i] = loss.tobytes()
        on_step(i, state)
    return state, losses, times


# ---------------------------------------------------------------- phase 3


def resume_phase(job: Job, root: Path) -> None:
    state_bytes = tree_bytes(jax.eval_shape(job.init))
    state = init_state(job.init, job.mesh, job.specs)
    held = check_placement(job, state)
    smoke(phase="train", arch=job.cfg.name, n_layers=job.cfg.n_layers,
          state_bytes=state_bytes, devices=job.mesh.devices.size,
          bytes_per_device=json.dumps(held, separators=(",", ":")),
          compile_s=job.compile_s)

    mgr = CheckpointManager(ckpt_config(root))
    data = SyntheticTokens(job.data_cfg)
    saved: Dict[str, Any] = {}

    def save_at_k(i: int, st: Any) -> None:
        if i != SAVE_AT:
            return
        t0 = time.perf_counter()
        saved["stats"] = mgr.save(SAVE_AT, {"train": st, "data": data.state_tree()})
        saved["save_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        mgr.wait()
        saved["wait_s"] = time.perf_counter() - t0
        check(not mgr.flush_errors, f"flush errors: {mgr.flush_errors}")
        status = mgr.step_status(SAVE_AT, "pfs")
        check(status == "flush_done", f"step {SAVE_AT} PFS status {status!r}")

    state, losses, times = train(job, state, data, 1, STEPS, save_at_k)
    digest = tree_digest(state)
    del state
    st = saved["stats"]
    check(st.flush is not None and not st.flush.failed, "flush result missing or failed")
    plain = [t for i, t in times.items() if i != SAVE_AT]
    smoke(phase="train", step_s_median=float(np.median(plain)), steps_timed=len(plain),
          step_with_save_s=times[SAVE_AT] + saved["save_s"], saves_timed=1)
    smoke(phase="save", step=SAVE_AT, raw_bytes=st.raw_bytes, stored_bytes=st.stored_bytes,
          encode_s=st.encode_time, local_time_s=st.local_time, save_call_s=saved["save_s"])
    smoke(phase="flush", step=SAVE_AT, strategy="stripe_aligned",
          flush_duration_s=st.flush.duration, flush_bytes=st.flush.bytes_written,
          wait_s=saved["wait_s"])
    mgr.close()
    del mgr, saved, st

    # a lost node: only the PFS level is left to restore from
    shutil.rmtree(root / "local")
    t0 = time.perf_counter()
    mgr = CheckpointManager(ckpt_config(root, async_flush=False))
    target = {"train": jax.eval_shape(job.init), "data": data.state_tree()}
    got, restored = mgr.restore(target, step=SAVE_AT)
    state = jax.block_until_ready(place_state(restored["train"], job.mesh, job.specs))
    restore_s = time.perf_counter() - t0
    check(got == SAVE_AT, f"restored step {got}, wanted {SAVE_AT}")
    check(mgr.last_read_result is not None, "restore did not read the PFS level")
    data = SyntheticTokens(job.data_cfg)
    data.load_state(restored["data"])
    check(int(data.state["batch_idx"]) == SAVE_AT, "data cursor not restored")
    del restored
    check_placement(job, state)
    smoke(phase="restore", step=SAVE_AT, level="pfs", restore_s=restore_s)

    state, losses2, _ = train(job, state, data, SAVE_AT + 1, STEPS)
    check(tree_digest(state) == digest, "resumed final state differs from the uninterrupted run")
    for i in range(SAVE_AT + 1, STEPS + 1):
        check(losses2[i] == losses[i], f"resumed loss differs at step {i}")
    del state
    mgr.close()
    peak = peak_hbm()
    if job.mesh.devices.size > 1:
        check(peak < state_bytes, f"a device held {peak} bytes: the whole sharded state")
    smoke(phase="resume", steps_compared=STEPS - SAVE_AT, losses_bit_identical=True,
          final_state_sha256=digest[:16], peak_bytes_in_use=peak)


# ---------------------------------------------------------------- phase 4


def precodec_phase(job: Job, root: Path) -> None:
    state = init_state(job.init, job.mesh, job.specs)
    p0 = jax.device_get(state["params"])
    data = SyntheticTokens(job.data_cfg)
    state, _ = job.step(state, jax.device_put(data.next(), job.batch_sharding))
    p1 = jax.device_get(state["params"])
    del state
    staged_bytes = tree_bytes(p0)
    trees = {1: p0, 2: p1}
    shape = jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), p0)

    restored = {}
    for path in ("device", "host"):
        mgr = CheckpointManager(ckpt_config(
            root / path, codec="zstd+delta", chunk_size=CHUNK,
            device_precodec=path == "device", chunk_aligned_split=True,
        ))
        for step, tree in trees.items():
            if path == "device":
                dev = jax.block_until_ready(place_state(tree, job.mesh, job.specs["params"]))
                mgr.stage(step, dev)
                st = mgr.save(step, dev)
                del dev
                smoke(phase="precodec", step=step, staged_bytes=staged_bytes,
                      stored_bytes=st.stored_bytes, stage_s=st.stage_s,
                      stage_wait_s=st.stage_wait_s, peak_bytes_in_use=peak_hbm())
            else:
                mgr.save(step, tree)
        mgr.wait()
        check(not mgr.flush_errors, f"{path} flush errors: {mgr.flush_errors}")
        for step in trees:
            status = mgr.step_status(step, "pfs")
            check(status == "flush_done", f"{path} step {step} PFS status {status!r}")
        man = Manifest.from_json(
            (root / path / "pfs" / f"step_{2:08d}" / "manifest.json").read_text()
        )
        check(man.base_step == 1, f"{path} step 2 is not a delta on step 1")
        mgr.close()
        mgr = CheckpointManager(ckpt_config(
            root / path, codec="zstd+delta", chunk_size=CHUNK, async_flush=False,
        ))
        restored[path] = {s: mgr.restore(shape, step=s)[1] for s in trees}
        mgr.close()
        shutil.rmtree(root / path)
    for step, tree in trees.items():
        check(trees_equal(restored["device"][step], restored["host"][step]),
              f"device-path step {step} restores differently from the host path")
        check(trees_equal(restored["device"][step], tree),
              f"device-path step {step} does not restore the saved tree")
    smoke(phase="precodec", restored_byte_identical=True, steps=len(trees))


# ---------------------------------------------------------------- main


def fit_depth(cfg: ModelConfig, root: Path, seed: int) -> ModelConfig:
    """The deepest cut of ``cfg`` whose checkpoints fit the free disk:
    L1 and PFS each hold a copy of the whole train state."""
    free = shutil.disk_usage(root).free
    full = cfg.n_layers
    while True:
        model = get_model(cfg)
        tcfg = TrainConfig(opt=OptConfig())
        need = 2.2 * tree_bytes(jax.eval_shape(
            lambda: init_train_state(model, jax.random.PRNGKey(seed), tcfg)
        ))
        if need <= free or cfg.n_layers == 1:
            break
        cfg = cfg.replace(n_layers=cfg.n_layers - 1)
    check(need <= free, f"disk holds {free / GB:.1f} GB, even one layer needs {need / GB:.1f} GB")
    if cfg.n_layers != full:
        print(f"smoke depth_cut n_layers {full} -> {cfg.n_layers} "
              f"(disk free {free / GB:.1f} GB, checkpoints need {need / GB:.1f} GB)")
    return cfg


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded train/save/restore phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = require_tpu(args.chips)
    cache = use_compile_cache()
    t_start = time.perf_counter()
    print(f"smoke output: times and sizes on 'smoke' lines describe this run; "
          f"they are not benchmark metrics. device_kind={devs[0].device_kind} "
          f"devices={len(devs)} compile_cache={cache}", flush=True)
    root = ROOT / ".smoke_ckpt"
    shutil.rmtree(root, ignore_errors=True)
    root.mkdir()
    try:
        if args.chips == 1:
            t0 = time.perf_counter()
            kernel_phase(args.seed)
            smoke(phase="kernels", phase_s=time.perf_counter() - t0)
        cfg = fit_depth(get_config(ARCH), root, args.seed)
        t0 = time.perf_counter()
        job = build_job(cfg, args.seed, BATCH, SEQ, STEPS)
        resume_phase(job, root / "train")
        smoke(phase="train+resume", phase_s=time.perf_counter() - t0)
        if args.chips == 1:
            t0 = time.perf_counter()
            precodec_phase(job, root / "precodec")
            smoke(phase="precodec", phase_s=time.perf_counter() - t0)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    smoke(total_s=time.perf_counter() - t_start)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
