"""One training job of the program under test, built from a cell's files.

The model configuration comes from ``bench/configs/<config>.json`` laid
over the program's registry entry; the weights, the optimizer state and
every batch are made here from ``--seed``, on the device, by the
configuration's plain reference module (``bench/refs/<reference>.py``).
The train step is the program's own (``make_train_step``), compiled
ahead of time for the cell's shapes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec

from repro.configs import get_config
from repro.models import get_model
from repro.models.config import ModelConfig
from repro.train import OptConfig, TrainConfig, make_train_step

BENCH = Path(__file__).resolve().parent

# published config key -> the program's ModelConfig field
HF_KEYS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size", "head_dim": "head_dim",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings", "qkv_bias": "qkv_bias",
    "param_dtype": "param_dtype", "compute_dtype": "compute_dtype",
}


def load_module(path: Path):
    """Import a file of the benchmark by path (its name may hold dots)."""
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(config: Dict[str, Any]):
    return load_module(BENCH / "refs" / f"{config['reference']}.py")


def model_config(config: Dict[str, Any]) -> ModelConfig:
    over = {f: config[k] for k, f in HF_KEYS.items() if k in config}
    return get_config(config["registry"]).replace(**over)


def key_from_seed(seed: int, stream: int) -> jax.Array:
    """A PRNG key from any non-negative seed: both 32-bit halves count."""
    k = jax.random.PRNGKey(stream)
    k = jax.random.fold_in(k, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32((seed >> 32) & 0xFFFFFFFF))


# ------------------------------------------------------------ digests


def _words(x: jnp.ndarray) -> jnp.ndarray:
    bits = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
    return jax.lax.bitcast_convert_type(x, bits).reshape(-1).astype(jnp.uint32)


def device_digest(tree: Any) -> jnp.ndarray:
    """(leaves, 2) uint32: per leaf, the sum of its elements' bit
    patterns and the sum weighted by position, both modulo 2**32."""
    out = []
    for leaf in jax.tree_util.tree_leaves(tree):
        w = _words(jnp.asarray(leaf))
        pos = jnp.arange(1, w.size + 1, dtype=jnp.uint32)
        out.append(jnp.stack([jnp.sum(w, dtype=jnp.uint32),
                              jnp.sum(w * pos, dtype=jnp.uint32)]))
    return jnp.stack(out)


def host_digest(a: np.ndarray) -> np.ndarray:
    """``device_digest`` of one leaf, computed by numpy on the host."""
    a = np.ascontiguousarray(a)
    bits = {1: np.uint8, 2: np.uint16, 4: np.uint32}[a.dtype.itemsize]
    w = a.reshape(-1).view(bits).astype(np.uint32)
    pos = np.arange(1, w.size + 1, dtype=np.uint32)
    return np.array([w.sum(dtype=np.uint32), (w * pos).sum(dtype=np.uint32)], np.uint32)


# ------------------------------------------------------------ the job


@dataclass
class Job:
    config: Dict[str, Any]
    mcfg: ModelConfig
    mesh: Any
    specs: Any
    step: Callable              # compiled program train step (donates its state)
    tokens: Callable            # (data key, idx) -> batch on the device
    seq_len: int
    global_batch: int
    ref: Any
    init_params: Callable       # key -> params
    opt: Dict[str, Any]
    make_state: Callable        # key -> train state, jitted into its shards
    data_key: Any = None

    @property
    def tokens_per_step(self) -> int:
        return self.seq_len * self.global_batch

    def for_seed(self, seed: int) -> "Job":
        """This job with the batches of ``seed``."""
        return dataclasses.replace(self, data_key=key_from_seed(seed, 1))

    def batch(self, idx: int) -> Dict[str, Any]:
        return self.tokens(self.data_key, np.int32(idx))

    def init_state(self, seed: int) -> Any:
        """The program's train state with the reference's weights, built
        in one jitted call straight into its shards."""
        return self.make_state(key_from_seed(seed, 0))


def build(config: Dict[str, Any], traffic: Dict[str, Any], seed: int,
          devices: List[Any]) -> Job:
    mcfg = model_config(config)
    ref = reference(config)
    model = get_model(mcfg)
    mesh = jax.make_mesh((len(devices), 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2, devices=devices)
    seq, gb = traffic["seq_len"], traffic["global_batch"]
    opt = dict(config["optimizer"])
    tcfg = TrainConfig(opt=OptConfig(**opt))
    bstruct = {"tokens": jax.ShapeDtypeStruct((gb, seq), jnp.int32)}
    step_fn, specs, bspecs = make_train_step(model, tcfg, mesh, bstruct)

    init_params = lambda key: ref.init_params(key, config)
    mine = jax.eval_shape(lambda: init_params(key_from_seed(0, 0)))
    theirs = model.param_struct()
    if jax.tree_util.tree_structure(mine) != jax.tree_util.tree_structure(theirs) or any(
        (a.shape, a.dtype) != (b.shape, b.dtype)
        for a, b in zip(jax.tree_util.tree_leaves(mine), jax.tree_util.tree_leaves(theirs))
    ):
        raise ValueError("reference parameter layout differs from the program's")

    def sharded(tree, spec_tree):
        return jax.tree_util.tree_map(
            lambda x, p: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                              sharding=NamedSharding(mesh, p)),
            tree, spec_tree)

    state_shape = {"params": mine, "opt": {"mu": mine, "nu": mine,
                                           "count": jax.ShapeDtypeStruct((), jnp.int32)},
                   "step": jax.ShapeDtypeStruct((), jnp.int32)}
    step = step_fn.lower(sharded(state_shape, specs), sharded(bstruct, bspecs)).compile()

    def make(key):
        p = init_params(key)
        return {"params": p,
                "opt": {"mu": jax.tree_util.tree_map(jnp.zeros_like, p),
                        "nu": jax.tree_util.tree_map(jnp.zeros_like, p),
                        "count": jnp.zeros((), jnp.int32)},
                "step": jnp.zeros((), jnp.int32)}

    shardings = jax.tree_util.tree_map(lambda p: NamedSharding(mesh, p), specs,
                                       is_leaf=lambda x: isinstance(x, PartitionSpec))
    make_state = jax.jit(make, out_shardings=shardings)
    # keys are arguments, never constants: every seed runs the same programs
    vocab = mcfg.vocab_size
    tokens = jax.jit(lambda key, idx: {"tokens": jax.random.randint(
        jax.random.fold_in(key, idx), (gb, seq), 0, vocab, jnp.int32)},
        out_shardings={"tokens": NamedSharding(mesh, bspecs["tokens"])})
    return Job(config, mcfg, mesh, specs, step, tokens, seq, gb, ref,
               init_params, opt, make_state).for_seed(seed)


# ------------------------------------------------------------ readings


@dataclass
class Readings:
    """What a run of the first steps gave, the program's or the
    reference's: each loss, the per-leaf norms of the first gradient as
    the optimizer got it, and of the parameters' change over the steps."""

    losses: np.ndarray
    grad_norms: np.ndarray
    change_norms: np.ndarray


def first_steps(job: Job, state: Any, seed: int, n: int):
    """Drive the program's step through batches 0..n-1 (the window's own
    call and feed).  Returns (state, Readings)."""
    b1 = job.opt["beta1"]
    grad_norms = jax.jit(lambda st: job.ref.leaf_norms(st["opt"]["mu"]) / (1.0 - b1))
    change = jax.jit(lambda st, key: job.ref.leaf_norms(jax.tree_util.tree_map(
        lambda p, q: p.astype(jnp.float32) - q.astype(jnp.float32),
        st["params"], job.init_params(key))))
    losses, g = [], None
    for i in range(n):
        state, m = job.step(state, job.batch(i))
        losses.append(float(m["loss"]))
        if i == 0:
            g = np.asarray(grad_norms(state))
    return state, Readings(np.asarray(losses), g,
                           np.asarray(change(state, key_from_seed(seed, 0))))


def reference_readings(job: Job, seed: int, n: int, *, matmul_dtype=None,
                       rows: slice = slice(None)) -> Readings:
    """The plain reference over the same weights and batches.  ``rows``
    keeps part of each batch (a planted fault: half a batch left out)."""
    params0 = jax.jit(job.init_params)(key_from_seed(seed, 0))
    batches = [job.batch(i)["tokens"][rows] for i in range(n)]
    r = job.ref.train_readings(params0, batches, job.config, job.opt,
                               matmul_dtype=matmul_dtype)
    return Readings(r["losses"], r["grad_norms"], r["change_norms"])
