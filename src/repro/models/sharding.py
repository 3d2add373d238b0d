"""Partition-spec rules: map every param/batch/cache leaf to mesh axes.

Scheme: FSDP over ("pod", "data") — weights sharded on a feature dim,
gathered just-in-time by GSPMD — and tensor parallelism over "model".
Rules are name+rank based and *divisibility-guarded*: a dim is only
sharded by axes whose size product divides it (e.g. whisper's vocab
51865 stays unsharded; 10-head attention replicates heads but still
shards d_ff).  GSPMD propagates everything else.
"""
from __future__ import annotations

import contextvars
import math
from contextlib import contextmanager
from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig
from repro.utils.treelib import flatten_with_names

# ---------------------------------------------------------------------------
# Activation-sharding context: model code calls shard_act(x, "dp", None,
# "tp") at layer boundaries; constraints are no-ops unless a harness has
# activated a mesh (GSPMD otherwise drops batch sharding across
# remat+scan boundaries and replicates compute — observed 8x flop
# inflation on the 16x16 mesh without these pins).
# ---------------------------------------------------------------------------

_ACT_RULES: "contextvars.ContextVar[Optional[dict]]" = contextvars.ContextVar(
    "repro_act_rules", default=None
)


@contextmanager
def activation_sharding(mesh: Mesh):
    """Enable bare-PartitionSpec activation constraints for this mesh."""
    rules = {
        "dp": fsdp_axes(mesh),
        "tp": "model",
        "sizes": {a: int(mesh.shape[a]) for a in mesh.axis_names},
    }
    jax.set_mesh(mesh)
    token = _ACT_RULES.set(rules)
    try:
        yield
    finally:
        _ACT_RULES.reset(token)


def tp_size() -> int:
    """Active TP degree (1 when no mesh context is active)."""
    rules = _ACT_RULES.get()
    if rules is None:
        return 1
    return int(rules["sizes"].get(rules["tp"], 1))


def shard_act(x, *kinds):
    """Constrain activation dims: kinds from {"dp", "tp", None} per dim.

    Divisibility-guarded: an axis that does not divide the dim is
    dropped (e.g. 10-head attention under 16-way TP replicates heads).
    """
    rules = _ACT_RULES.get()
    if rules is None:
        return x
    sizes = rules["sizes"]

    def ok(dim: int, axes) -> Optional[Any]:
        if axes is None:
            return None
        seq = (axes,) if isinstance(axes, str) else tuple(axes)
        prod = 1
        for a in seq:
            prod *= sizes.get(a, 1)
        if dim % prod == 0:
            return axes
        for k in range(len(seq) - 1, 0, -1):
            prod = 1
            for a in seq[:k]:
                prod *= sizes.get(a, 1)
            if dim % prod == 0:
                return seq[:k]
        return None

    spec = P(*[ok(x.shape[i], rules.get(k) if k else None) for i, k in enumerate(kinds)])
    return jax.lax.with_sharding_constraint(x, spec)

# leaf-name fragments whose *first* big axis is the contraction output
# (down-projections: shard input dim by TP, output dim by FSDP)
_DOWN_NAMES = ("w_down", "wo", "m_down", "w_out", "shared_down")
_REPLICATE_NAMES = (
    "ln", "final_norm", "gn", "_s']", "_b']", "conv_b", "lam", "b_r", "b_i",
    "bq", "bk", "bv", "bo", "b_in", "b_out", "['b']", "['r']", "enc_pos",
    "dec_pos", "pos",
)


def axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return int(np.prod([mesh.shape[a] for a in axes]))


def fsdp_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _maybe(dim: int, axes, mesh: Mesh):
    """axes if they evenly divide dim else None."""
    if axes is None:
        return None
    if dim % axis_size(mesh, axes) == 0:
        return axes
    # try a prefix (e.g. ("pod","data") -> ("pod",))
    if not isinstance(axes, str) and len(axes) > 1:
        for k in range(len(axes) - 1, 0, -1):
            sub = axes[:k]
            if dim % axis_size(mesh, sub) == 0:
                return sub
    return None


def param_spec_for(name: str, shape: Tuple[int, ...], cfg: ModelConfig, mesh: Mesh) -> P:
    F = fsdp_axes(mesh)
    T = "model"
    if any(frag in name for frag in _REPLICATE_NAMES):
        return P()
    if "embed" in name:
        # Lookup table: vocab over TP, d replicated; a vocab-sharded
        # gather lowers to the standard mask+all-reduce pattern.  The
        # untied `out` projection is a plain matmul and stays sharded on
        # both dims.
        v, d = shape
        return P(_maybe(v, T, mesh), None)
    if "'out'" in name or name.endswith("out']") and "w_out" not in name:
        v, d = shape
        return P(_maybe(v, T, mesh), _maybe(d, F, mesh))
    if "router" in name:
        return P(None, _maybe(shape[-2], F, mesh), None)
    # MoE expert stacks: (L, E, a, b)
    if len(shape) == 4 and cfg.moe is not None and "moe" in name:
        L, E, a, b = shape
        ep = _maybe(E, T, mesh)
        if ep is not None:
            return P(None, ep, _maybe(a, F, mesh), None)
        # expert-TP fallback: shard the expert feature dims
        if any(frag in name for frag in _DOWN_NAMES):
            return P(None, None, _maybe(a, T, mesh), _maybe(b, F, mesh))
        return P(None, None, _maybe(a, F, mesh), _maybe(b, T, mesh))
    down = any(frag in name for frag in _DOWN_NAMES)
    if len(shape) == 3:  # stacked layers: (L, a, b)
        _, a, b = shape
        if down:
            return P(None, _maybe(a, T, mesh), _maybe(b, F, mesh))
        return P(None, _maybe(a, F, mesh), _maybe(b, T, mesh))
    if len(shape) == 2:  # per-layer dict weights (xlstm/griffin lists)
        a, b = shape
        if "conv_w" in name:
            return P(None, _maybe(b, T, mesh))
        if down:
            return P(_maybe(a, T, mesh), _maybe(b, F, mesh))
        return P(_maybe(a, F, mesh), _maybe(b, T, mesh))
    if len(shape) == 1:
        return P()
    return P()


def param_specs(model, mesh: Mesh) -> Any:
    struct = model.param_struct()
    named, treedef = flatten_with_names(struct)
    specs = [
        param_spec_for(name, tuple(leaf.shape), model.cfg, mesh) for name, leaf in named
    ]
    return jax.tree_util.tree_unflatten(treedef, specs)


# ---------------------------------------------------------------------------
# batch / cache / optimizer specs
# ---------------------------------------------------------------------------


def batch_specs(model, mesh: Mesh) -> Any:
    F = fsdp_axes(mesh)

    def spec(name: str, leaf) -> P:
        rank = len(leaf.shape)
        dp = _maybe(leaf.shape[0], F, mesh)
        return P(dp, *([None] * (rank - 1)))

    struct = model.batch_struct(8 * axis_size(mesh, fsdp_axes(mesh)), 128)
    named, treedef = flatten_with_names(struct)
    return jax.tree_util.tree_unflatten(
        treedef, [spec(n, l) for n, l in named]
    )


def cache_spec_for(name: str, shape: Tuple[int, ...], cfg: ModelConfig, mesh: Mesh) -> P:
    F = fsdp_axes(mesh)
    T = "model"
    if name.endswith("pos']") or "slot_pos" in name or shape == ():
        return P()
    # transformer / whisper stacked caches: (L, B, S, H_kv, hd)
    if len(shape) == 5:
        _, b, _, h_kv, hd = shape
        return P(
            None, _maybe(b, F, mesh), None, _maybe(h_kv, T, mesh) ,
            None if _maybe(h_kv, T, mesh) else _maybe(hd, T, mesh),
        )
    # xlstm: C (B,H,hd,hd) / conv (B,W,di) / n (B,H,hd) / m (B,H)
    if len(shape) == 4:
        b, h, hd, _ = shape
        return P(_maybe(b, F, mesh), _maybe(h, T, mesh),
                 None if _maybe(h, T, mesh) else _maybe(hd, T, mesh), None)
    if len(shape) == 3:
        b = shape[0]
        return P(_maybe(b, F, mesh), None, _maybe(shape[-1], T, mesh))
    if len(shape) == 2:
        b = shape[0]
        return P(_maybe(b, F, mesh), _maybe(shape[-1], T, mesh))
    if len(shape) == 1:
        return P(None)
    return P()


def cache_specs(model, mesh: Mesh, b: int, s_max: int) -> Any:
    struct = model.cache_struct(b, s_max)
    named, treedef = flatten_with_names(struct)
    specs = []
    for name, leaf in named:
        shape = tuple(getattr(leaf, "shape", ()))
        specs.append(cache_spec_for(name, shape, model.cfg, mesh))
    return jax.tree_util.tree_unflatten(treedef, specs)


def named(tree: Any, mesh: Mesh, specs: Any) -> Any:
    """Attach NamedShardings (for device_put of real arrays)."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P),
    )
