"""Plain reference of a decoder-only language model's training step.

Straight ``jax.numpy`` in float32 at ``HIGHEST`` matmul precision, with
no kernels, no sharding and no batching tricks.  It follows the
published Qwen2 / Mistral decoder: RMSNorm before attention and MLP,
rotary embeddings on half-split heads, grouped-query causal attention
with optional q/k/v biases, a SwiGLU MLP, a final RMSNorm, an untied
output head, and next-token cross-entropy averaged over every predicted
position.  The optimizer is AdamW with global-norm clipping, bias
correction, decoupled weight decay on every leaf, and a linear warm-up
into a cosine decay.

Departures, both of layout only: a norm's scale is stored as its offset
from 1 (the published ``weight`` is ``1 + scale``), and the layers'
weights are stacked along a leading layer axis, as the program under
test holds them.  Nothing here imports the program.

``matmul_dtype`` rounds every matmul operand to a lower precision first;
the benchmark's control uses it (float8) to show that its comparison
fails a step computed below the configuration's precision.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {
        "d": d, "h": h, "kv": cfg["num_key_value_heads"],
        "hd": cfg.get("head_dim") or d // h, "ff": cfg["intermediate_size"],
        "layers": cfg["num_hidden_layers"], "vocab": cfg["vocab_size"],
        "eps": cfg["rms_norm_eps"], "theta": cfg["rope_theta"],
        "bias": cfg.get("qkv_bias", False),
    }


def init_params(key: jax.Array, cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Random weights from ``key`` in the program's parameter layout and
    the configuration's parameter dtype."""
    m = dims(cfg)
    d, hd, L = m["d"], m["hd"], m["layers"]
    qd, kvd = m["h"] * hd, m["kv"] * hd
    dt = jnp.dtype(cfg["param_dtype"])
    shapes = {
        "wq": (L, d, qd), "wk": (L, d, kvd), "wv": (L, d, kvd), "wo": (L, qd, d),
        "w_gate": (L, d, m["ff"]), "w_up": (L, d, m["ff"]), "w_down": (L, m["ff"], d),
        "ln1": (L, d), "ln2": (L, d),
    }
    if m["bias"]:
        shapes.update({"bq": (L, qd), "bk": (L, kvd), "bv": (L, kvd)})
    keys = jax.random.split(key, len(shapes) + 4)

    def draw(k, shape, std):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dt)

    layers = {}
    for k, (name, shape) in zip(keys, sorted(shapes.items())):
        std = 0.02 if len(shape) == 2 else 1.0 / np.sqrt(shape[1])
        layers[name] = draw(k, shape, std)
    params = {
        "embed": draw(keys[-1], (m["vocab"], d), 0.02),
        "final_norm": draw(keys[-2], (d,), 0.02),
        "layers": layers,
    }
    if not cfg.get("tie_word_embeddings", False):
        params["out"] = draw(keys[-3], (m["vocab"], d), 0.02)
    return params


def _round(x, dtype):
    # forward operands rounded to ``dtype``; gradients pass at float32
    return x + jax.lax.stop_gradient(x.astype(dtype).astype(jnp.float32) - x)


def _mm(spec: str, a, b, matmul_dtype):
    if matmul_dtype is not None:
        a, b = _round(a, matmul_dtype), _round(b, matmul_dtype)
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + scale)


def _rope(x, theta):
    s, hd = x.shape[-3], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def row_loss(params, tokens, cfg, matmul_dtype=None):
    """Mean next-token cross-entropy of one sequence ``tokens`` (S,)."""
    m = dims(cfg)
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    s = tokens.shape[0]
    x = p["embed"][tokens]
    mask = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, lp):
        h = _rms(x, lp["ln1"], m["eps"])
        q = _mm("sd,dq->sq", h, lp["wq"], matmul_dtype)
        k = _mm("sd,dq->sq", h, lp["wk"], matmul_dtype)
        v = _mm("sd,dq->sq", h, lp["wv"], matmul_dtype)
        if m["bias"]:
            q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
        q = _rope(q.reshape(s, m["h"], m["hd"]), m["theta"])
        k = _rope(k.reshape(s, m["kv"], m["hd"]), m["theta"])
        v = v.reshape(s, m["kv"], m["hd"])
        rep = m["h"] // m["kv"]
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        scores = _mm("qhd,khd->hqk", q, k, matmul_dtype) / np.sqrt(m["hd"])
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        o = _mm("hqk,khd->qhd", probs, v, matmul_dtype).reshape(s, -1)
        x = x + _mm("sq,qd->sd", o, lp["wo"], matmul_dtype)
        h = _rms(x, lp["ln2"], m["eps"])
        g = _mm("sd,df->sf", h, lp["w_gate"], matmul_dtype)
        u = _mm("sd,df->sf", h, lp["w_up"], matmul_dtype)
        x = x + _mm("sf,fd->sd", jax.nn.silu(g) * u, lp["w_down"], matmul_dtype)
        return x, None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, p["layers"])
    x = _rms(x, p["final_norm"], m["eps"])
    head = p.get("out", p["embed"])
    logits = _mm("sd,vd->sv", x[:-1], head, matmul_dtype)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tokens[1:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


def loss_and_grads(params, tokens, cfg, matmul_dtype=None):
    """Batch loss and float32 gradients, one sequence at a time: every
    row predicts as many tokens, so the batch mean is the mean of rows."""
    grad_fn = jax.value_and_grad(row_loss)
    zero = jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, jnp.float32), params)

    def body(carry, row):
        loss, grads = carry
        l, g = grad_fn(params, row, cfg, matmul_dtype)
        return (loss + l, jax.tree_util.tree_map(jnp.add, grads, g)), None

    (loss, grads), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32), zero), tokens)
    n = tokens.shape[0]
    return loss / n, jax.tree_util.tree_map(lambda g: g / n, grads)


def lr_at(opt: Dict[str, Any], count) -> jnp.ndarray:
    """Learning rate of the update made when ``count`` updates are done."""
    c = jnp.asarray(count, jnp.float32)
    warm = jnp.minimum(1.0, (c + 1.0) / max(1, opt["warmup_steps"]))
    if opt["schedule"] == "constant":
        return opt["lr"] * warm
    t = jnp.clip((c - opt["warmup_steps"])
                 / max(1, opt["total_steps"] - opt["warmup_steps"]), 0.0, 1.0)
    cos = 0.5 * (1.0 + jnp.cos(jnp.pi * t))
    r = opt["min_lr_ratio"]
    return opt["lr"] * warm * (r + (1 - r) * cos)


def adamw(params, grads, mu, nu, count, opt):
    """One AdamW update; returns (params, mu, nu, clipped grads)."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(grads)))
    scale = jnp.where(gnorm > opt["grad_clip"], opt["grad_clip"] / gnorm, 1.0)
    lr = lr_at(opt, count)
    c = count + 1
    bc1, bc2 = 1.0 - opt["beta1"] ** c, 1.0 - opt["beta2"] ** c
    g = jax.tree_util.tree_map(lambda x: x * scale, grads)
    mu = jax.tree_util.tree_map(lambda m, x: opt["beta1"] * m + (1 - opt["beta1"]) * x, mu, g)
    nu = jax.tree_util.tree_map(lambda v, x: opt["beta2"] * v + (1 - opt["beta2"]) * x * x, nu, g)
    params = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + opt["eps"])
                                  + opt["weight_decay"] * p),
        params, mu, nu)
    return params, mu, nu, g


def leaf_norms(tree) -> jnp.ndarray:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


def train_readings(params0, batches: List[Any], cfg, opt,
                   matmul_dtype: Optional[Any] = None) -> Dict[str, np.ndarray]:
    """Run len(batches) reference steps from ``params0``.  Returns each
    step's loss, the per-leaf norms of the first (clipped) gradient, and
    the per-leaf norms of the parameters' change over all the steps."""
    lg = jax.jit(lambda p, t: loss_and_grads(p, t, cfg, matmul_dtype))
    upd = jax.jit(lambda p, g, m, v, c: adamw(p, g, m, v, c, opt))
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params0)
    start = p
    mu = jax.tree_util.tree_map(jnp.zeros_like, p)
    nu = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses, first_grad = [], None
    for i, tokens in enumerate(batches):
        loss, grads = lg(p, tokens)
        p, mu, nu, g = upd(p, grads, mu, nu, i)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = np.asarray(leaf_norms(g))
    change = np.asarray(leaf_norms(jax.tree_util.tree_map(jnp.subtract, p, start)))
    return {"losses": np.asarray(losses), "grad_norms": first_grad,
            "change_norms": change}
