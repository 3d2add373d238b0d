"""Model FLOPs of a training step, counted from the configuration's shapes.

The PaLM convention (Chowdhery et al., 2022, appendix B): per trained
token, 6 FLOPs for every weight of a matrix multiplication (forward 2,
backward 4) plus 12 * layers * heads * head_dim * sequence for the
attention scores and their weighted sum, full square as computed.  The
embedding lookup, norms, biases and the optimizer are not counted, and
neither is the recomputation of rematerialised layers.
"""
from __future__ import annotations

from typing import Any, Dict


def matmul_weights(cfg: Dict[str, Any]) -> int:
    d = cfg["hidden_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    per_layer = d * h * hd * 2 + d * kv * hd * 2 + 3 * d * cfg["intermediate_size"]
    head = cfg["vocab_size"] * d
    return cfg["num_hidden_layers"] * per_layer + head


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // h
    attn = 12 * cfg["num_hidden_layers"] * h * hd * seq_len
    return float(6 * matmul_weights(cfg) + attn)
