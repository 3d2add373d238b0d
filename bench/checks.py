"""The numbers that decide ``correct``, each held against its limit.

Training numbers compare the program's first steps with the plain
reference over the same weights and batches:

* ``loss_gap``: the largest gap between a step's loss and the
  reference's, in nats.
* ``grad_gap``: over the leaves, the largest gap between the norm of
  the first gradient as the program's optimizer got it and the
  reference's, over the larger of the reference leaf's norm and the
  median leaf's.
* ``change_gap``: the same for the norm of the parameters' change over
  the steps, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's (Adam moves those by round-off alone,
  as it moves a key bias under softmax).

Checkpoint numbers count leaves whose bytes read back differ from the
state that was saved (limit 0: the comparison is exact).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from bench.job import Readings

NEGLIGIBLE_GRAD = 1e-3


def _rel_gap(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray) -> float:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    floor = np.maximum(ref, np.median(ref))
    gap = np.abs(prog - ref) / np.where(floor > 0, floor, 1.0)
    return float(gap[keep].max()) if keep.any() else 0.0


def training_gaps(prog: Readings, ref: Readings) -> Dict[str, float]:
    if len(prog.grad_norms) != len(ref.grad_norms):
        raise ValueError("program and reference have different leaf counts")
    moved = ref.grad_norms >= NEGLIGIBLE_GRAD * np.median(ref.grad_norms)
    return {
        "loss_gap": float(np.max(np.abs(np.asarray(prog.losses, np.float64)
                                        - np.asarray(ref.losses, np.float64)))),
        "grad_gap": _rel_gap(prog.grad_norms, ref.grad_norms,
                             np.ones(len(ref.grad_norms), bool)),
        "change_gap": _rel_gap(prog.change_norms, ref.change_norms, moved),
    }


Check = Tuple[str, float, float]


def verdict(checks: List[Check]) -> bool:
    return all(np.isfinite(v) and v <= lim for _, v, lim in checks)


def format_checks(checks: List[Check]) -> Dict[str, Dict[str, float]]:
    return {name: {"value": value, "limit": limit} for name, value, limit in checks}
