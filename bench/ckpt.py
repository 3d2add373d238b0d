"""The program's checkpoint manager as the cells drive it, and the
read-back that compares a saved step with the state it was saved from."""
from __future__ import annotations

import os
import shutil
from pathlib import Path
from typing import Any, Dict

import jax
import numpy as np

from bench.job import host_digest
from repro.core import CheckpointConfig, CheckpointManager, theta_like


def manager(root: Path, config: Dict[str, Any], **kw: Any) -> CheckpointManager:
    c = config["checkpoint"]
    return CheckpointManager(CheckpointConfig(
        root=str(root), cluster=theta_like(c["nodes"], c["procs_per_node"]),
        strategy=c["strategy"], codec=c["codec"], **kw))


def target(state_shape: Any) -> Dict[str, Any]:
    return {"train": state_shape,
            "data": {"batch_idx": jax.ShapeDtypeStruct((), np.int32)}}


def leaves_differing(tree: Any, digests: np.ndarray) -> int:
    """Leaves of ``tree`` (host arrays) whose digest differs from the
    device digest taken of the state that was saved."""
    leaves = jax.tree_util.tree_leaves(tree)
    if len(leaves) != len(digests):
        return max(len(leaves), len(digests))
    return sum(not np.array_equal(host_digest(np.asarray(a)), d)
               for a, d in zip(leaves, digests))


def read_back(root: Path, config: Dict[str, Any], step: int, state_shape: Any,
              level: str) -> Any:
    """Restore ``step`` from one level alone, the other set aside, in a
    fresh manager.  Returns the restored tree, or None if it fails."""
    other = root / ("local" if level == "pfs" else "pfs")
    aside = root / f"{other.name}.aside"
    other.rename(aside)
    try:
        mgr = manager(root, config, async_flush=False)
        try:
            return mgr.restore(target(state_shape), step=step)[1]
        except (OSError, ValueError, KeyError):
            return None
        finally:
            mgr.close()
    finally:
        shutil.rmtree(other, ignore_errors=True)
        aside.rename(other)


def evict_from_page_cache(directory: Path) -> None:
    """Write back and drop every file's cached pages, so the next read
    comes from the disk."""
    for path in directory.rglob("*"):
        if path.is_file():
            fd = os.open(path, os.O_RDONLY)
            try:
                os.fsync(fd)
                os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
            finally:
                os.close(fd)


def tree_bytes(tree: Any) -> int:
    return sum(int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
               for x in jax.tree_util.tree_leaves(tree))
