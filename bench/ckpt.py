"""The program's checkpoint manager as the cells drive it, where its
node-local level (L1) lives, and the read-back that compares a saved
step with the state it was saved from.

A configuration's ``"checkpoint"`` block holds the manager's
``strategy``, ``codec``, ``nodes`` and ``procs_per_node``, and may hold
``"l1"``: ``"disk"`` (the default) keeps L1 at ``<ckpt_root>/local`` on
the disk beside the PFS; ``"ram"`` keeps it in a fresh directory under
``RAM_ROOT`` that ``<ckpt_root>/local`` links to.  The program writes
L1 unsynced (``CheckpointManager.save``'s rank tasks) and fsyncs only
its directories, so L1 survives a process's death and not a node's loss
on either medium; durability is judged at the PFS, which stays on disk.
"""
from __future__ import annotations

import os
import shutil
import tempfile
from pathlib import Path
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from bench.job import host_digest
from repro.core import CheckpointConfig, CheckpointManager, SaveStats, theta_like

RAM_ROOT = Path("/dev/shm")  # a host-RAM file system, for an L1 in RAM
L1_MEDIA = ("disk", "ram")
MANAGER_KEYS = ("strategy", "codec", "nodes", "procs_per_node")


def settings(config: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration's ``"checkpoint"`` block with ``l1`` filled in.
    Raises ``ValueError`` for a key it does not know or an unknown ``l1``."""
    c = dict(config["checkpoint"])
    unknown = sorted(set(c) - set(MANAGER_KEYS) - {"l1"})
    if unknown:
        raise ValueError(f"checkpoint keys {unknown}: only {[*MANAGER_KEYS, 'l1']} "
                         "are known")
    c.setdefault("l1", "disk")
    if c["l1"] not in L1_MEDIA:
        raise ValueError(f"checkpoint l1 {c['l1']!r}: one of {list(L1_MEDIA)}")
    return c


def manager(root: Path, config: Dict[str, Any], **kw: Any) -> CheckpointManager:
    c = settings(config)
    return CheckpointManager(CheckpointConfig(
        root=str(root), cluster=theta_like(c["nodes"], c["procs_per_node"]),
        strategy=c["strategy"], codec=c["codec"], **kw))


def place_l1(root: Path, config: Dict[str, Any]) -> Optional[Path]:
    """Where the configuration keeps L1 in RAM: a fresh directory under
    ``RAM_ROOT`` holding the level as ``local``, linked from
    ``<root>/local``.  Returns that fresh directory, which the caller
    removes; None where L1 stays on disk."""
    if settings(config)["l1"] != "ram":
        return None
    fresh = Path(tempfile.mkdtemp(prefix="bench-l1-", dir=RAM_ROOT))
    (fresh / "local").mkdir()
    (root / "local").symlink_to(fresh / "local", target_is_directory=True)
    return fresh


def drop_l1(root: Path) -> None:
    """Empty L1 wherever it lives (the lost node), keeping its directory
    so that a link to it stays whole.  Raises if any file is left."""
    l1 = (root / "local").resolve()
    if not l1.exists():
        return
    for p in l1.iterdir():
        shutil.rmtree(p) if p.is_dir() else p.unlink()
    left = [p for p in l1.rglob("*") if p.is_file()]
    if left:
        raise RuntimeError(f"L1 at {l1} still holds {len(left)} files after the drop")


def levels_on_disk(config: Dict[str, Any]) -> List[str]:
    """The checkpoint levels that a save writes to the disk."""
    return ["pfs"] + (["l1"] if settings(config)["l1"] == "disk" else [])


def written(config: Dict[str, Any], mgr: CheckpointManager,
            stats: List[SaveStats]) -> Dict[str, Any]:
    """What the saves of a run wrote, from their own stats: L1's stored
    bytes (twice with a partner replica, as the manager counts its L1
    cost) and the PFS flush's bytes, in GB; ``disk_GB`` sums the levels
    on the disk."""
    replicas = 2 if mgr.cfg.partner_replication and mgr.cluster.n_nodes > 1 else 1
    l1 = sum(st.stored_bytes for st in stats) * replicas / 1e9
    pfs = sum(st.flush.bytes_written for st in stats if st.flush is not None) / 1e9
    on_disk = "l1" in levels_on_disk(config)
    return {"l1_GB": l1, "l1_medium": "disk" if on_disk else "ram", "pfs_GB": pfs,
            "disk_GB": pfs + (l1 if on_disk else 0.0)}


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
    """Restore ``step`` from one level alone, the other set aside (where
    it lives, an L1 in RAM too) and left empty, in a fresh manager.
    Returns the restored tree, or None if it fails."""
    other = (root / ("local" if level == "pfs" else "pfs")).resolve()
    aside = other.with_name(f"{other.name}.aside")
    other.rename(aside)
    other.mkdir()
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
