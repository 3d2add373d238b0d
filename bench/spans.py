"""The program's own spans (``repro.core.spans``) of a traced run, one
tree per save or restore of the record.

The program records spans only while a profiler session captures, so in
a run of one cell the ring holds the traced window's spans alone.  A
tree is a root span (``ckpt.save``, ``ckpt.restore``) with every span
below it, on any thread.  A reading is ``None``, never a number, when
the program records no spans, when its ring dropped records, or when
the roots are not one per entry of the record's ``saves`` or
``restores``.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Any, Dict, List, Optional


def _program():
    try:
        from repro.core import spans
    except ImportError:  # a program without the recorder
        return None
    return spans


def trees(rec: Dict[str, Any], root: str, key: str,
          records: Optional[List[Any]] = None,
          dropped: int = 0) -> Optional[List[List[Any]]]:
    """Each root span named ``root`` with its descendants, in order; the
    program's ring unless ``records`` (and its ``dropped``) are given."""
    if records is None:
        program = _program()
        if program is None:
            return None
        records, dropped = program.spans(), program.dropped()
    if dropped > 0:
        return None
    roots = [r for r in records if r.name == root and r.parent is None]
    if not roots or len(roots) != len(rec.get(key) or []):
        return None
    kids = defaultdict(list)
    for r in records:
        kids[r.parent].append(r)
    out = []
    for r in roots:
        tree, todo = [], [r]
        while todo:
            s = todo.pop()
            tree.append(s)
            todo.extend(kids[s.id])
        out.append(tree)
    return out


def seconds(r: Any) -> float:
    return (r.t1_ns - r.t0_ns) / 1e9


def per_tree(forest: Optional[List[List[Any]]], name: str,
             attr: Optional[str] = None) -> Optional[List[float]]:
    """For each tree that holds spans called ``name``: their seconds (or
    their attribute ``attr``) summed."""
    if forest is None:
        return None
    out = []
    for tree in forest:
        hits = [s for s in tree if s.name == name]
        if hits:
            out.append(sum(float(s.attrs[attr]) if attr else seconds(s) for s in hits))
    return out


def mean(values: Optional[List[float]]) -> Optional[float]:
    return sum(values) / len(values) if values else None
