"""Device-side pre-codecs applied to the state *before* serialization.

``int8`` — blockwise int8 quantization via the Pallas kernel
(:mod:`repro.kernels.quantize`): every float leaf is replaced by
``{"q": int8 blocks, "s": f32 scales}`` computed on-device, shrinking
flush volume ~4x (bf16: ~2x) at <1% relative error per block.  Lossy —
intended for high-frequency checkpoint tiers where the paper's concern
(PFS pressure) dominates, with periodic lossless checkpoints alongside.

Transform + inverse are structure-deterministic so saved and restoring
processes independently agree on the manifest leaf table.

Device-resident staging (:class:`DevicePrecodec`): instead of the
per-leaf ``quantize_tree`` tree_map + full-state ``device_get`` +
host-side dirty scan, the whole transformed state is assembled into one
uint32 word stream *on device* (one grouped quantize launch for every
float leaf together), the fused Pallas pass
(:mod:`repro.kernels.fused`) XORs it against the previous staged
snapshot and emits the per-chunk dirty mask + digests, and only the
dirty chunks are copied D2H — asynchronously, overlapped with the
caller's next train step.  ``save()`` then consumes the staged buffers
(see ``engine.CheckpointConfig.device_precodec``); the per-leaf host
path stays as the executable reference spec the staged stream is
asserted byte-identical against.
"""
from __future__ import annotations

import threading
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass
from time import perf_counter
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.serialize import Buffer, LeafEntry
from repro.kernels.fused.ops import (
    CHUNK_ALIGN,
    digests_from_meta,
    fused_precodec,
)
from repro.kernels.quantize import dequantize, quantize
from repro.kernels.quantize.ops import TILE, quantize_blocks_needed
from repro.utils.treelib import flatten_with_names

_FLOATS = {jnp.dtype(d) for d in (jnp.float32, jnp.float64, jnp.bfloat16, jnp.float16)}
# leaves smaller than one kernel tile stay raw: the (32, 128) tile pad
# would inflate them, and tiny tensors (norm scales, biases) are exactly
# where int8 noise hurts most.
MIN_QUANT_ELEMS = 4096


def _is_float_leaf(x: Any) -> bool:
    try:
        if jnp.dtype(getattr(x, "dtype", None)) not in _FLOATS:
            return False
    except TypeError:
        return False
    size = int(np.prod(np.shape(x))) if np.shape(x) else 1
    return size >= MIN_QUANT_ELEMS


def quantize_tree(state: Any) -> Any:
    def f(leaf):
        if not _is_float_leaf(leaf):
            return leaf
        q, s = quantize(jnp.asarray(leaf))
        return {"q": q, "s": s}

    return jax.tree_util.tree_map(f, state)


def quant_target_like(target: Any) -> Any:
    """The structure ``quantize_tree`` would produce, as ShapeDtypeStructs."""

    def f(leaf):
        if not _is_float_leaf(leaf):
            return leaf
        n = int(np.prod(np.shape(leaf))) if np.shape(leaf) else 1
        blocks = quantize_blocks_needed(n)
        return {
            "q": jax.ShapeDtypeStruct((blocks, 128), jnp.int8),
            "s": jax.ShapeDtypeStruct((blocks,), jnp.float32),
        }

    return jax.tree_util.tree_map(f, target)


def _dequantize_leaf_np(q: Any, s: Any, t: Any) -> np.ndarray:
    """Vectorized host-side dequant of one leaf: ``q * s`` per block.

    Bit-identical to the kernel/oracle result (both are a plain f32
    multiply per element), but a single NumPy expression instead of a
    jit dispatch + device round trip per leaf — the restore path is on
    the host anyway, where the D2H-side kernel buys nothing.
    """
    n = int(np.prod(np.shape(t))) if np.shape(t) else 1
    x = np.asarray(q, np.float32) * np.asarray(s, np.float32)[:, None]
    return (
        x.reshape(-1)[:n]
        .reshape(np.shape(t))
        .astype(np.dtype(getattr(t, "dtype", np.float32)))
    )


def dequantize_tree(qtree: Any, target: Any, *, pool: Any = None) -> Any:
    """Invert ``quantize_tree`` into ``target``'s shapes/dtypes.

    Vectorized per leaf (one blockwise ``q * s`` NumPy expression) and —
    given ``pool`` — parallel across leaves: the block multiplies and
    astype copies release the GIL, so a many-leaf train state
    dequantizes at memory bandwidth instead of crawling through a
    serial per-leaf jit loop.  The seed per-leaf kernel loop survives
    as :func:`dequantize_tree_reference`, the executable spec the
    vectorized path is tested bit-identical against.
    """
    tleaves, tdef = jax.tree_util.tree_flatten(target)
    qleaves = jax.tree_util.tree_leaves(
        qtree, is_leaf=lambda x: isinstance(x, dict) and set(x) == {"q", "s"}
    )
    if len(tleaves) != len(qleaves):
        raise ValueError("quantized tree does not match target structure")

    def one(job):
        t, q = job
        if isinstance(q, dict):
            return _dequantize_leaf_np(q["q"], q["s"], t)
        return q

    jobs = list(zip(tleaves, qleaves))
    if pool is not None and len(jobs) > 1:
        out = list(pool.map(one, jobs))
    else:
        out = [one(j) for j in jobs]
    return jax.tree_util.tree_unflatten(tdef, out)


def dequantize_tree_reference(qtree: Any, target: Any) -> Any:
    """Seed restore path: per-leaf Pallas ``dequantize`` dispatches with
    a reshape/astype copy per leaf.  Kept as the executable spec for
    :func:`dequantize_tree`."""
    tleaves, tdef = jax.tree_util.tree_flatten(target)
    qleaves = jax.tree_util.tree_leaves(
        qtree, is_leaf=lambda x: isinstance(x, dict) and set(x) == {"q", "s"}
    )
    if len(tleaves) != len(qleaves):
        raise ValueError("quantized tree does not match target structure")
    out = []
    for t, q in zip(tleaves, qleaves):
        if isinstance(q, dict):
            n = int(np.prod(np.shape(t))) if np.shape(t) else 1
            x = dequantize(jnp.asarray(q["q"]), jnp.asarray(q["s"]), n=n)
            out.append(np.asarray(x).reshape(np.shape(t)).astype(t.dtype))
        else:
            out.append(q)
    return jax.tree_util.tree_unflatten(tdef, out)


# -- device-resident pre-codec staging --------------------------------------


def _leaf_bytes_device(x: jax.Array) -> jax.Array:
    """Flat little-endian uint8 view of a device array — the on-device
    twin of ``np.asarray(leaf).tobytes()`` (C order)."""
    x = x.reshape(-1)
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.uint8)
    return jax.lax.bitcast_convert_type(x, jnp.uint8).reshape(-1)


def _leaf_words_device(x: jax.Array) -> jax.Array:
    """``_leaf_bytes_device`` viewed as little-endian uint32 words, for
    a 1-, 2- or 4-byte array whose byte size is a multiple of 4.

    Built without a byte view: bitcasting between widths goes through a
    ``(n, k)`` array, and the TPU pads a minor dim ``k < 128`` to a full
    lane tile (32x for bytes), so a multi-GB state would not fit in
    HBM.  Narrow elements are packed from strided 1-D slices instead.
    """
    x = x.reshape(-1)
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.uint8)
    k = x.dtype.itemsize
    if k == 4:
        return jax.lax.bitcast_convert_type(x, jnp.uint32)
    u = jax.lax.bitcast_convert_type(x, jnp.uint16 if k == 2 else jnp.uint8)
    per = 4 // k
    w = u[0::per].astype(jnp.uint32)
    for j in range(1, per):
        w = w | (u[j::per].astype(jnp.uint32) << (8 * k * j))
    return w


@dataclass
class _StreamSpec:
    """Per-(treedef, shapes, precodec) compiled device serializer."""

    fn: Any                    # jit: ordered leaf list -> uint32 word stream
    leaves: List[LeafEntry]    # transformed leaf table (manifest layout)
    total: int                 # serialized byte count


@dataclass
class _StageResult:
    base_step: Optional[int]   # base actually used (None: full snapshot)
    mask: np.ndarray           # (n_chunks,) bool dirty mask
    digests: np.ndarray        # (n_chunks,) uint64 raw-chunk digests
    dirty_idx: np.ndarray      # global indices of dirty chunks
    sub: jax.Array             # (n_dirty, chunk_words // 128, 128) u32, D2H in flight
    stage_s: float


@dataclass
class StagedPrecodec:
    """Handle for one in-flight staged step (returned by ``stage``)."""

    step: int
    base_step: Optional[int]   # the *requested* base (device may still miss)
    spec: _StreamSpec
    future: "Future[_StageResult]"


@dataclass
class StagedBuffers:
    """Host-side staging output, ready for ``encode_state_staged``."""

    stream: memoryview         # reconstructed raw logical stream
    leaves: List[LeafEntry]
    mask: np.ndarray           # (n_chunks,) bool
    deltas: Dict[int, np.ndarray]  # dirty global chunk -> u8 XOR payload
    digests: np.ndarray        # (n_chunks,) uint64
    base_step: Optional[int]
    stage_s: float             # device-side work (worker thread span)
    wait_s: float              # how long consume() blocked on the D2H


class DevicePrecodec:
    """Double-buffered device→host checkpoint staging.

    ``stage(step, state)`` runs on a single background worker: one
    device pass assembles the transformed state into a uint32 word
    stream (grouped quantize launch — one dispatch for *all* float
    leaves, not a per-leaf tree_map), the fused kernel diffs it against
    the device-held words of the previously staged step, and only the
    dirty chunks start an async D2H copy.  The caller's next train step
    runs concurrently; ``consume`` (called from ``save()``) blocks only
    on whatever D2H is still in flight, then reconstructs the raw
    stream host-side as ``base XOR delta`` over the dirty chunks.

    Buffer ownership: the worker owns the device word stream of the
    last staged step (the double buffer — it becomes the next step's
    base and is replaced, never mutated); the host never holds a full
    D2H copy of a delta step, only its dirty chunks plus the previous
    stream already resident in the engine's L0 twin.

    64-bit leaves require jax x64 mode: without it ``jnp.asarray``
    silently narrows and the staged stream would diverge from the host
    reference serializer, so the spec builder rejects them up front.
    """

    def __init__(
        self,
        *,
        chunk_size: int,
        precodec: str = "none",
        interpret: Optional[bool] = None,
    ):
        if chunk_size <= 0 or chunk_size % CHUNK_ALIGN:
            raise ValueError(
                f"device precodec requires chunk_size to be a positive "
                f"multiple of {CHUNK_ALIGN}, got {chunk_size}"
            )
        if precodec not in ("none", "int8"):
            raise ValueError(f"unknown precodec {precodec!r}")
        self.chunk_size = chunk_size
        self.precodec = precodec
        self.interpret = interpret
        self._specs: Dict[Any, _StreamSpec] = {}
        self._lock = threading.Lock()
        self._exec = ThreadPoolExecutor(1, thread_name_prefix="precodec-stage")
        self._base_words: Optional[jax.Array] = None
        self._base_step: Optional[int] = None

    # -- spec construction --------------------------------------------------

    def _spec_for(self, named, treedef) -> _StreamSpec:
        key = (
            treedef,
            tuple((tuple(np.shape(l)), str(np.asarray(l).dtype if not hasattr(l, "dtype") else l.dtype)) for _, l in named),
            self.precodec,
        )
        with self._lock:
            spec = self._specs.get(key)
        if spec is not None:
            return spec
        spec = self._build_spec(named, treedef)
        with self._lock:
            self._specs[key] = spec
        return spec

    def _build_spec(self, named, treedef) -> _StreamSpec:
        x64 = bool(jax.config.jax_enable_x64)
        quant_rows: List[Optional[Tuple[int, int]]] = []
        rows = 0
        for name, leaf in named:
            dt = np.dtype(getattr(leaf, "dtype", None) or np.asarray(leaf).dtype)
            if dt.itemsize == 8 and not x64:
                raise ValueError(
                    f"device_precodec: leaf {name!r} is {dt} but jax x64 "
                    "mode is off — the staged stream would silently narrow; "
                    "cast the leaf or enable jax_enable_x64"
                )
            if self.precodec == "int8" and _is_float_leaf(leaf):
                n = int(np.prod(np.shape(leaf))) if np.shape(leaf) else 1
                r = quantize_blocks_needed(n)
                quant_rows.append((rows, rows + r))
                rows += r
            else:
                quant_rows.append(None)

        def build(leaf_list):
            q = s = None
            qparts = []
            for leaf, qr in zip(leaf_list, quant_rows):
                if qr is not None:
                    flat = jnp.asarray(leaf).reshape(-1).astype(jnp.float32)
                    pad = (-flat.shape[0]) % TILE
                    if pad:
                        flat = jnp.pad(flat, (0, pad))
                    qparts.append(flat)
            if qparts:
                q, s = quantize(jnp.concatenate(qparts), interpret=self.interpret)
            pieces = []
            for leaf, qr in zip(leaf_list, quant_rows):
                if qr is None:
                    pieces.append(jnp.asarray(leaf))
                else:
                    a, b = qr
                    pieces += [q[a:b], s[a:b]]
            # zero-pad to whole chunks here, inside the one assembly
            # pass, so the fused kernel's own pad never copies the stream
            pad = -((total + 3) // 4) % chunk_words
            tail = [jnp.zeros((pad,), jnp.uint32)] if pad else []
            if word_aligned:
                words = [_leaf_words_device(p) for p in pieces] + tail
                return jnp.concatenate(words)
            parts = [_leaf_bytes_device(p) for p in pieces]
            u8 = jnp.concatenate(parts)
            u8 = jnp.pad(u8, (0, (-u8.shape[0]) % 4))
            w = jax.lax.bitcast_convert_type(u8.reshape(-1, 4), jnp.uint32)
            return jnp.concatenate([w] + tail)

        # the transformed leaf table mirrors what the host reference path
        # (quantize_tree -> serialize_tree) would record in the manifest
        tree = jax.tree_util.tree_unflatten(treedef, [l for _, l in named])
        spec_tree = quant_target_like(tree) if self.precodec == "int8" else tree
        tnamed, _ = flatten_with_names(spec_tree)
        leaves: List[LeafEntry] = []
        off = 0
        for name, l in tnamed:
            dt = np.dtype(getattr(l, "dtype", None) or np.asarray(l).dtype)
            shape = tuple(getattr(l, "shape", np.shape(l)))
            size = int(np.prod(shape, dtype=np.int64) if shape else 1) * dt.itemsize
            leaves.append(
                LeafEntry(
                    name=name, dtype=str(dt), shape=shape, offset=off, size=size
                )
            )
            off += size
        chunk_words, total = self.chunk_size // 4, off
        # every leaf starts on a word boundary: assemble words directly
        word_aligned = all(
            l.size % 4 == 0 and np.dtype(l.dtype).itemsize <= 4 for l in leaves
        )
        return _StreamSpec(fn=jax.jit(build), leaves=leaves, total=off)

    # -- staging ------------------------------------------------------------

    def stage(
        self, step: int, state: Any, *, base_step: Optional[int] = None
    ) -> StagedPrecodec:
        """Kick the fused device pass for ``step`` on the worker thread.

        ``base_step`` is the engine's delta-base choice; the device only
        honors it when it still holds that step's words (otherwise the
        stage silently becomes a full snapshot and the returned buffers
        carry ``base_step=None``).  Returns immediately.
        """
        named, treedef = flatten_with_names(state)
        spec = self._spec_for(named, treedef)
        if spec.total == 0:
            raise ValueError("device precodec requires a non-empty state")
        leaf_list = [leaf for _, leaf in named]
        fut = self._exec.submit(self._run_stage, spec, leaf_list, step, base_step)
        return StagedPrecodec(step=step, base_step=base_step, spec=spec, future=fut)

    def _run_stage(
        self,
        spec: _StreamSpec,
        leaf_list: List[Any],
        step: int,
        base_step: Optional[int],
    ) -> _StageResult:
        t0 = perf_counter()
        words = spec.fn(leaf_list)
        use_base = (
            base_step is not None
            and self._base_step == base_step
            and self._base_words is not None
            and self._base_words.shape == words.shape
        )
        basew = self._base_words if use_base else jnp.zeros_like(words)
        delta, meta = fused_precodec(
            words, basew, chunk_words=self.chunk_size // 4,
            interpret=self.interpret,
        )
        meta_np = np.asarray(meta)
        digests = digests_from_meta(meta_np)
        n_chunks = len(digests)
        # no base: the XOR against zeros IS the stream; every chunk ships
        mask = meta_np[:, 0] > 0 if use_base else np.ones(n_chunks, bool)
        dirty_idx = np.flatnonzero(mask)
        sub = (
            delta
            if len(dirty_idx) == n_chunks
            else jnp.take(delta, jnp.asarray(dirty_idx), axis=0)
        )
        sub.copy_to_host_async()
        self._base_words, self._base_step = words, step
        return _StageResult(
            base_step=base_step if use_base else None,
            mask=mask, digests=digests, dirty_idx=dirty_idx, sub=sub,
            stage_s=perf_counter() - t0,
        )

    def consume(
        self, staged: StagedPrecodec, base_stream: Optional[Buffer] = None
    ) -> StagedBuffers:
        """Block on the staged D2H and reconstruct the raw stream.

        For delta stages ``base_stream`` must be the raw stream of the
        base step (the engine's L0 twin keeps it resident); the stream
        is rebuilt as a copy of the base with the dirty chunks XORed in
        place — no full-state D2H ever happens for a delta step.
        """
        t0 = perf_counter()
        res = staged.future.result()
        dirty_np = np.asarray(res.sub).reshape(len(res.dirty_idx), -1)
        wait_s = perf_counter() - t0
        total, cs = staged.spec.total, self.chunk_size
        deltas: Dict[int, np.ndarray] = {}
        if res.base_step is None:
            stream_arr = dirty_np.reshape(-1).view(np.uint8)[:total]
        else:
            if base_stream is None or len(base_stream) != total:
                raise ValueError(
                    "staged delta consume requires the base step's stream"
                )
            stream_arr = np.frombuffer(base_stream, np.uint8).copy()
            for i, gi in enumerate(res.dirty_idx):
                a = int(gi) * cs
                b = min(a + cs, total)
                db = dirty_np[i].view(np.uint8)[: b - a]
                np.bitwise_xor(stream_arr[a:b], db, out=stream_arr[a:b])
                deltas[int(gi)] = db
        return StagedBuffers(
            stream=memoryview(stream_arr).toreadonly(),
            leaves=staged.spec.leaves,
            mask=res.mask,
            deltas=deltas,
            digests=res.digests,
            base_step=res.base_step,
            stage_s=res.stage_s,
            wait_s=wait_s,
        )

    def invalidate_base(self) -> None:
        """Drop the device-held base words (forces the next stage full)."""
        self._base_words = self._base_step = None

    def close(self) -> None:
        self._exec.shutdown(wait=False)
