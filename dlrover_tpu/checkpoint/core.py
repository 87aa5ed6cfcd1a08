"""Shard-pack format: sharded pytrees ⇄ one contiguous buffer per host.

The unit of checkpoint IO. Each host packs the *replica-0 addressable
shards* of every array in the state pytree into a single buffer:

    [u64 header_len][header JSON][shard payload | shard payload | ...]

The header records, per leaf: its pytree path, dtype, global shape, and the
global index (slice per dim) + offset of every shard in the payload. Because
indices are global, restore can assemble ANY target sharding from the union
of packs — the resharding path the reference implements by hand for each
framework (fsdp_save_util.py, megatron_dist_ckpt.py) falls out of the
format here.

Same bytes live in shared memory (staging) and on disk (persisted), so the
agent's async persist is a raw copy.
"""

import dataclasses
import json
import math
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from dlrover_tpu.observability.tracing import get_tracer

HEADER_LEN_BYTES = 8
ALIGN = 128

# module-level so the compiled copy is cached across leaves that share a
# shape/sharding (a fresh jax.jit per leaf would recompile every time)
_owned_copy = jax.jit(jax.numpy.copy)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _slice_to_json(s: slice, dim: int) -> List[int]:
    start = 0 if s.start is None else int(s.start)
    stop = dim if s.stop is None else int(s.stop)
    return [start, stop]


@dataclasses.dataclass
class ShardEntry:
    index: List[List[int]]  # [[start, stop], ...] per dim (global coords)
    offset: int
    nbytes: int


@dataclasses.dataclass
class LeafEntry:
    path: str
    dtype: str
    global_shape: List[int]
    shards: List[ShardEntry]


def plan_pack(state: Any) -> Tuple[List[LeafEntry], int]:
    """Compute the header + total payload size for a pytree of jax arrays."""
    leaves_with_path = jax.tree_util.tree_flatten_with_path(state)[0]
    entries: List[LeafEntry] = []
    offset = 0
    for path, leaf in leaves_with_path:
        arr = leaf
        dtype = np.dtype(arr.dtype)
        gshape = list(arr.shape)
        shards: List[ShardEntry] = []
        for shard in _replica0_shards(arr):
            idx = [
                _slice_to_json(s, d)
                for s, d in zip(shard.index, gshape)
            ] if gshape else []
            nbytes = int(
                dtype.itemsize
                * (math.prod(b - a for a, b in idx) if idx else 1)
            )
            offset = (offset + ALIGN - 1) // ALIGN * ALIGN
            shards.append(ShardEntry(index=idx, offset=offset, nbytes=nbytes))
            offset += nbytes
        entries.append(
            LeafEntry(
                path=_path_str(path),
                dtype=dtype.name,
                global_shape=gshape,
                shards=shards,
            )
        )
    return entries, offset


def _replica0_shards(arr):
    if hasattr(arr, "addressable_shards"):
        return [s for s in arr.addressable_shards if s.replica_id == 0]

    class _Whole:
        index = ()
        data = arr

    w = _Whole()
    w.index = tuple(slice(0, d) for d in np.shape(arr))
    w.data = np.asarray(arr)
    return [w]


def header_bytes(step: int, entries: List[LeafEntry], extra: Dict = None) -> bytes:
    doc = {
        "version": 1,
        "step": step,
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "extra": extra or {},
        "leaves": [
            {
                "path": e.path,
                "dtype": e.dtype,
                "global_shape": e.global_shape,
                "shards": [dataclasses.asdict(s) for s in e.shards],
            }
            for e in entries
        ],
    }
    return json.dumps(doc).encode("utf-8")


def pack_size(header: bytes, payload_size: int) -> int:
    base = HEADER_LEN_BYTES + len(header)
    base = (base + ALIGN - 1) // ALIGN * ALIGN
    return base + payload_size


def payload_start(header: bytes) -> int:
    base = HEADER_LEN_BYTES + len(header)
    return (base + ALIGN - 1) // ALIGN * ALIGN


def write_pack(
    buf: memoryview,
    step: int,
    state: Any,
    entries: List[LeafEntry],
    extra: Dict = None,
    header: Optional[bytes] = None,
    phases: Optional[Dict[str, float]] = None,
) -> int:
    """Write header + all shard payloads into ``buf``; returns bytes used.

    Device→host copies are started async for every shard first, then
    consumed — overlapping DMA with serialization. Pass the ``header``
    already computed for sizing to avoid re-serializing the (potentially
    large) leaf manifest under the checkpoint lock.

    The two things this does are timed apart, summed over the leaves:
    ``shm_copy`` (copying a shard that is on the host into ``buf``) and
    ``d2h_wait`` (all the rest: starting the copies and waiting for each
    shard to arrive). Each becomes one span, ``ckpt.d2h_wait`` then
    ``ckpt.shm_copy`` laid end to end from the start, and their seconds
    are added to ``phases``.
    """
    clock = time.monotonic
    t_start = clock()
    if header is None:
        header = header_bytes(step, entries, extra)
    n = len(header)
    buf[:HEADER_LEN_BYTES] = n.to_bytes(HEADER_LEN_BYTES, "little")
    buf[HEADER_LEN_BYTES : HEADER_LEN_BYTES + n] = header
    start = payload_start(header)

    leaves = [leaf for _, leaf in jax.tree_util.tree_flatten_with_path(state)[0]]
    # kick off async D2H for everything first
    for leaf in leaves:
        if hasattr(leaf, "copy_to_host_async"):
            leaf.copy_to_host_async()
    used = start
    copy_s = 0.0
    for leaf, entry in zip(leaves, entries):
        shards = _replica0_shards(leaf)
        for shard, sentry in zip(shards, entry.shards):
            data = np.asarray(shard.data)  # waits for the shard
            t0 = clock()
            raw = np.ascontiguousarray(data).view(np.uint8).reshape(-1)
            lo = start + sentry.offset
            hi = lo + sentry.nbytes
            # direct buffer-protocol assignment: .tobytes() would copy
            # through an intermediate bytes object (measured ~9x slower
            # for large shards — this is the staging hot loop)
            buf[lo:hi] = raw
            copy_s += clock() - t0
            used = max(used, hi)
    wait_s = clock() - t_start - copy_s
    tracer = get_tracer()
    nbytes = used - start
    tracer.complete_span(
        "ckpt.d2h_wait", t_start, dur_s=wait_s, step=step, nbytes=nbytes,
        leaves=len(leaves),
    )
    tracer.complete_span(
        "ckpt.shm_copy", t_start + wait_s, dur_s=copy_s, step=step,
        nbytes=nbytes,
    )
    if phases is not None:
        phases["d2h_wait"] = phases.get("d2h_wait", 0.0) + wait_s
        phases["shm_copy"] = phases.get("shm_copy", 0.0) + copy_s
    return used


def read_header(buf: memoryview) -> Dict:
    n = int.from_bytes(buf[:HEADER_LEN_BYTES], "little")
    return json.loads(bytes(buf[HEADER_LEN_BYTES : HEADER_LEN_BYTES + n]))


class PackIndex:
    """Random access over one or more packs (shm buffers or mmapped files)."""

    def __init__(self):
        # path -> list of (index, np_view)
        self._shards: Dict[str, List[Tuple[Tuple[slice, ...], np.ndarray]]] = {}
        self._meta: Dict[str, Tuple[str, Tuple[int, ...]]] = {}
        self.step: Optional[int] = None
        self.process_count: int = 0

    def add_pack(self, buf: memoryview):
        n = int.from_bytes(buf[:HEADER_LEN_BYTES], "little")
        doc = json.loads(bytes(buf[HEADER_LEN_BYTES : HEADER_LEN_BYTES + n]))
        if self.step is None:
            self.step = doc["step"]
            self.process_count = doc.get("process_count", 1)
        base = HEADER_LEN_BYTES + n
        start = (base + ALIGN - 1) // ALIGN * ALIGN
        for leaf in doc["leaves"]:
            path = leaf["path"]
            dtype = np.dtype(leaf["dtype"])
            gshape = tuple(leaf["global_shape"])
            self._meta[path] = (leaf["dtype"], gshape)
            for s in leaf["shards"]:
                idx = tuple(slice(a, b) for a, b in s["index"])
                shape = tuple(b - a for a, b in s["index"])
                lo = start + s["offset"]
                view = np.frombuffer(
                    buf, dtype=dtype, count=max(1, math.prod(shape)) if shape else 1,
                    offset=lo,
                ).reshape(shape)
                self._shards.setdefault(path, []).append((idx, view))

    def close(self):
        """Drop all buffer views so the backing shm/mmap can close
        cleanly (numpy views pin the mapping; without this, SharedMemory
        teardown raises 'cannot close exported pointers exist')."""
        self._shards.clear()
        self._meta.clear()

    def paths(self) -> List[str]:
        return list(self._meta.keys())

    def global_shape(self, path: str) -> Tuple[int, ...]:
        return self._meta[path][1]

    def dtype(self, path: str) -> np.dtype:
        return np.dtype(self._meta[path][0])

    def read_slice(self, path: str, index: Tuple[slice, ...]) -> np.ndarray:
        """Assemble an arbitrary global slice from stored shards."""
        dtype, gshape = np.dtype(self._meta[path][0]), self._meta[path][1]
        want = tuple(
            slice(
                0 if s.start is None else s.start,
                dim if s.stop is None else s.stop,
            )
            for s, dim in zip(index, gshape)
        ) if gshape else ()
        if not gshape:
            shards = self._shards.get(path, [])
            if not shards:
                raise KeyError(f"no shards for {path}")
            # COPY, not a view: jax's CPU backend zero-copy aliases numpy
            # arrays, and a view would pin the backing shm mapping open
            return np.array(shards[0][1], copy=True).reshape(())
        shape = tuple(s.stop - s.start for s in want)
        out = np.empty(shape, dtype)
        filled = np.zeros(shape, bool) if not _covers(want, self._shards.get(path, [])) else None
        for idx, view in self._shards.get(path, []):
            inter = []
            ok = True
            for w, h in zip(want, idx):
                lo = max(w.start, h.start)
                hi = min(w.stop, h.stop)
                if lo >= hi:
                    ok = False
                    break
                inter.append((lo, hi))
            if not ok:
                continue
            dst = tuple(
                slice(lo - w.start, hi - w.start)
                for (lo, hi), w in zip(inter, want)
            )
            src = tuple(
                slice(lo - h.start, hi - h.start)
                for (lo, hi), h in zip(inter, idx)
            )
            out[dst] = view[src]
            if filled is not None:
                filled[dst] = True
        if filled is not None and not filled.all():
            raise KeyError(
                f"pack set does not cover requested slice of {path}"
            )
        return out


def _covers(want, shards) -> bool:
    # fast path: a single shard covering the whole request
    for idx, _ in shards:
        if all(
            h.start <= w.start and h.stop >= w.stop
            for w, h in zip(want, idx)
        ):
            return True
    return False


class RestoreMismatchError(Exception):
    """The checkpoint's leaf set does not satisfy the restore contract
    (missing leaves without ``partial``, missing PARAM leaves, or an
    abstract target that cannot supply fresh values). Deliberately NOT
    a KeyError: the engine's load fallbacks swallow KeyError as
    "no checkpoint here" — a contract violation must propagate loudly
    instead of silently restarting training from scratch."""


def restore_tree(
    target: Any,
    pack_index: PackIndex,
    shardings: Any = None,
    partial: bool = False,
    phases: Optional[Dict[str, float]] = None,
) -> Any:
    """Build a pytree of (sharded) jax arrays matching ``target``'s structure.

    ``target`` is a pytree of ShapeDtypeStruct/arrays providing structure;
    ``shardings`` an optional matching pytree of NamedSharding for the NEW
    mesh — this is the resharded-restore path after an elastic re-election.

    ``partial=True``: leaves MISSING from the pack keep the target's
    value — the forward-compatibility path for state trees that grew
    since the checkpoint (new fp8 amax slots, new optimizer state).
    The target must then carry CONCRETE arrays (the freshly initialized
    live state, not a ShapeDtypeStruct template) so there is a value to
    keep; an abstract target with a missing leaf still raises.

    Returns once the arrays are on the device. Three phases, each summed
    over the leaves, are child spans of ``ckpt.restore_tree`` and are
    added to ``phases``: ``read`` (assembling each slice from the pack
    on the host), ``h2d`` (handing it to the device) and ``device_wait``
    (the wait, at the end, for the copies to land).
    """
    leaves_with_path, treedef = jax.tree_util.tree_flatten_with_path(target)
    shard_leaves = (
        jax.tree_util.tree_flatten(shardings)[0]
        if shardings is not None
        else [None] * len(leaves_with_path)
    )
    restore_span = get_tracer().span(
        "ckpt.restore_tree",
        step=pack_index.step if pack_index.step is not None else -1,
        leaves=len(leaves_with_path),
        resharded=shardings is not None,
    )
    try:
        return _restore_leaves(
            leaves_with_path, treedef, shard_leaves, pack_index, partial,
            phases, restore_span,
        )
    except BaseException:
        # a mismatch records nothing: only completed restores land on
        # the timeline
        restore_span.cancel()
        raise


def _restore_leaves(
    leaves_with_path, treedef, shard_leaves, pack_index, partial, phases,
    restore_span,
):
    out = []
    kept = []
    clock = time.monotonic
    t_start = clock()
    read_s = 0.0

    def read(path, index, dtype):
        nonlocal read_s
        t0 = clock()
        got = pack_index.read_slice(path, index).astype(dtype, copy=False)
        read_s += clock() - t0
        return got

    for (path, leaf), sharding in zip(leaves_with_path, shard_leaves):
        pstr = _path_str(path)
        if pstr not in pack_index._meta:
            if not partial:
                raise RestoreMismatchError(
                    f"checkpoint has no leaf {pstr} (state tree grew "
                    "since the save?); pass partial=True with the live "
                    "state to keep fresh values for new leaves"
                )
            if pstr.startswith("params"):
                # a missing PARAM is never an upgrade — it is a rename
                # or corruption, and silently resuming with random
                # weights in one subtree is the worst failure mode
                raise RestoreMismatchError(
                    f"partial restore: param leaf {pstr} is missing "
                    "from the checkpoint — refusing to substitute "
                    "fresh weights"
                )
            if not isinstance(leaf, (np.ndarray, jax.Array)):
                raise RestoreMismatchError(
                    f"partial restore: {pstr} is missing from the "
                    "checkpoint and the target leaf is abstract — pass "
                    "the live initialized state as target"
                )
            kept.append(pstr)
            out.append(
                leaf
                if sharding is None
                else jax.device_put(leaf, sharding)
            )
            continue
        gshape = pack_index.global_shape(pstr)
        # restore into the TARGET's dtype: a precision change between
        # save and restore (bf16 run resumed in f32, or vice versa) must
        # not silently leak the pack dtype into the training state
        dtype = np.dtype(
            getattr(leaf, "dtype", None) or pack_index.dtype(pstr)
        )
        # Both branches must hand back jax-OWNED buffers, never a
        # zero-copy alias of the assembled numpy arrays: jax's CPU
        # backend aliases any 64-byte-aligned numpy buffer, and the
        # train step DONATES the restored state — XLA then releases
        # memory that numpy's allocator owns, which corrupts the glibc
        # heap a step or two after an in-place resume. Alignment of
        # np.empty is luck-of-the-malloc, so the crash is flaky.
        if sharding is None:
            # astype copy=False (in read): a no-op when the pack already
            # matches the target dtype; jnp.array makes the owned copy
            full = read(pstr, tuple(slice(0, d) for d in gshape), dtype)
            out.append(jax.numpy.array(full))
        else:
            arr = jax.make_array_from_callback(
                gshape,
                sharding,
                lambda idx, p=pstr, dt=dtype: read(p, idx, dt),
            )
            # device-to-device copy off the aliased callback shards;
            # jit keeps the sharding and works on multi-host globals
            out.append(_owned_copy(arr))
    if kept:
        from dlrover_tpu.common.log import get_logger

        get_logger(__name__).warning(
            "partial restore: %d leaves not in the checkpoint kept "
            "their fresh values (first: %s) — expected after a "
            "state-tree upgrade (e.g. new fp8 slots), NOT for params",
            len(kept),
            kept[0],
        )
    t_issued = clock()
    out = jax.block_until_ready(out)
    h2d_s = t_issued - t_start - read_s
    wait_s = clock() - t_issued
    tracer = get_tracer()
    tracer.complete_span("ckpt.restore_read", t_start, dur_s=read_s)
    tracer.complete_span("ckpt.restore_h2d", t_start + read_s, dur_s=h2d_s)
    tracer.complete_span("ckpt.restore_device_wait", t_issued, dur_s=wait_s)
    if phases is not None:
        for name, seconds in (
            ("read", read_s), ("h2d", h2d_s), ("device_wait", wait_s),
        ):
            phases[name] = phases.get(name, 0.0) + seconds
    restore_span.end(kept=len(kept))
    return jax.tree_util.tree_unflatten(treedef, out)
