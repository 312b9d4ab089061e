"""Multi-tier KV offload plane: G2 (host RAM), G3 (disk), and G4 (the
fleet-shared remote store) behind the G1 page pool, coordinated by
:class:`KVOffloadEngine`.

Reference parity: lib/llm/src/block_manager offload (offload.rs:76-80 --
eviction cascades G1 -> G2 -> G3, lookups promote back up) plus the
offload/onboard engines that move blocks between tiers asynchronously.
The TPU build keeps the same cascade but moves data on XLA's terms (see
engine/engine.py): an evicted block's pages are *sliced on device* before
the free-list reclaims them (device program order guarantees the slice
reads pre-reuse contents), the transfer rides ``copy_to_host_async``, and
the blocking materialize + every tier put/get runs on the offload
engine's dedicated thread -- never the event loop, never the engine
executor that drives device ticks.

A block is stored as ``(blob, meta)``: blob is the raw page content of the
block's pages (a blob of ``engine/kv_cache.py``, which owns its format:
this module asks it for named arrays or bytes), meta carries the router-facing
identity (block_hash, parent_sequence_hash, position) so an onboarded
block re-registers and re-publishes exactly as it first did.

Beyond block offload, the engine parks whole preempted sequences here:
swap-based preemption snapshots the victim lane's KV into a request-keyed
swap record and restores it through the chunked scatter path on resume,
instead of burning prefill FLOPs recomputing KV that already existed
(FlowKV, arXiv:2504.03775).  ``DYN_KV_OFFLOAD`` arms the whole plane from
the environment; unset and unconfigured, no engine is built and no
offload thread ever starts.
"""

from __future__ import annotations

import collections
import json
import logging
import os
import struct
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .runtime import thread_sentry

logger = logging.getLogger("dynamo.offload")

# The designated sync-transfer helpers (dynalint DT009): every synchronous
# device<->host materialization in this module must happen inside one of
# these functions -- bare names cover module functions, dotted qualnames
# pin single methods -- so an accidental blocking transfer on a tier hot
# path is a lint error, not a latent stall.  ``pack_kv_blob_frame`` is
# the G4 remote tier's materialize point; ``RemoteTier._put``/``_get``
# are the store round-trips themselves: all three run only on the
# kv-remote thread (thread_sentry asserts the role at runtime).
COPY_HELPERS = (
    "to_host",
    "pack_kv_blob_frame",
    "RemoteTier._put",
    "RemoteTier._get",
)

# Pseudo worker id of the hub-backed G4 store in every per-link table
# (telemetry TransferLog rows, the observatory's LinkModel, the global
# holdings index): store<->worker edges fit and predict like any
# worker<->worker link.
G4_STORE_ID = -4


def to_host(arr: Any) -> np.ndarray:
    """THE designated device->host materialize point for the offload plane.

    Runs only on the offload engine's thread: by the time it is called the
    async DMA (``copy_to_host_async``, started at dispatch) has usually
    landed, so this is a wait, not a transfer -- and if it is a transfer,
    it blocks a thread nobody's tick latency depends on.  A blob of several
    leaves (an int8 pool's) materializes them together."""
    thread_sentry.assert_role("kv-offload", what="offload.to_host")
    from .engine.kv_cache import blob_to_host

    return blob_to_host(arr)


@dataclass
class BlockMeta:
    block_hash: int = 0
    parent_sequence_hash: int = 0
    position: int = 0
    # shard geometry of the pool the blob was exported from ({"axis": i,
    # "parts": n}, parallel.sharding.kv_shard_geometry) -- None for an
    # unsharded pool.  Tier blobs are always full-width (per-shard slices
    # reassemble on export), so this is provenance for restore-site
    # validation, not a layout switch.
    shards: Optional[Dict[str, int]] = None
    # dtype of the pool the blob was sliced from ("int8" = the quantized
    # pair -- its per-row scales travel inside the blob).
    # Restore sites use this to route cross-geometry deliveries through
    # the shared conversion rule; None = pre-ISSUE-13 full-width blob.
    kv_dtype: Optional[str] = None

    def to_dict(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "block_hash": self.block_hash,
            "parent_sequence_hash": self.parent_sequence_hash,
            "position": self.position,
        }
        if self.shards is not None:
            out["shards"] = dict(self.shards)
        if self.kv_dtype is not None:
            out["kv_dtype"] = str(self.kv_dtype)
        return out

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "BlockMeta":
        shards = d.get("shards")
        kv_dtype = d.get("kv_dtype")
        return cls(
            int(d.get("block_hash", 0)),
            int(d.get("parent_sequence_hash", 0)),
            int(d.get("position", 0)),
            dict(shards) if shards else None,
            str(kv_dtype) if kv_dtype else None,
        )


class KVStagingBuffer:
    """Host-RAM landing zone for an incoming chunked KV transfer.

    The decode side of disaggregation (and the prefix-onboard importer)
    assembles wire chunks here before the device scatter; this class owns
    the geometry arithmetic -- the preallocated ndarray, its flat byte
    view, and each chunk's [start, end) byte range -- so sender and
    receiver derive identical bounds from the same metadata.  Layer spans
    map to byte ranges because layers lead the blob's axes, so their slabs
    are contiguous in its C-order bytes."""

    def __init__(self, shape, dtype, bounds) -> None:
        # lazy: kv_cache lives with the (jax-importing) engine package
        from .engine.kv_cache import blob_nbytes

        self.shape = tuple(int(s) for s in shape)
        self.dtype = dtype
        # one flat byte buffer in the blob's wire form
        # (kv_cache.blob_to_bytes); payload / layer_slice unpack views of it
        self.flat = np.empty((blob_nbytes(self.shape, dtype),), np.uint8)
        self.bounds = [(int(s), int(e)) for s, e in bounds]
        if self.bounds and self.bounds[-1][1] != self.flat.size:
            raise ValueError(
                f"chunk bounds end at {self.bounds[-1][1]}, blob holds "
                f"{self.flat.size} bytes"
            )

    @classmethod
    def for_layer_spans(cls, shape, dtype, spans) -> "KVStagingBuffer":
        """One chunk per layer-group span [lo, hi) over the layer axis,
        each span in the wire form of its own blob."""
        from .engine.kv_cache import blob_nbytes, blob_num_layers

        bpl = blob_nbytes(shape, dtype) // max(blob_num_layers(shape), 1)
        return cls(shape, dtype, [(lo * bpl, hi * bpl) for lo, hi in spans])

    @classmethod
    def for_byte_chunks(cls, shape, dtype, chunk_bytes: int) -> "KVStagingBuffer":
        """Fixed-size byte chunks (the block-blob transfer framing)."""
        from .engine.kv_cache import blob_nbytes

        total = blob_nbytes(shape, dtype)
        if total == 0:
            return cls(shape, dtype, [(0, 0)])
        bounds = [
            (off, min(off + chunk_bytes, total))
            for off in range(0, total, chunk_bytes)
        ]
        return cls(shape, dtype, bounds)

    def payload(self):
        """The assembled blob in its engine-facing form, ALIASING the
        staging buffer (zero-copy).  Valid for whole-blob staging
        (``for_byte_chunks``) only -- the layer-span layout is in wire form
        PER SPAN, so those consumers unpack via :meth:`layer_slice`."""
        from .engine.kv_cache import blob_from_bytes

        return blob_from_bytes(self.flat, self.shape, self.dtype)

    @property
    def memoryview(self) -> memoryview:
        return memoryview(self.flat)

    def layer_slice(self, lo: int, hi: int) -> np.ndarray:
        """View of layers [lo, hi) -- stable once their bytes landed.  It
        ALIASES the staging buffer (zero-copy), so it is valid only while
        the buffer's bytes stay untouched."""
        from .engine.kv_cache import (
            blob_from_bytes,
            blob_num_layers,
            blob_shape,
        )

        bpl = self.flat.size // max(blob_num_layers(self.shape), 1)
        return blob_from_bytes(
            self.flat[lo * bpl : hi * bpl],
            blob_shape(self.shape, num_layers=hi - lo),
            self.dtype,
        )


class DiskTier:
    """G3: one ``.npz`` file per block under ``root``, LRU-capped.

    ``put``/``get`` do blocking file I/O and therefore must only be
    called from the :class:`KVOffloadEngine`'s dedicated thread (the same
    single-writer-thread pattern as the hub WAL) -- the event loop and
    the engine's device executor never touch this class directly.  The
    residency index (``__contains__``) is in-RAM and safe from any
    thread."""

    def __init__(self, root: str, capacity_blocks: int) -> None:
        self.root = root
        self.capacity = capacity_blocks
        os.makedirs(root, exist_ok=True)
        self._lru: "collections.OrderedDict[int, None]" = collections.OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def _path(self, seq_hash: int) -> str:
        return os.path.join(self.root, f"{seq_hash & (2**64 - 1):016x}.npz")

    def __len__(self) -> int:
        return len(self._lru)

    def __contains__(self, seq_hash: int) -> bool:
        with self._lock:
            return seq_hash in self._lru

    def put(
        self, seq_hash: int, blob: np.ndarray, meta: BlockMeta
    ) -> List[Tuple[int, Optional[str], int]]:
        """Offload-thread only.  File I/O runs OUTSIDE the lock (write to
        a temp file, rename into place): the lock guards only the in-RAM
        index, so ``__contains__`` probes from the admission path never
        wait behind a multi-MB compressed write.

        Returns the holdings delta this put caused -- ``(hash, "disk",
        nbytes)`` for the stored block (``(hash, None, 0)`` when capacity
        or a write error dropped it) plus ``(victim, None, 0)`` for every
        LRU eviction -- so the publisher never advertises a tier the
        worker already dropped."""
        thread_sentry.assert_role("kv-offload", what="DiskTier.put")
        from .engine.kv_cache import blob_to_arrays

        if self.capacity <= 0:
            return [(seq_hash, None, 0)]
        path = self._path(seq_hash)
        tmp = path + ".tmp.npz"  # .npz suffix so np.savez appends nothing
        try:
            meta_d = {
                k: v for k, v in meta.to_dict().items() if k != "shards"
            }
            np.savez(tmp, **blob_to_arrays(blob), **meta_d)
            os.replace(tmp, path)
        except OSError:
            logger.exception("disk tier write failed for %x", seq_hash)
            with_suppress_remove(tmp)
            return [(seq_hash, None, 0)]
        victims: List[int] = []
        with self._lock:
            self._lru[seq_hash] = None
            self._lru.move_to_end(seq_hash)
            while len(self._lru) > self.capacity:
                victim, _ = self._lru.popitem(last=False)
                victims.append(victim)
        for victim in victims:
            with_suppress_remove(self._path(victim))
        delta: List[Tuple[int, Optional[str], int]] = [
            (seq_hash, "disk", int(blob.nbytes))
        ]
        delta.extend((v, None, 0) for v in victims)
        return delta

    def get(self, seq_hash: int) -> Optional[Tuple[np.ndarray, BlockMeta]]:
        """Offload-thread only (single reader; puts rename atomically, so
        a file listed in the index is always complete).  The lock again
        covers only the index."""
        thread_sentry.assert_role("kv-offload", what="DiskTier.get")
        with self._lock:
            if seq_hash not in self._lru:
                self.misses += 1
                return None
        from .engine.kv_cache import blob_from_arrays

        try:
            with np.load(self._path(seq_hash)) as z:
                blob = blob_from_arrays(z)
                meta = BlockMeta(
                    int(z["block_hash"]),
                    int(z["parent_sequence_hash"]),
                    int(z["position"]),
                    kv_dtype=(
                        str(z["kv_dtype"]) if "kv_dtype" in z.files else None
                    ),
                )
        except OSError:
            with self._lock:
                self._lru.pop(seq_hash, None)
                self.misses += 1
            return None
        with self._lock:
            if seq_hash in self._lru:
                self._lru.move_to_end(seq_hash)
            self.hits += 1
        return blob, meta


def with_suppress_remove(path: str) -> None:
    try:
        os.remove(path)
    except OSError:
        pass


class HostTier:
    """G2: preallocated host-RAM ring of block blobs; overflow demotes to
    the G3 parent.

    The ring is ONE contiguous ndarray of ``capacity_blocks`` slots,
    allocated lazily from the first block's geometry (the pinned-buffer
    analog on a platform without a user pin API: a single stable
    allocation the allocator never fragments or re-touches).  ``put``
    copies into a free slot with ``np.copyto`` -- zero allocations on the
    eviction path -- and ``get`` copies out, so a returned blob stays
    valid after its slot is recycled.  Blocks whose geometry does not
    match the ring (foreign-engine donors) fall back to a per-entry side
    table, counted against the same LRU capacity."""

    def __init__(
        self, capacity_blocks: int, parent: Optional[DiskTier] = None
    ) -> None:
        self.capacity = capacity_blocks
        self.parent = parent
        # LRU order over every resident hash; value = ring slot or None
        # (None = side-table entry)
        self._slots: "collections.OrderedDict[int, Optional[int]]" = (
            collections.OrderedDict()
        )
        self._misc: Dict[int, Tuple[np.ndarray, BlockMeta]] = {}
        self._meta: Dict[int, BlockMeta] = {}
        # one ring an array of the block's stored form
        # (kv_cache.blob_to_arrays): an int8 pool's block is data and row
        # scales, and the pair occupies one LRU slot
        self._ring: Optional[Dict[str, np.ndarray]] = None
        self._ring_failed = False
        self._free_slots: List[int] = []
        # prefetch pins: hash -> refcount.  A pinned block is skipped by
        # LRU demotion, so a chain promoted for a queued request cannot
        # be churned back to disk before its admission consumes it.  Pins
        # come only from bounded prefetch windows and are released at
        # admission or cancel (the leak the ISSUE 10 satellite closes).
        self._pins: Dict[int, int] = {}
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        # holdings sink (KVOffloadEngine._on_holdings): fired -- outside
        # the lock, on the offload thread -- with the per-put residency
        # delta, so every promote/demote/evict reaches the cluster-global
        # prefix index the moment it happens
        self.holdings_cb: Optional[Any] = None

    def __len__(self) -> int:
        return len(self._slots)

    @property
    def ring_nbytes(self) -> int:
        return sum(r.nbytes for r in (self._ring or {}).values())

    def _ensure_ring_locked(self, arrays: Dict[str, np.ndarray]) -> None:
        if self._ring is not None or self._ring_failed or self.capacity <= 0:
            return
        try:
            self._ring = {
                name: np.empty((self.capacity,) + tuple(a.shape), a.dtype)
                for name, a in arrays.items()
            }
        except MemoryError:
            # remember the failure: retrying a multi-GB allocation on
            # every eviction would hammer the allocator on the one thread
            # all offload work queues behind
            logger.exception(
                "host tier ring allocation failed (%d blocks); falling "
                "back to per-entry storage", self.capacity,
            )
            self._ring_failed = True
            return
        self._free_slots = list(range(self.capacity - 1, -1, -1))

    def _ring_fits_locked(self, arrays: Dict[str, np.ndarray]) -> bool:
        ring = self._ring
        return (
            ring is not None
            and ring.keys() == arrays.keys()
            and all(
                tuple(a.shape) == ring[name].shape[1:]
                and a.dtype == ring[name].dtype
                for name, a in arrays.items()
            )
        )

    def _ring_read_locked(self, slot: int):
        from .engine.kv_cache import blob_from_arrays

        return blob_from_arrays(
            {name: r[slot].copy() for name, r in self._ring.items()}
        )

    def put(self, seq_hash: int, blob: np.ndarray, meta: BlockMeta) -> None:
        delta: List[Tuple[int, Optional[str], int]] = []
        if self.capacity <= 0:
            if self.parent is not None:
                delta = self.parent.put(seq_hash, blob, meta)
            else:
                delta = [(seq_hash, None, 0)]
            self._emit_holdings(delta)
            return
        from .engine.kv_cache import blob_to_arrays

        arrays = blob_to_arrays(blob)
        demote: List[Tuple[int, np.ndarray, BlockMeta]] = []
        with self._lock:
            self._evict_locked(seq_hash)  # overwrite: recycle the old slot
            self._ensure_ring_locked(arrays)
            slot: Optional[int] = None
            if self._ring_fits_locked(arrays):
                if not self._free_slots:
                    self._demote_lru_locked(demote)
                if self._free_slots:
                    slot = self._free_slots.pop()
                    for name, a in arrays.items():
                        np.copyto(self._ring[name][slot], a)
            if slot is None:
                # geometry mismatch (or ring unavailable): side table
                self._misc[seq_hash] = (blob.copy(), meta)
            self._slots[seq_hash] = slot
            self._slots.move_to_end(seq_hash)
            self._meta[seq_hash] = meta
            while len(self._slots) > self.capacity:
                if not self._demote_lru_locked(demote):
                    break  # everything resident is pinned; overshoot
        delta.append((seq_hash, "host", int(blob.nbytes)))
        for victim, vb, vm in demote:
            if self.parent is not None:
                delta.extend(self.parent.put(victim, vb, vm))
            else:
                delta.append((victim, None, 0))
        self._emit_holdings(delta)

    def _emit_holdings(
        self, delta: List[Tuple[int, Optional[str], int]]
    ) -> None:
        """Forward a residency delta to the holdings sink.  Disk-LRU
        victims that are still RAM-resident (a promote leaves the disk
        copy behind; the disk ring may later churn it out) are filtered
        -- the worker still holds them, just in a warmer tier."""
        cb = self.holdings_cb
        if cb is None or not delta:
            return
        out = []
        for h, tier, nbytes in delta:
            if tier is None:
                with self._lock:
                    if h in self._slots:
                        continue
            out.append((h, tier, nbytes))
        if out:
            try:
                cb(out)
            except Exception:
                logger.debug("holdings callback failed", exc_info=True)

    def _demote_lru_locked(
        self, demote: List[Tuple[int, np.ndarray, BlockMeta]]
    ) -> bool:
        """Demote the least-recent UNPINNED resident; returns False when
        every resident is pinned (caller stops demoting -- the ring may
        transiently exceed capacity rather than evict a block a queued
        request is about to consume)."""
        victim = next(
            (h for h in self._slots if not self._pins.get(h)), None
        )
        if victim is None:
            return False
        slot = self._slots.pop(victim)
        meta = self._meta.pop(victim)
        if slot is None:
            vb, meta = self._misc.pop(victim)
        else:
            vb = self._ring_read_locked(slot)
            self._free_slots.append(slot)
        demote.append((victim, vb, meta))
        return True

    def pin(self, seq_hash: int) -> bool:
        """Pin a RAM-resident block against demotion (prefetch holds);
        returns False when the hash is not resident."""
        with self._lock:
            if seq_hash not in self._slots:
                return False
            self._pins[seq_hash] = self._pins.get(seq_hash, 0) + 1
            return True

    def unpin(self, seq_hash: int) -> None:
        with self._lock:
            n = self._pins.get(seq_hash, 0) - 1
            if n > 0:
                self._pins[seq_hash] = n
            else:
                self._pins.pop(seq_hash, None)

    @property
    def pinned_blocks(self) -> int:
        with self._lock:
            return len(self._pins)

    @property
    def block_nbytes(self) -> int:
        """Bytes of one resident block blob (0 until the first put)."""
        if self._ring is not None:
            return sum(int(r[0].nbytes) for r in self._ring.values())
        with self._lock:
            for blob, _meta in self._misc.values():
                return int(blob.nbytes)
        return 0

    def _evict_locked(self, seq_hash: int) -> None:
        slot = self._slots.pop(seq_hash, "absent")
        if slot == "absent":
            return
        self._meta.pop(seq_hash, None)
        if slot is None:
            self._misc.pop(seq_hash, None)
        else:
            self._free_slots.append(slot)

    def get_ram(self, seq_hash: int) -> Optional[Tuple[np.ndarray, BlockMeta]]:
        """RAM-resident hit only: never consults the disk parent, so it is
        safe to call from latency-sensitive threads (the admission path)."""
        with self._lock:
            if seq_hash not in self._slots:
                return None
            slot = self._slots[seq_hash]
            self._slots.move_to_end(seq_hash)
            self.hits += 1
            if slot is None:
                blob, meta = self._misc[seq_hash]
                return blob.copy(), meta
            return self._ring_read_locked(slot), self._meta[seq_hash]

    def get(self, seq_hash: int) -> Optional[Tuple[np.ndarray, BlockMeta]]:
        """Tiered get: RAM first, then the disk parent (promoting the hit
        back into G2).  May do file I/O -- offload-thread only."""
        hit = self.get_ram(seq_hash)
        if hit is not None:
            return hit
        if self.parent is not None:
            promoted = self.parent.get(seq_hash)
            if promoted is not None:
                # promote back into G2 (and let LRU demote something else)
                self.put(seq_hash, *promoted)
                return promoted
        self.misses += 1
        return None

    def contains(self, seq_hash: int) -> bool:
        with self._lock:
            if seq_hash in self._slots:
                return True
        return self.parent is not None and seq_hash in self.parent

    def stats(self) -> Dict[str, Any]:
        out = {
            "g2_blocks": len(self),
            "g2_hits": self.hits,
            "g2_misses": self.misses,
            "g2_ring_bytes": self.ring_nbytes,
        }
        if self.parent is not None:
            out.update(
                g3_blocks=len(self.parent),
                g3_hits=self.parent.hits,
                g3_misses=self.parent.misses,
            )
        return out


# ---------------------------------------------------------------------------
# the G4 remote tier: fleet-shared blob store behind the hub
# ---------------------------------------------------------------------------


def pack_kv_blob_frame(blob: Any, meta: BlockMeta) -> bytes:
    """Self-describing G4 wire frame for one block blob.

    ``u32-LE header length | JSON header | payload``, the payload in the
    blob's wire form (kv_cache.blob_to_bytes: an int8 pool's pair ships
    half a dense blob's bytes).  A COPY_HELPERS member: this is the
    remote tier's one sync materialize point and runs only on the
    kv-remote thread."""
    from .engine.kv_cache import blob_kind, blob_to_bytes

    payload = blob_to_bytes(blob)
    dtype = str(blob.dtype)
    hdr = json.dumps(
        {
            "v": 1,
            "kind": blob_kind(dtype),
            "dtype": dtype,
            "shape": [int(s) for s in blob.shape],
            "meta": meta.to_dict(),
            "payload_nbytes": len(payload),
        }
    ).encode("utf-8")
    return struct.pack("<I", len(hdr)) + hdr + payload


def unpack_kv_blob_frame(buf: Any) -> Tuple[Any, BlockMeta]:
    """Inverse of :func:`pack_kv_blob_frame`; raises ``ValueError`` on any
    framing violation (truncation, garbage header, payload/shape size
    mismatch) so a corrupt store entry surfaces as a fetch miss -- the
    gate falls back to recompute -- never as a malformed scatter.

    The returned blob ALIASES ``buf`` (zero-copy unpack); the host-tier
    put that follows copies into the ring."""
    from .engine.kv_cache import blob_from_bytes, blob_nbytes

    view = memoryview(buf)
    if len(view) < 4:
        raise ValueError("G4 frame shorter than its header-length word")
    (hlen,) = struct.unpack_from("<I", view, 0)
    if hlen <= 0 or 4 + hlen > len(view):
        raise ValueError(f"G4 frame header length {hlen} exceeds frame")
    try:
        hdr = json.loads(bytes(view[4 : 4 + hlen]).decode("utf-8"))
    except (UnicodeDecodeError, ValueError) as e:
        raise ValueError("G4 frame header is not valid JSON") from e
    if not isinstance(hdr, dict) or "shape" not in hdr:
        raise ValueError("G4 frame header missing blob geometry")
    shape = tuple(int(s) for s in hdr["shape"])
    payload = view[4 + hlen :]
    # the kind decides; a dense frame names its dtype
    dtype = "int8" if hdr.get("kind") == "quant" else str(hdr.get("dtype"))
    try:
        expect = blob_nbytes(shape, dtype)
    except TypeError as e:
        raise ValueError("G4 frame header names an unknown dtype") from e
    if len(payload) != expect or expect != int(hdr.get("payload_nbytes", -1)):
        raise ValueError(
            f"G4 frame payload holds {len(payload)} bytes, geometry "
            f"expects {expect}"
        )
    meta = BlockMeta.from_dict(hdr.get("meta") or {})
    return blob_from_bytes(payload, shape, dtype), meta


class InMemoryBlobStore:
    """Process-local G4 store (tests, single-process bench legs): the hub
    blob verbs' semantics -- byte-capacity LRU over named blobs -- behind
    the same sync ``put``/``get``/``delete`` protocol :class:`RemoteTier`
    speaks, without a hub in the loop.  Thread-safe: every worker's
    kv-remote thread may hit the shared instance concurrently."""

    def __init__(self, cap_bytes: int = 1 << 30) -> None:
        self.cap_bytes = int(cap_bytes)
        self._blobs: "collections.OrderedDict[str, bytes]" = (
            collections.OrderedDict()
        )
        self._total = 0
        self._lock = threading.Lock()

    def put(self, name: str, data: bytes) -> None:
        data = bytes(data)
        with self._lock:
            old = self._blobs.pop(name, None)
            if old is not None:
                self._total -= len(old)
            self._blobs[name] = data
            self._total += len(data)
            while self._total > self.cap_bytes and len(self._blobs) > 1:
                _, dropped = self._blobs.popitem(last=False)
                self._total -= len(dropped)

    def get(self, name: str) -> Optional[bytes]:
        with self._lock:
            data = self._blobs.get(name)
            if data is not None:
                self._blobs.move_to_end(name)
            return data

    def delete(self, name: str) -> bool:
        with self._lock:
            old = self._blobs.pop(name, None)
            if old is not None:
                self._total -= len(old)
            return old is not None

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"blobs": len(self._blobs), "bytes": self._total}


class RemoteTier:
    """G4: block blobs in a fleet-shared object store (the hub's blob
    verbs, or any sync ``put``/``get`` duck type).

    All store I/O runs on ONE private thread (``kv-remote``) -- the same
    isolation contract as the kv-offload thread, so a slow or wedged
    store RPC can never stall an eviction cascade, a tick, or the event
    loop.  ``submit_put``/``fetch`` enqueue and return futures;
    ``fetch_blocking`` is for worker threads that may wait (the offload
    thread's tiered ``get_blocking`` chain, the onboard path's executor
    hop).  Every store/fetch feeds the shared telemetry
    :class:`~dynamo_tpu.runtime.telemetry.TransferLog` with the
    :data:`G4_STORE_ID` pseudo endpoint, so the fleet observatory fits a
    store link and ``predict_transfer_ms`` covers the G4 edge like any
    worker<->worker hop."""

    def __init__(
        self,
        store: Any,
        *,
        worker_id: int = 0,
        namespace: str = "dynamo",
        registry: Any = None,
    ) -> None:
        self.store = store
        self.worker_id = int(worker_id)
        self.namespace = namespace
        self._ex = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="kv-remote"
        )
        self._lock = threading.Lock()
        # hash -> frame nbytes known to be in the store (our own puts +
        # adverts merged back from the cluster-global holdings index)
        self._known: Dict[int, int] = {}
        from .runtime.metrics import RemoteKVMetrics

        self.metrics = RemoteKVMetrics(registry)
        # holdings sink (KVOffloadEngine._on_holdings): a successful put
        # advertises (hash, "remote", nbytes) to the global index
        self.holdings_cb: Optional[Any] = None
        # plain mirrors for bench/tests (no registry scrape needed)
        self.puts = 0
        self.fetches = 0
        self.store_bytes = 0
        self.store_seconds = 0.0
        self.fetch_bytes = 0
        self.fetch_seconds = 0.0
        self.fetch_fails: Dict[str, int] = {}

    # -- lifecycle ---------------------------------------------------------

    def close(self) -> None:
        self._ex.shutdown(wait=True)

    def drain(self) -> None:
        self._ex.submit(lambda: None).result()

    def _name(self, seq_hash: int) -> str:
        return f"kv/{self.namespace}/{seq_hash & (2**64 - 1):016x}"

    # -- residency index ---------------------------------------------------

    def contains(self, seq_hash: int) -> bool:
        with self._lock:
            return seq_hash in self._known

    def note_remote(self, seq_hash: int, nbytes: int) -> None:
        """Merge a G4 advert from the cluster-global index (another
        worker published this block) into the local residency view."""
        with self._lock:
            self._known[seq_hash] = int(nbytes)

    def known_blocks(self) -> int:
        with self._lock:
            return len(self._known)

    # -- async surface -----------------------------------------------------

    def submit_put(self, seq_hash: int, blob: Any, meta: BlockMeta):
        """Queue a store upload; returns the future (True on success)."""
        return self._ex.submit(self._put, seq_hash, blob, meta)

    def fetch(self, seq_hash: int):
        """Queue a store fetch; the future resolves to ``(blob, meta)``
        or None (missing / failed / corrupt -- the caller recomputes)."""
        return self._ex.submit(self._get, seq_hash)

    def fetch_blocking(
        self, seq_hash: int
    ) -> Optional[Tuple[Any, BlockMeta]]:
        """Worker-thread fetch (never the event loop): waits on the
        kv-remote thread's result."""
        return self.fetch(seq_hash).result()

    # -- kv-remote thread side ---------------------------------------------

    def _put(self, seq_hash: int, blob: Any, meta: BlockMeta) -> bool:
        thread_sentry.assert_role("kv-remote", what="RemoteTier._put")
        try:
            frame = pack_kv_blob_frame(blob, meta)
            t0 = time.perf_counter()
            self.store.put(self._name(seq_hash), frame)
            dt = time.perf_counter() - t0
        except Exception:
            logger.debug("G4 store put failed for %x", seq_hash, exc_info=True)
            return False
        with self._lock:
            self._known[seq_hash] = len(frame)
            self.puts += 1
            self.store_bytes += len(frame)
            self.store_seconds += dt
            known = len(self._known)
        self.metrics.record_store(len(frame), dt)
        self.metrics.blocks.set(known)
        from .runtime.telemetry import note_transfer

        note_transfer(self.worker_id, G4_STORE_ID, len(frame), dt)
        cb = self.holdings_cb
        if cb is not None:
            try:
                cb([(seq_hash, "remote", len(frame))])
            except Exception:
                logger.debug("G4 holdings callback failed", exc_info=True)
        return True

    def _get(self, seq_hash: int) -> Optional[Tuple[Any, BlockMeta]]:
        thread_sentry.assert_role("kv-remote", what="RemoteTier._get")
        from .runtime import faults

        if faults.injector.enabled and faults.injector.should_fire(
            "remote.fetch_fail", f"g4/{seq_hash:x}"
        ):
            self._count_fail("fetch_fail")
            return None
        t0 = time.perf_counter()
        try:
            frame = self.store.get(self._name(seq_hash))
        except Exception:
            logger.debug(
                "G4 store get failed for %x", seq_hash, exc_info=True
            )
            self._count_fail("fetch_fail")
            return None
        if frame is None:
            # the store LRU'd it out from under the index: forget it
            with self._lock:
                self._known.pop(seq_hash, None)
            self._count_fail("missing")
            return None
        dt = time.perf_counter() - t0
        if faults.injector.enabled and faults.injector.should_fire(
            "remote.blob_corrupt", f"g4/{seq_hash:x}"
        ):
            # truncate mid-payload: the frame validator must catch it
            frame = bytes(frame)[: max(len(frame) // 2, 4)]
        try:
            blob, meta = unpack_kv_blob_frame(frame)
        except ValueError:
            logger.warning(
                "G4 blob for %x failed frame validation; treating as miss",
                seq_hash,
            )
            self._count_fail("blob_corrupt")
            return None
        with self._lock:
            self.fetches += 1
            self.fetch_bytes += len(frame)
            self.fetch_seconds += dt
        self.metrics.record_fetch(len(frame), dt)
        from .runtime.telemetry import note_transfer

        note_transfer(G4_STORE_ID, self.worker_id, len(frame), dt)
        return blob, meta

    def _count_fail(self, cause: str) -> None:
        with self._lock:
            self.fetch_fails[cause] = self.fetch_fails.get(cause, 0) + 1
        self.metrics.fetch_failures.labels(cause).inc()

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            out: Dict[str, Any] = {
                "g4_known_blocks": len(self._known),
                "g4_puts": self.puts,
                "g4_fetches": self.fetches,
                "g4_store_bytes": self.store_bytes,
                "g4_fetch_bytes": self.fetch_bytes,
                "g4_fetch_fails": dict(self.fetch_fails),
            }
            seconds = self.store_seconds + self.fetch_seconds
            if seconds > 0:
                out["kv_g4_gbps"] = round(
                    (self.store_bytes + self.fetch_bytes) / seconds / 1e9, 3
                )
        return out


def parse_kv_remote_spec(spec: str) -> Optional[Dict[str, Any]]:
    """Parse a ``--kv-remote`` / ``DYN_KV_REMOTE`` value into G4 settings,
    or None when empty/off (no remote tier, no kv-remote thread).

    Grammar: ``1``/``on`` arms the tier with defaults, or a
    comma-separated ``k=v`` list::

        DYN_KV_REMOTE=mirror=1,fetch=1,prefill_tok_s=4000,gbps=1.0,namespace=prod

    ``mirror`` re-publishes host-tier eviction stores into the fleet
    store; ``fetch`` lets the router gate choose G4 as a prefix source;
    ``prefill_tok_s`` is the per-worker prefill-rate estimate and
    ``gbps`` the unfitted-link bandwidth prior, both feeding the
    fetch-vs-recompute gate until the observatory has real
    observations."""
    spec = (spec or "").strip()
    if not spec or spec.lower() in ("0", "off", "false", "no"):
        return None
    out: Dict[str, Any] = {
        "mirror": True,
        "fetch": True,
        "prefill_tok_s": 4000.0,
        "gbps": 1.0,
        "namespace": "dynamo",
    }
    if spec.lower() in ("1", "on", "true", "yes"):
        return out
    for clause in filter(None, (c.strip() for c in spec.split(","))):
        k, sep, v = clause.partition("=")
        k = k.strip().lower()
        if not sep:
            raise ValueError(f"malformed DYN_KV_REMOTE clause {clause!r}")
        try:
            if k in ("mirror", "fetch"):
                out[k] = v.strip().lower() not in ("0", "off", "false", "no")
            elif k in ("prefill_tok_s", "gbps"):
                out[k] = float(v)
                if out[k] <= 0:
                    raise ValueError(f"{k} must be positive")
            elif k == "namespace":
                out[k] = v.strip()
            else:
                raise ValueError(f"unknown DYN_KV_REMOTE key {k!r}")
        except ValueError as e:
            raise ValueError(f"bad DYN_KV_REMOTE value {clause!r}") from e
    return out


def env_remote_spec(
    environ: Optional[Dict[str, str]] = None,
) -> Optional[Dict[str, Any]]:
    """``DYN_KV_REMOTE`` from the environment, parsed; None when unset."""
    env = environ if environ is not None else os.environ
    return parse_kv_remote_spec(env.get("DYN_KV_REMOTE", ""))


# ---------------------------------------------------------------------------
# the offload engine: dedicated thread + swap records + env arming
# ---------------------------------------------------------------------------


SWAP_PENDING = "pending"
SWAP_READY = "ready"
SWAP_FAILED = "failed"


@dataclass
class PrefetchState:
    """One queued request's prefetch walk (queue-side prefix promotion
    with completion tracking, ISSUE 10).

    ``done`` collects the hashes the walk found (or made) RAM-resident
    -- each is pinned in the host ring until the request admits or
    cancels.  ``completed_at`` stamps the walk's end; together with
    ``issued_at`` and the admission stamp it yields the *overlap ratio*:
    the fraction of the disk->host walk that ran during queue wait
    instead of on the TTFT critical path (1.0 = fully hidden)."""

    hashes: List[int]
    issued_at: float = field(default_factory=time.perf_counter)
    done: set = field(default_factory=set)
    completed_at: Optional[float] = None
    # stamped by finish_prefetch when admission lands before the walk
    # finishes; the walk's tail then computes the partial overlap
    admitted_at: Optional[float] = None
    consumed: Optional[set] = None


@dataclass
class SwapRecord:
    """One preempted sequence's parked KV, staged across two homes:

    ``dev`` is the gathered device-side snapshot -- retained (budgeted)
    so a short park restores with a device-to-device scatter and never
    round-trips the host link (FlowKV's low-latency staged transfer; the host link is
    far slower than HBM).  ``blob``
    is the host materialization the offload thread produces -- the spill
    that survives once the device copy is dropped for budget.  A record
    is restorable the moment either exists."""

    cache_len: int
    n_blocks: int  # block-equivalents charged against the swap budget
    # shard geometry of the source pool at snapshot time (provenance for
    # the restore-side compatibility check; blobs are full-width)
    shards: Optional[Dict[str, int]] = None
    state: str = SWAP_PENDING
    dev: Any = None  # device-resident staging copy (fast-path restore)
    blob: Optional[np.ndarray] = None
    nbytes: int = 0
    started_at: float = field(default_factory=time.perf_counter)


def env_offload_spec(environ: Optional[Dict[str, str]] = None) -> Optional[Dict[str, Any]]:
    """Parse ``DYN_KV_OFFLOAD`` into offload-plane settings, or None when
    unset (the plane stays a no-op: no tiers, no thread, no swap).

    Grammar: ``1``/``on`` arms the host tier with defaults, or a
    comma-separated ``k=v`` list::

        DYN_KV_OFFLOAD=host=256,disk=1024,dir=/var/kv,swap=1

    with ``host``/``disk`` in blocks, ``dir`` the G3 root, and ``swap``
    enabling/disabling swap-based preemption (default on)."""
    env = environ if environ is not None else os.environ
    spec = env.get("DYN_KV_OFFLOAD", "").strip()
    if not spec or spec.lower() in ("0", "off", "false", "no"):
        return None
    out: Dict[str, Any] = {"host": 256, "disk": 0, "dir": None, "swap": True}
    if spec.lower() in ("1", "on", "true", "yes"):
        return out
    for clause in filter(None, (c.strip() for c in spec.split(","))):
        k, sep, v = clause.partition("=")
        k = k.strip().lower()
        if not sep:
            raise ValueError(f"malformed DYN_KV_OFFLOAD clause {clause!r}")
        try:
            if k == "host":
                out["host"] = int(v)
            elif k == "disk":
                out["disk"] = int(v)
            elif k == "dir":
                out["dir"] = v
            elif k == "swap":
                out["swap"] = v.strip().lower() not in ("0", "off", "false", "no")
            else:
                raise ValueError(f"unknown DYN_KV_OFFLOAD key {k!r}")
        except ValueError as e:
            raise ValueError(f"bad DYN_KV_OFFLOAD value {clause!r}") from e
    return out


class KVOffloadEngine:
    """The G2/G3 coordinator: owns the tiers, the dedicated offload
    thread, the swap records, and the plane's metrics.

    Every blocking step -- the device->host materialize of an eviction
    snapshot, disk writes, disk reads, host-ring copies -- runs on ONE
    private thread (``kv-offload``), the same isolation pattern as the
    hub WAL's writer thread: the asyncio event loop and the engine's
    device executor only ever enqueue work here or probe RAM-resident
    indexes.  Capacity and occupancy are deterministic: the host ring is
    one preallocated buffer, swap records are budgeted in
    block-equivalents against ``swap_blocks``."""

    def __init__(
        self,
        host_blocks: int,
        disk_blocks: int = 0,
        disk_dir: Optional[str] = None,
        *,
        swap_enabled: bool = True,
        swap_blocks: Optional[int] = None,
        registry: Any = None,
    ) -> None:
        disk = None
        if disk_blocks > 0:
            if not disk_dir:
                raise ValueError("disk_blocks > 0 requires disk_dir")
            disk = DiskTier(disk_dir, disk_blocks)
        self.disk = disk
        self.host = HostTier(host_blocks, parent=disk)
        self.swap_enabled = swap_enabled
        self.swap_blocks = (
            swap_blocks if swap_blocks is not None else max(host_blocks, 8)
        )
        # device-side staging budget (block-equivalents of retained device
        # snapshots, HBM *outside* the page pool -- the same scratch class
        # as the disagg export gathers); 0 = host-blob restores only.
        # Half the swap budget: short parks ride the device fast path,
        # but once parked KV piles up the overflow spills to host blobs
        # instead of holding HBM scratch for the whole park.
        self.swap_device_blocks = max(self.swap_blocks // 2, 1)
        self._swaps: Dict[str, SwapRecord] = {}
        self._swap_used = 0
        self._swap_dev_used = 0
        self._promoting: set = set()
        self._lock = threading.Lock()
        self._ex = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="kv-offload"
        )
        # lazy import keeps this module importable without prometheus
        from .runtime.metrics import OffloadMetrics

        self.metrics = OffloadMetrics(registry)
        self._registry = registry
        # the G4 remote tier (attach_remote): host-tier eviction stores
        # mirror into the fleet store, and the tiered get_blocking chain
        # extends host -> disk -> remote
        self.remote: Optional[RemoteTier] = None
        self._remote_mirror = True
        # holdings sink (engine._emit_kv_holdings): receives every tier
        # residency delta [(hash, tier|None, nbytes)] for the
        # cluster-global prefix index
        self.holdings_cb: Optional[Any] = None
        self.host.holdings_cb = self._on_holdings
        # called (from the offload thread) when a swap blob becomes ready,
        # so a sleeping tick loop wakes to apply it
        self.wake_cb: Optional[Any] = None
        # plain-int mirrors for bench/tests (no registry scrape needed)
        self.offload_bytes = 0
        self.offload_seconds = 0.0
        self.onboard_bytes = 0
        self.onboard_seconds = 0.0
        # per-tier [bytes, seconds] so bench can separate swap restores
        # from prefix onboards when deriving recovery rates
        self.onboard_detail: Dict[str, List[float]] = {}
        self.tier_hits: Dict[str, int] = {"host": 0, "disk": 0, "swap": 0}
        self.tier_lookups = 0
        # disk->host promotions (prefetch or lookup-triggered); kept OUT
        # of tier_hits so tier_hit_rate only counts lookups actually
        # served -- a warmed-but-unused worker must not read as warm
        self.disk_promotes = 0
        self.copy_fails = 0
        self.swap_outs = 0
        self.swap_ins = 0
        self.swap_fallbacks = 0
        self.onboard_fallbacks = 0
        # queue-side prefetch tracking (ISSUE 10): request-keyed walk
        # states (pins + stamps) and the aggregate counters behind
        # dynamo_kv_prefetch_* / the bench overlap ratio
        self._prefetch_states: Dict[str, PrefetchState] = {}
        self.prefetch_issued = 0  # blocks requested by tracked walks
        self.prefetch_hits = 0  # staged blocks consumed at admission
        self.prefetch_wasted_bytes = 0  # staged but never consumed
        self.prefetch_overlap_sum = 0.0
        self.prefetch_overlap_n = 0

    # -- lifecycle -----------------------------------------------------------

    def close(self) -> None:
        self._ex.shutdown(wait=True)
        if self.remote is not None:
            self.remote.close()

    def drain(self) -> None:
        """Barrier: returns once every queued offload/prefetch/swap task
        has run (tests and shutdown; never called on a hot path)."""
        self._ex.submit(lambda: None).result()
        if self.remote is not None:
            self.remote.drain()

    def attach_remote(
        self,
        store: Any,
        *,
        worker_id: int = 0,
        namespace: str = "dynamo",
        mirror: bool = True,
    ) -> RemoteTier:
        """Arm the G4 tier over ``store`` (the hub blob verbs or any sync
        put/get duck type).  ``mirror=True`` re-publishes every host-tier
        eviction store into the fleet store so peers (and cold restarts)
        can fetch instead of recompute."""
        remote = RemoteTier(
            store,
            worker_id=worker_id,
            namespace=namespace,
            registry=self._registry,
        )
        remote.holdings_cb = self._on_holdings
        self._remote_mirror = bool(mirror)
        self.remote = remote
        return remote

    def _on_holdings(self, delta: List[Tuple[int, Optional[str], int]]) -> None:
        """Tier-side residency deltas (host/disk/remote puts, demotions,
        evictions, promotes) fan into the engine-facing sink."""
        cb = self.holdings_cb
        if cb is None:
            return
        try:
            cb(delta)
        except Exception:
            logger.debug("holdings sink failed", exc_info=True)

    def _wake(self) -> None:
        cb = self.wake_cb
        if cb is not None:
            try:
                cb()
            except Exception:
                logger.debug("offload wake callback failed", exc_info=True)

    # -- eviction path (G1 -> G2 -> G3) --------------------------------------

    def submit_evict(self, seq_hash: int, snap: Any, meta: BlockMeta) -> None:
        """Queue an eviction snapshot for materialize + tier store.  The
        caller has already dispatched the device slice and started the
        async host copy; nothing here blocks."""
        self._ex.submit(self._store_evict, seq_hash, snap, meta)

    def _store_evict(self, seq_hash: int, snap: Any, meta: BlockMeta) -> None:
        from .runtime import faults

        try:
            if faults.injector.enabled and faults.injector.should_fire(
                "offload.copy_fail", f"evict/{seq_hash:x}"
            ):
                # copy_fails is also bumped by swap_out on the engine
                # executor: both increments go through the lock (DT014)
                with self._lock:
                    self.copy_fails += 1
                self.metrics.copy_fails.inc()
                return  # lost offload = a cache miss later, never an error
            t0 = time.perf_counter()
            blob = to_host(snap)
            self.host.put(seq_hash, blob, meta)
            dt = time.perf_counter() - t0
            with self._lock:
                self.offload_bytes += blob.nbytes
                self.offload_seconds += dt
            self.metrics.record_offload("host", blob.nbytes, dt)
            remote = self.remote
            if (
                remote is not None
                and self._remote_mirror
                and not remote.contains(seq_hash)
            ):
                # fleet publication rides the kv-remote thread; the host
                # blob is already materialized, so this enqueue is free
                remote.submit_put(seq_hash, blob, meta)
            self._observe_occupancy()
        except Exception:
            logger.debug("offload store failed for %x", seq_hash, exc_info=True)

    def submit_put(self, seq_hash: int, blob: np.ndarray, meta: BlockMeta) -> None:
        """Store an externally-sourced block (prefix-onboard donor fetch)
        without touching the calling thread: the put -- and any disk
        demotion it cascades into -- runs on the offload thread."""
        self._ex.submit(self._store_put, seq_hash, blob, meta)

    def _store_put(self, seq_hash: int, blob: np.ndarray, meta: BlockMeta) -> None:
        try:
            self.host.put(seq_hash, blob, meta)
            self._observe_occupancy()
        except Exception:
            logger.debug("tier put failed for %x", seq_hash, exc_info=True)

    # -- lookup path (tiered prefix reuse) -----------------------------------

    def lookup(self, seq_hash: int) -> Optional[Tuple[np.ndarray, BlockMeta, str]]:
        """Admission-time probe: returns ``(blob, meta, tier)`` for a
        RAM-resident hit.  A disk-only hit schedules an asynchronous
        promote (so a later admission -- or the retry after prefetch --
        hits in RAM) and returns None: this path runs on the event loop
        and must never wait on file I/O."""
        self.tier_lookups += 1
        hit = self.host.get_ram(seq_hash)
        if hit is not None:
            self.tier_hits["host"] += 1
            self.metrics.tier_hits.labels("host").inc()
            return hit[0], hit[1], "host"
        if self.disk is not None and seq_hash in self.disk:
            with self._lock:
                schedule = seq_hash not in self._promoting
                if schedule:
                    self._promoting.add(seq_hash)
            if schedule:
                self._ex.submit(self._promote, seq_hash)
        return None

    def _promote(self, seq_hash: int) -> None:
        try:
            hit = self.host.get(seq_hash)  # promotes disk -> ring
            if hit is not None:
                self.disk_promotes += 1
                self.metrics.tier_promotes.labels("disk").inc()
                self._observe_occupancy()
        except Exception:
            logger.debug("disk promote failed for %x", seq_hash, exc_info=True)
        finally:
            with self._lock:
                self._promoting.discard(seq_hash)
            self._wake()

    def prefetch(
        self, seq_hashes: List[int], request_id: Optional[str] = None
    ) -> None:
        """Queue-side prefetch: while the request waits for admission,
        promote its offloaded prefix chain into the host ring so the
        admission-time ``lookup`` is a RAM hit and the onboard's H2D
        scatter can be dispatched with the admitting tick (overlapping
        the copy with that tick's compute) instead of stalling on a disk
        read.  Stops at the first tier miss -- prefix chains are only
        usable contiguously.

        With a ``request_id`` the walk is *tracked*: every block it
        stages is pinned against ring demotion until the request admits
        (:meth:`finish_prefetch`) or cancels (:meth:`cancel_prefetch`),
        and the issue/complete/admit stamps feed the
        ``dynamo_kv_prefetch_*`` series and the bench overlap ratio."""
        if not seq_hashes:
            return
        state = None
        if request_id is not None:
            state = PrefetchState(hashes=list(seq_hashes))
            with self._lock:
                old = self._prefetch_states.pop(request_id, None)
                self._prefetch_states[request_id] = state
                self.prefetch_issued += len(seq_hashes)
            if old is not None:
                self._release_prefetch(old, wasted=True)
            self.metrics.prefetch_issued.inc(len(seq_hashes))
        self._ex.submit(self._prefetch, list(seq_hashes), request_id, state)

    def _prefetch(
        self,
        seq_hashes: List[int],
        request_id: Optional[str] = None,
        state: Optional[PrefetchState] = None,
    ) -> None:
        for h in seq_hashes:
            try:
                resident = self.host.get_ram(h) is not None
                if not resident:
                    if self.host.get(h) is None:
                        break
                    # a promote is NOT a hit: only lookups actually
                    # served count toward tier_hit_rate (the router
                    # warmth signal)
                    self.disk_promotes += 1
                    self.metrics.tier_promotes.labels("disk").inc()
                if state is not None:
                    # pin-and-record under the engine lock so a
                    # concurrent cancel (which pops the state under the
                    # same lock and unpins ``done``) cannot miss a pin
                    with self._lock:
                        if self._prefetch_states.get(
                            request_id
                        ) is state and self.host.pin(h):
                            state.done.add(h)
            except Exception:
                logger.debug("prefetch failed at %x", h, exc_info=True)
                break
        if state is not None:
            settle = False
            with self._lock:
                state.completed_at = time.perf_counter()
                if (
                    self._prefetch_states.get(request_id) is state
                    and state.admitted_at is not None
                ):
                    # admission landed mid-walk: settle the partial
                    # overlap now that the walk's end is known
                    self._prefetch_states.pop(request_id, None)
                    settle = True
            if settle:
                self._settle_prefetch(state)
        self._observe_occupancy()

    def finish_prefetch(
        self, request_id: str, consumed_hashes: List[int]
    ) -> int:
        """Admission landed: release the request's prefetch pins, count
        hits (staged blocks the admission actually onboarded) vs wasted
        bytes, and record the overlap ratio.  Returns the hit count (the
        admission-path span attr).  Safe to call for untracked ids."""
        with self._lock:
            state = self._prefetch_states.get(request_id)
            if state is None:
                return 0
            state.admitted_at = time.perf_counter()
            state.consumed = set(consumed_hashes)
            if state.completed_at is None:
                # walk still running: it settles the state at its end
                # (pins it takes after this point release there too)
                return len(state.done & state.consumed)
            self._prefetch_states.pop(request_id, None)
        return self._settle_prefetch(state)

    def cancel_prefetch(self, request_id: str) -> None:
        """A queued request left before admission (cancel / error): free
        its host-staged prefetch state -- unpin every staged block and
        charge the bytes as wasted.  Without this, pins from abandoned
        requests accumulate and the ring degenerates to unevictable.  A
        still-running walk stops pinning the moment the state is popped
        (it re-checks registration under the lock before every pin)."""
        with self._lock:
            state = self._prefetch_states.pop(request_id, None)
        if state is None:
            return
        self._release_prefetch(state, wasted=True)

    def _settle_prefetch(self, state: PrefetchState) -> int:
        """Settle one tracked walk's accounting and release its pins.
        Called from the offload thread (walk end) or the engine executor
        (admission) -- never while holding ``self._lock``; the plain-int
        aggregates update under it so concurrent settles cannot lose
        increments."""
        consumed = state.consumed or set()
        hits = len(state.done & consumed)
        wasted = len(state.done - consumed) * self.host.block_nbytes
        walk = (state.completed_at or state.issued_at) - state.issued_at
        ratio = None
        if walk > 0 and state.admitted_at is not None:
            ratio = min(
                max((state.admitted_at - state.issued_at) / walk, 0.0), 1.0
            )
        with self._lock:
            self.prefetch_hits += hits
            self.prefetch_wasted_bytes += wasted
            if ratio is not None:
                self.prefetch_overlap_sum += ratio
                self.prefetch_overlap_n += 1
        if hits:
            self.metrics.prefetch_hits.inc(hits)
        if wasted:
            self.metrics.prefetch_wasted.inc(wasted)
        if ratio is not None:
            self.metrics.prefetch_overlap.observe(ratio)
        for h in state.done:
            self.host.unpin(h)
        return hits

    def _release_prefetch(self, state: PrefetchState, wasted: bool) -> None:
        if wasted and state.done:
            nbytes = len(state.done) * self.host.block_nbytes
            with self._lock:
                self.prefetch_wasted_bytes += nbytes
            self.metrics.prefetch_wasted.inc(nbytes)
        for h in state.done:
            self.host.unpin(h)

    def contains(self, seq_hash: int) -> bool:
        return self.host.contains(seq_hash)

    def get_blocking(self, seq_hash: int) -> Optional[Tuple[np.ndarray, Any]]:
        """Tiered get from a worker thread (block export / donor paths):
        routes the possibly-disk read through the offload thread and
        waits for it, falling through to the G4 store when the local
        tiers miss (the fetch waits on the kv-remote thread -- a
        different executor, so no deadlock).  A G4 hit promotes into the
        host ring so the next lookup is a RAM hit.  Never call on the
        event loop."""
        hit = self._ex.submit(self.host.get, seq_hash).result()
        if (
            hit is None
            and self.remote is not None
            and self.remote.contains(seq_hash)
        ):
            fetched = self.remote.fetch_blocking(seq_hash)
            if fetched is not None:
                self.submit_put(seq_hash, fetched[0], fetched[1])
                hit = fetched
        return hit

    # -- swap records (preempted-sequence KV) --------------------------------

    def swap_out(
        self, request_id: str, snap: Any, cache_len: int, n_blocks: int,
        shards: Optional[Dict[str, int]] = None,
    ) -> bool:
        """Reserve budget and park a preemption snapshot.  The device copy
        is retained (within ``swap_device_blocks``) so a short park can
        restore without ever crossing the host link; the host materialize
        is queued as the spill.  Returns False (caller falls back to
        recompute) when swap is disabled, the budget is exhausted, or the
        ``offload.copy_fail`` chaos site fires -- tiers-full is a
        fallback, never an error."""
        from .runtime import faults

        if not self.swap_enabled:
            return False
        if faults.injector.enabled and faults.injector.should_fire(
            "offload.copy_fail", f"swap/{request_id}"
        ):
            # runs on the engine executor while the offload thread may be
            # bumping the same counters: lock-guard the increments (DT014)
            with self._lock:
                self.copy_fails += 1
                self.swap_fallbacks += 1
            self.metrics.copy_fails.inc()
            self.metrics.swap_fallbacks.labels("copy_fail").inc()
            return False
        keep_dev = self.swap_device_blocks > 0
        with self._lock:
            if request_id in self._swaps:
                return False  # defensive: one parked record per request
            if self._swap_used + n_blocks > self.swap_blocks:
                self.swap_fallbacks += 1
                self.metrics.swap_fallbacks.labels("budget").inc()
                return False
            self._swap_used += n_blocks
            if keep_dev:
                self._swap_dev_used += n_blocks
            self._swaps[request_id] = SwapRecord(
                cache_len=cache_len,
                n_blocks=n_blocks,
                shards=dict(shards) if shards else None,
                dev=snap if keep_dev else None,
            )
            self.swap_outs += 1
        self.metrics.swap_events.labels("out").inc()
        self._ex.submit(self._store_swap, request_id, snap)
        return True

    def _store_swap(self, request_id: str, snap: Any) -> None:
        with self._lock:  # racing drop_swap pops under the same lock
            rec = self._swaps.get(request_id)
        if rec is None:
            return  # dropped (cancel / already restored from the device copy)
        try:
            t0 = time.perf_counter()
            rec.blob = to_host(snap)
            rec.nbytes = rec.blob.nbytes
            dt = time.perf_counter() - t0
            rec.state = SWAP_READY
            with self._lock:
                self.offload_bytes += rec.nbytes
                self.offload_seconds += dt
            self.metrics.record_offload("swap", rec.nbytes, dt)
            # host spill landed: drop the device copy if the staging
            # budget is oversubscribed (long parks ride the host blob)
            with self._lock:
                if (
                    rec.dev is not None
                    and self._swap_dev_used > self.swap_device_blocks
                ):
                    rec.dev = None
                    self._swap_dev_used -= rec.n_blocks
        except Exception:
            logger.debug("swap store failed for %s", request_id, exc_info=True)
            rec.state = SWAP_FAILED
        finally:
            self._observe_occupancy()
            self._wake()

    def poll_swap(self, request_id: str) -> Optional[SwapRecord]:
        return self._swaps.get(request_id)

    def drop_swap(self, request_id: str) -> None:
        with self._lock:
            rec = self._swaps.pop(request_id, None)
            if rec is not None:
                self._swap_used -= rec.n_blocks
                if rec.dev is not None:
                    rec.dev = None
                    self._swap_dev_used -= rec.n_blocks
        if rec is not None:
            self._observe_occupancy()

    def record_onboard(self, tier: str, nbytes: int, seconds: float) -> None:
        """Called by the engine after an onboard scatter lands on device;
        feeds the ``kv_onboard_gbps`` accounting."""
        self.onboard_bytes += nbytes
        self.onboard_seconds += seconds
        d = self.onboard_detail.setdefault(tier, [0.0, 0.0])
        d[0] += nbytes
        d[1] += seconds
        if tier == "swap":
            self.swap_ins += 1
            self.metrics.swap_events.labels("in").inc()
        self.metrics.record_onboard(tier, nbytes, seconds)

    # -- observability -------------------------------------------------------

    def _observe_occupancy(self) -> None:
        with self._lock:  # _swap_used mutates under the lock on two roles
            swap_used = self._swap_used
        self.metrics.tier_blocks.labels("host").set(len(self.host))
        if self.disk is not None:
            self.metrics.tier_blocks.labels("disk").set(len(self.disk))
        self.metrics.tier_blocks.labels("swap").set(swap_used)

    @property
    def tier_hit_rate(self) -> float:
        """Fraction of tier lookups served from G2/G3 -- the router-facing
        warmth signal (a worker whose tiers keep hitting is a better home
        for repeat prefixes than a cold one)."""
        if not self.tier_lookups:
            return 0.0
        return min(
            (self.tier_hits["host"] + self.tier_hits["disk"])
            / self.tier_lookups,
            1.0,
        )

    def stats(self) -> Dict[str, Any]:
        out = dict(self.host.stats())
        out.update(
            offload_bytes=self.offload_bytes,
            offload_seconds=round(self.offload_seconds, 6),
            onboard_bytes=self.onboard_bytes,
            onboard_seconds=round(self.onboard_seconds, 6),
            onboard_detail={
                t: {"bytes": int(b), "seconds": round(s, 6)}
                for t, (b, s) in self.onboard_detail.items()
            },
            tier_hits=dict(self.tier_hits),
            tier_lookups=self.tier_lookups,
            disk_promotes=self.disk_promotes,
            swap_outs=self.swap_outs,
            swap_ins=self.swap_ins,
            swap_fallbacks=self.swap_fallbacks,
            onboard_fallbacks=self.onboard_fallbacks,
            swap_used_blocks=self._swap_used,
            copy_fails=self.copy_fails,
            prefetch_issued=self.prefetch_issued,
            prefetch_hits=self.prefetch_hits,
            prefetch_wasted_bytes=self.prefetch_wasted_bytes,
            prefetch_pinned_blocks=self.host.pinned_blocks,
        )
        if self.prefetch_overlap_n:
            out["prefetch_overlap_ratio"] = round(
                self.prefetch_overlap_sum / self.prefetch_overlap_n, 4
            )
        if self.onboard_seconds > 0:
            out["onboard_gbps"] = round(
                self.onboard_bytes / self.onboard_seconds / 1e9, 3
            )
        if self.remote is not None:
            out.update(self.remote.stats())
        return out
