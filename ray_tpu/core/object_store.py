"""Node-local shared-memory object store (plasma equivalent).

TPU-native analog of the reference's plasma store
(/root/reference/src/ray/object_manager/plasma/store.cc, plasma_allocator.cc,
eviction_policy.cc): objects live in OS shared memory, readers map them
zero-copy, the per-node agent owns lifecycle (create/seal/pin/evict/delete) with
LRU eviction of unpinned sealed objects when capacity is exceeded.

Two backends share the ShmStore interface:
- this pure-python backend: one ``multiprocessing.shared_memory`` segment per
  object (simple, portable);
- the native C++ arena store in ``ray_tpu/_native`` (single mapped arena +
  free-list allocator), used when built (config.use_native_object_store).

TPU twist (SURVEY.md §7 phase 2): sealed objects carry a ``device_hint`` so a
get on a TPU host can ``device_put`` straight from shm into HBM without an
extra host copy.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from multiprocessing import shared_memory

from ray_tpu.core.ids import ObjectID
from ray_tpu.exceptions import ObjectStoreFullError

# Arenas living in THIS process (agent-side native stores), keyed by shm
# name: same-process clients write through the agent's warm mapping (pages
# materialized by the C++ pre-toucher) instead of faulting in their own.
_LOCAL_ARENAS: dict[str, "NativeObjectStore"] = {}
_ARENA_LOCK = threading.Lock()


def local_arena(shm_name: str) -> "NativeObjectStore | None":
    """The in-process native store owning ``shm_name``, if any."""
    with _ARENA_LOCK:
        return _LOCAL_ARENAS.get(shm_name)


@dataclass
class _ObjMeta:
    shm_name: str
    size: int
    sealed: bool = False
    pinned: bool = True  # pinned on create until the owner unpins (ref: PinObjectIDs)
    device_hint: str = ""
    created_at: float = field(default_factory=time.monotonic)


class ShmStore:
    """Agent-side registry + allocator. All mutations go through the node agent's
    RPC handlers; clients attach to segments by name for zero-copy reads."""

    def __init__(self, capacity_bytes: int, prefix: str = "rtpu"):
        self.capacity = capacity_bytes
        self.prefix = prefix
        self._lock = threading.Lock()
        self._objects: OrderedDict[ObjectID, _ObjMeta] = OrderedDict()  # LRU order
        self._segments: dict[str, shared_memory.SharedMemory] = {}
        self._used = 0
        self.num_evicted = 0
        self.on_evict = None  # callback(ObjectID) — notify owner of lost copy

    # ---- lifecycle ----------------------------------------------------
    def create(self, object_id: ObjectID, size: int,
               device_hint: str = "") -> tuple[str, int]:
        """Returns (shm_name, offset). Offset is always 0 for this backend
        (one segment per object); the native arena backend returns real
        offsets into its single segment."""
        with self._lock:
            if object_id in self._objects:
                meta = self._objects[object_id]
                return meta.shm_name, 0
            self._evict_until(size)
            if self._used + size > self.capacity:
                raise ObjectStoreFullError(
                    f"object of {size} bytes does not fit: {self._used}/{self.capacity} used")
            name = f"{self.prefix}_{object_id.hex()[:24]}"
            seg = shared_memory.SharedMemory(name=name, create=True, size=max(size, 1))
            self._segments[name] = seg
            self._objects[object_id] = _ObjMeta(shm_name=name, size=size, device_hint=device_hint)
            self._used += size
            return name, 0

    def seal(self, object_id: ObjectID):
        with self._lock:
            meta = self._objects.get(object_id)
            if meta is None:
                raise KeyError(f"seal of unknown object {object_id}")
            meta.sealed = True
            self._objects.move_to_end(object_id)

    def get_meta(self, object_id: ObjectID) -> tuple | None:
        """(shm_name, offset, size, device_hint, copy_on_read) of a sealed
        object. copy_on_read=False: per-object segments stay valid while
        mapped even after unlink, so zero-copy reads are safe."""
        with self._lock:
            meta = self._objects.get(object_id)
            if meta is None or not meta.sealed:
                return None
            self._objects.move_to_end(object_id)  # LRU touch
            return (meta.shm_name, 0, meta.size, meta.device_hint, False)

    def contains(self, object_id: ObjectID) -> bool:
        with self._lock:
            m = self._objects.get(object_id)
            return m is not None and m.sealed

    def pin(self, object_id: ObjectID, pinned: bool = True):
        """Owner pins primary copies while refs are live
        (ref: node_manager.proto:479 PinObjectIDs)."""
        with self._lock:
            meta = self._objects.get(object_id)
            if meta is not None:
                meta.pinned = pinned

    def delete(self, object_id: ObjectID):
        with self._lock:
            self._delete_locked(object_id)

    def _delete_locked(self, object_id: ObjectID):
        meta = self._objects.pop(object_id, None)
        if meta is None:
            return
        seg = self._segments.pop(meta.shm_name, None)
        self._used -= meta.size
        if seg is not None:
            try:
                seg.close()
                seg.unlink()
            except Exception:
                pass

    def _evict_until(self, need: int):
        """Evict unpinned sealed objects in LRU order (ref: eviction_policy.cc)."""
        if self._used + need <= self.capacity:
            return
        victims = [oid for oid, m in self._objects.items() if m.sealed and not m.pinned]
        for oid in victims:
            if self._used + need <= self.capacity:
                break
            self._delete_locked(oid)
            self.num_evicted += 1
            if self.on_evict is not None:
                try:
                    self.on_evict(oid)
                except Exception:
                    pass

    def read_bytes(self, object_id: ObjectID, offset: int = 0,
                   size: int | None = None) -> tuple[int, bytes] | None:
        """Range copy-out for chunked cross-node transfer
        (ref: object_manager ObjectBufferPool chunking). Returns
        (total_size, chunk)."""
        meta = self.get_meta(object_id)
        if meta is None:
            return None
        seg = self._segments.get(meta[0])
        if seg is None:
            return None
        total = meta[2]
        end = total if size is None else min(total, offset + size)
        return total, bytes(seg.buf[offset:end])

    def write_bytes(self, object_id: ObjectID, data: bytes):
        """Write a received remote copy (ref: object_manager.cc chunked push)."""
        name, _off = self.create(object_id, len(data))
        seg = self._segments[name]
        seg.buf[: len(data)] = data
        self.seal(object_id)

    def write_chunk(self, object_id: ObjectID, offset: int, data: bytes,
                    total: int):
        """Streamed chunk write: create on first chunk, seal when the last
        byte lands (ref: ObjectBufferPool chunked writes). The caller is the
        single writer for the object."""
        name, _off = self.create(object_id, total)
        seg = self._segments[name]
        seg.buf[offset:offset + len(data)] = data
        if offset + len(data) >= total:
            self.seal(object_id)

    def stats(self) -> dict:
        with self._lock:
            return {
                "num_objects": len(self._objects),
                "used_bytes": self._used,
                "capacity_bytes": self.capacity,
                "num_evicted": self.num_evicted,
            }

    def shutdown(self):
        with self._lock:
            for oid in list(self._objects):
                self._delete_locked(oid)


class _MappedSegment:
    """Direct /dev/shm mmap attach. Unlike multiprocessing.SharedMemory this
    never touches the resource tracker (we don't own the segment — the node
    agent does) and tolerates still-exported buffer views at close (readers
    may hold zero-copy numpy arrays into the mapping; the OS reclaims at
    process exit — same lifetime model as plasma's client-side mappings,
    plasma/client.cc)."""

    def __init__(self, name: str):
        import mmap
        self.path = "/dev/shm/" + name.lstrip("/")
        self._f = open(self.path, "r+b")
        self.mm = mmap.mmap(self._f.fileno(), 0)
        self._f.close()
        # Populate this process's page table in the background: the agent's
        # pre-toucher materialized the pages, but OUR mapping still pays a
        # minor fault per 4 KiB on first touch (~1.6 GB/s inside a cold
        # copy vs ~3.2 with populated read PTEs). Reads only — this client
        # does not own the data.
        if len(self.mm) >= (64 << 20):
            threading.Thread(target=self._prefault, name="shm-prefault",
                             daemon=True).start()

    def _prefault(self):
        try:
            mv = memoryview(self.mm)
            # one C-level strided copy touches every page (bytes() of a
            # step-4096 view); chunked so the transient buffer stays small
            # and a racing close fails at a chunk boundary
            chunk = 256 << 20
            for start in range(0, len(mv), chunk):
                bytes(mv[start:start + chunk:4096])
        except (ValueError, IndexError, BufferError):
            pass  # mapping closed mid-walk: nothing to do

    def buf(self) -> memoryview:
        return memoryview(self.mm)

    def close(self):
        try:
            self.mm.close()
        except BufferError:
            pass  # zero-copy views still alive; leave mapping for process exit


class ShmClient:
    """Client-side zero-copy access to segments created by the agent-side store.
    Mirrors the reference's plasma client (plasma/client.cc) minus fd-passing:
    POSIX shm names stand in for the fds (fling.cc)."""

    def __init__(self):
        self._attached: dict[str, _MappedSegment] = {}
        self._lock = threading.Lock()

    def map(self, shm_name: str, size: int, offset: int = 0) -> memoryview:
        with self._lock:
            seg = self._attached.get(shm_name)
            if seg is None:
                seg = self._attached[shm_name] = _MappedSegment(shm_name)
        return seg.buf()[offset:offset + size]

    def write(self, shm_name: str, size: int, writer, offset: int = 0) -> None:
        """``writer(memoryview)`` fills the buffer."""
        mv = self.map(shm_name, size, offset)
        writer(mv)

    def release(self, shm_name: str):
        with self._lock:
            seg = self._attached.pop(shm_name, None)
        if seg is not None:
            seg.close()

    def close(self):
        with self._lock:
            segs, self._attached = list(self._attached.values()), {}
        for seg in segs:
            seg.close()


class NativeShmStore:
    """Agent-side store backed by the C++ arena allocator
    (ray_tpu/_native/shm_store.cc): ONE shm segment per node, objects are
    [offset, size) extents handed out by a best-fit free list, LRU eviction in
    native code. Clients mmap the arena once and read every object zero-copy
    at its offset — same client model as plasma's single memory-mapped pool
    (plasma/client.cc), with (arena_name, offset) standing in for fd-passing.

    Same interface as ShmStore; selected by config.use_native_object_store
    when the toolchain can build the library.
    """

    def __init__(self, capacity_bytes: int, prefix: str = "rtpu"):
        import ctypes
        import os

        from ray_tpu import _native

        lib = _native.load_library()
        if lib is None:
            raise RuntimeError(
                f"native store unavailable: {_native.build_error()!r}")
        self._ctypes = ctypes
        self._lib = lib
        self.capacity = capacity_bytes
        self.arena_name = f"{prefix}_arena_{os.getpid()}"
        self._handle = lib.rtpu_store_create(
            self.arena_name.encode(), ctypes.c_uint64(capacity_bytes))
        if not self._handle:
            raise RuntimeError("native store arena creation failed")
        self._base = lib.rtpu_store_base(ctypes.c_void_p(self._handle))
        self._lock = threading.Lock()
        # same-process writers (driver in head mode, in-proc workers) write
        # through THIS mapping instead of creating their own: the arena's
        # pages are materialized here by the C++ pre-toucher, while a fresh
        # per-client mmap pays a minor fault per 4 KiB (measured 1.6 vs
        # 5.6+ GB/s on the dev box)
        self._views_handed = False
        with _ARENA_LOCK:
            _LOCAL_ARENAS[self.arena_name] = self
        self._hints: dict[ObjectID, str] = {}
        # reused under self._lock: avoids a 64KB alloc+memset per put
        self._evicted_buf = ctypes.create_string_buffer(1 << 16)
        self.num_evicted = 0
        self.on_evict = None

    def _drain_evictions(self) -> list[ObjectID]:
        """Parse newline-separated hex ids out of the (truncation-safe)
        eviction buffer; must hold self._lock."""
        raw = self._evicted_buf.value
        if not raw:
            return []
        out = []
        for hexid in raw.decode().split("\n"):
            if not hexid:
                continue
            try:
                oid = ObjectID(bytes.fromhex(hexid))
            except ValueError:
                continue  # defensive: never fail a put on a bad notice
            self._hints.pop(oid, None)
            out.append(oid)
        return out

    def _notify_evicted(self, oids: list[ObjectID]) -> None:
        for oid in oids:
            self.num_evicted += 1
            if self.on_evict is not None:
                try:
                    self.on_evict(oid)
                except Exception:
                    pass

    def create(self, object_id: ObjectID, size: int,
               device_hint: str = "") -> tuple[str, int]:
        ct = self._ctypes
        offset = ct.c_uint64()
        with self._lock:
            self._evicted_buf[0] = b"\x00"
            rc = self._lib.rtpu_store_put(
                ct.c_void_p(self._handle), object_id.hex().encode(),
                ct.c_uint64(size), ct.byref(offset), self._evicted_buf,
                ct.c_uint64(len(self._evicted_buf)))
            if rc == 0 and device_hint:
                self._hints[object_id] = device_hint
            evicted = self._drain_evictions()
        self._notify_evicted(evicted)
        if rc == -2:
            raise ObjectStoreFullError(
                f"object of {size} bytes does not fit in native arena "
                f"({self.capacity} capacity)")
        return self.arena_name, offset.value

    def seal(self, object_id: ObjectID):
        rc = self._lib.rtpu_store_seal(
            self._ctypes.c_void_p(self._handle), object_id.hex().encode())
        if rc != 0:
            raise KeyError(f"seal of unknown object {object_id}")

    def _get(self, object_id: ObjectID):
        ct = self._ctypes
        offset, size, sealed = ct.c_uint64(), ct.c_uint64(), ct.c_int()
        rc = self._lib.rtpu_store_get(
            ct.c_void_p(self._handle), object_id.hex().encode(),
            ct.byref(offset), ct.byref(size), ct.byref(sealed))
        if rc != 0:
            return None
        return offset.value, size.value, bool(sealed.value)

    def get_meta(self, object_id: ObjectID) -> tuple | None:
        """copy_on_read=True: arena extents are REUSED after LRU eviction,
        so readers must not keep aliases into the mapping (plasma solves
        this with client-side pinning, plasma/client.cc; until that
        protocol exists here, readers copy out)."""
        got = self._get(object_id)
        if got is None or not got[2]:
            return None
        return (self.arena_name, got[0], got[1],
                self._hints.get(object_id, ""), True)

    def contains(self, object_id: ObjectID) -> bool:
        got = self._get(object_id)
        return got is not None and got[2]

    def pin(self, object_id: ObjectID, pinned: bool = True):
        self._lib.rtpu_store_pin(
            self._ctypes.c_void_p(self._handle), object_id.hex().encode(),
            1 if pinned else 0)

    def delete(self, object_id: ObjectID):
        self._hints.pop(object_id, None)
        self._lib.rtpu_store_delete(
            self._ctypes.c_void_p(self._handle), object_id.hex().encode())

    def read_bytes(self, object_id: ObjectID, offset: int = 0,
                   size: int | None = None) -> tuple[int, bytes] | None:
        meta = self.get_meta(object_id)
        if meta is None:
            return None
        _name, obj_off, total = meta[0], meta[1], meta[2]
        end = total if size is None else min(total, offset + size)
        n = max(0, end - offset)
        data = self._ctypes.string_at(self._base + obj_off + offset, n)
        return total, data

    def write_bytes(self, object_id: ObjectID, data: bytes):
        _name, obj_off = self.create(object_id, len(data))
        self._ctypes.memmove(self._base + obj_off, data, len(data))
        self.seal(object_id)

    def write_chunk(self, object_id: ObjectID, offset: int, data: bytes,
                    total: int):
        """Streamed chunk write into the arena (single writer per object)."""
        _name, obj_off = self.create(object_id, total)
        self._ctypes.memmove(self._base + obj_off + offset, data, len(data))
        if offset + len(data) >= total:
            self.seal(object_id)

    def stats(self) -> dict:
        ct = self._ctypes
        used, num_obj, evicted, cap = (ct.c_uint64(), ct.c_uint64(),
                                       ct.c_uint64(), ct.c_uint64())
        with self._lock:
            if not self._handle:  # shut down concurrently (agent stop)
                return {"num_objects": 0, "used_bytes": 0,
                        "capacity_bytes": 0, "num_evicted": 0,
                        "backend": "native"}
            self._lib.rtpu_store_stats(
                ct.c_void_p(self._handle), ct.byref(used), ct.byref(num_obj),
                ct.byref(evicted), ct.byref(cap))
        return {
            "num_objects": num_obj.value,
            "used_bytes": used.value,
            "capacity_bytes": cap.value,
            "num_evicted": evicted.value,
            "backend": "native",
        }

    def local_write_view(self, offset: int, size: int):
        """Writable memoryview over [offset, offset+size) of the in-process
        arena mapping, or None once shut down. Handing out a view switches
        the arena to leak-the-mapping-at-destroy (a racing shutdown must
        not munmap under a writer mid-memcpy; pages go back at process
        exit — the same lifetime model as _MappedSegment.close)."""
        with self._lock:
            if not self._handle:
                return None
            if not self._views_handed:
                self._views_handed = True
                self._lib.rtpu_store_leak_mapping(
                    self._ctypes.c_void_p(self._handle))
            buf = (self._ctypes.c_char * size).from_address(self._base + offset)
        return memoryview(buf).cast("B")

    def shutdown(self):
        with _ARENA_LOCK:
            if _LOCAL_ARENAS.get(self.arena_name) is self:
                del _LOCAL_ARENAS[self.arena_name]
        with self._lock:
            if self._handle:
                self._lib.rtpu_store_destroy(self._ctypes.c_void_p(self._handle))
                self._handle = None


class SpillingStore:
    """Disk-spilling wrapper over either shm backend.

    TPU-native analog of the reference's LocalObjectManager spilling
    (/root/reference/src/ray/raylet/local_object_manager.h:44,
    SpillObjects:114 + SpilledObjectReader): when a create would exceed the
    high-water mark, sealed objects are spilled to local disk in LRU order
    (pinned or not — spill preserves the value, so it never changes
    semantics; a get of a spilled object restores it transparently). The
    wrapper owns ALL reclamation: every object stays backend-pinned so the
    backend's lease-blind LRU eviction can never reuse an extent under a
    live reader (see pin()).
    """

    def __init__(self, backend, spill_dir: str, capacity_bytes: int,
                 headroom: float = 0.1):
        import os

        self._b = backend
        self._dir = spill_dir
        os.makedirs(spill_dir, exist_ok=True)
        self._capacity = capacity_bytes
        self._high_water = int(capacity_bytes * (1.0 - headroom))
        self._lock = threading.Lock()
        # our own LRU + seal view (backend internals differ); oid -> size
        self._lru: OrderedDict[ObjectID, int] = OrderedDict()
        self._sealed: set[ObjectID] = set()
        self._spilled: dict[ObjectID, int] = {}  # oid -> size on disk
        self._last_read: dict[ObjectID, float] = {}  # grace vs read races
        # READ LEASES: arena extents are reused after spill/delete, and
        # readers deserialize zero-copy over the mapping (arrow tables keep
        # aliasing it) — spilling an object mid-read segfaults the reader
        # in native code. get_meta takes a lease; the reader releases it
        # after deserializing; spill skips leased objects (expiry bounds a
        # crashed reader).
        self._read_leases: dict[ObjectID, int] = {}
        self._lease_expiry: dict[ObjectID, float] = {}
        self._pending_delete: set[ObjectID] = set()
        self.num_spilled = 0
        self.num_restored = 0

    # passthrough surface ------------------------------------------------
    @property
    def capacity(self):
        return self._capacity

    @property
    def on_evict(self):
        return self._b.on_evict

    @on_evict.setter
    def on_evict(self, fn):
        self._b.on_evict = fn

    def _spill_path(self, oid: ObjectID) -> str:
        import os
        return os.path.join(self._dir, oid.hex())

    def _maybe_spill(self, need: int) -> None:
        """Spill LRU sealed objects until `need` fits under the high-water
        mark. Lock held. Unlike eviction, spilling is safe for PINNED
        (live-ref) objects — that is its purpose (the reference spills
        primary copies under memory pressure, local_object_manager.h:44);
        a later get transparently restores. Unsealed (mid-write) objects
        are never touched."""
        used = self._b.stats()["used_bytes"]
        if used + need <= self._high_water:
            return
        now = time.monotonic()
        for oid in list(self._lru):
            if used + need <= self._high_water:
                break
            # grace window: a reader that just fetched this object's meta
            # may still be copying out of the mapping — don't pull the
            # extent out from under it (full safety needs client read
            # leases, plasma client.cc; this closes the practical window)
            if now - self._last_read.get(oid, 0.0) < 5.0:
                continue
            if self._spill_one(oid):
                used = self._b.stats()["used_bytes"]

    def _lease_active(self, oid: ObjectID) -> bool:
        """Lock held. Expired leases (crashed/lost readers — read_done is a
        best-effort notify) are swept here so they cannot leak pending
        deletes or embargo spilling forever."""
        if self._read_leases.get(oid, 0) <= 0:
            return False
        if time.monotonic() < self._lease_expiry.get(oid, 0.0):
            return True
        self._read_leases.pop(oid, None)
        self._lease_expiry.pop(oid, None)
        return False

    def _spill_one(self, oid: ObjectID) -> bool:
        """Spill one sealed object to disk. Lock held."""
        if oid not in self._sealed:
            return False
        if self._lease_active(oid):
            return False  # a reader still aliases this extent
        if oid in self._pending_delete:
            # condemned while a (now-gone) reader held it: free the memory
            # instead of wasting disk I/O on a dead object
            self._pending_delete.discard(oid)
            self._drop_locked(oid)
            return True
        out = self._b.read_bytes(oid)
        if out is None:
            self._lru.pop(oid, None)
            return False
        _total, data = out
        with open(self._spill_path(oid), "wb") as f:
            f.write(data)
        self._b.delete(oid)
        self._spilled[oid] = len(data)
        self._lru.pop(oid, None)
        self.num_spilled += 1
        return True

    def _restore(self, oid: ObjectID) -> bool:
        """Bring a spilled object back into shm. Lock held."""
        import os
        path = self._spill_path(oid)
        size = self._spilled.get(oid)
        if size is None or not os.path.exists(path):
            return False
        self._maybe_spill(size)
        with open(path, "rb") as f:
            data = f.read()
        self._alloc_with_forced_spill(
            lambda: self._b.write_bytes(oid, data), size, exclude=oid)
        # stays backend-pinned (see pin()): reclamation is wrapper-only
        self._lru[oid] = size
        self._sealed.add(oid)
        self._spilled.pop(oid, None)
        os.remove(path)
        self.num_restored += 1
        return True

    def _alloc_with_forced_spill(self, attempt, size: int, exclude=None):
        """Run an allocating backend op, force-spilling LRU objects one at
        a time on ObjectStoreFullError (grace-window skips or arena
        fragmentation must grind through disk, not fail the task). Lock
        held. Raises only when the op can never fit or nothing is left to
        spill."""
        while True:
            try:
                return attempt()
            except ObjectStoreFullError:
                if size > self._high_water:
                    raise  # spilling can never make this fit
                spilled = False
                for oid in list(self._lru):
                    if oid != exclude and self._spill_one(oid):
                        spilled = True
                        break
                if not spilled:
                    raise

    def _drop_locked(self, oid: ObjectID):
        """Forget an object entirely (lock held)."""
        import os
        self._lru.pop(oid, None)
        self._sealed.discard(oid)
        self._last_read.pop(oid, None)
        if self._spilled.pop(oid, None) is not None:
            try:
                os.remove(self._spill_path(oid))
            except OSError:
                pass
        self._b.delete(oid)

    # store interface ----------------------------------------------------
    def create(self, object_id: ObjectID, size: int, device_hint: str = ""):
        with self._lock:
            self._maybe_spill(size)
            name_off = self._alloc_with_forced_spill(
                lambda: self._b.create(object_id, size, device_hint), size)
            self._lru[object_id] = size
            return name_off

    def seal(self, object_id: ObjectID):
        self._b.seal(object_id)
        with self._lock:
            self._sealed.add(object_id)

    def get_meta(self, object_id: ObjectID):
        with self._lock:
            meta = self._b.get_meta(object_id)
            if meta is None and object_id in self._spilled:
                if self._restore(object_id):
                    meta = self._b.get_meta(object_id)
            if meta is not None:
                self._lru.move_to_end(object_id, last=True)
                self._last_read[object_id] = time.monotonic()
                # read lease: the caller will map/alias this extent; it
                # must not be spilled until read_done (expiry backstops a
                # crashed reader)
                self._read_leases[object_id] = \
                    self._read_leases.get(object_id, 0) + 1
                # expiry scales with size: copy-out + deserialize of a
                # GiB-scale object on a busy host can exceed a flat minute
                self._lease_expiry[object_id] = time.monotonic() + 60.0 + \
                    meta[2] / (16 * 1024 * 1024)
            return meta

    def read_done(self, object_id: ObjectID):
        """Reader finished deserializing: release one read lease (and apply
        a deletion that arrived mid-read)."""
        do_delete = False
        with self._lock:
            n = self._read_leases.get(object_id, 0)
            if n <= 1:
                self._read_leases.pop(object_id, None)
                self._lease_expiry.pop(object_id, None)
                do_delete = object_id in self._pending_delete
            else:
                self._read_leases[object_id] = n - 1
        if do_delete:
            self._pending_delete.discard(object_id)
            self.delete(object_id)

    def contains(self, object_id: ObjectID) -> bool:
        return self._b.contains(object_id) or object_id in self._spilled

    def pin(self, object_id: ObjectID, pinned: bool = True):
        """Deliberately INERT under spilling. The backend must never see
        unpinned objects: its internal LRU eviction reuses extents without
        consulting our read leases, which tore buffers under live remote
        reads (libarrow segfaults parsing the corrupt copy). With every
        object backend-pinned, ALL reclamation flows through this
        wrapper's spill/delete, which honor leases — and spilling pinned
        objects is safe by design, so pin state doesn't gate anything."""

    def delete(self, object_id: ObjectID):
        with self._lock:
            if self._lease_active(object_id):
                # a reader is mid-copy over the extent: freeing it now
                # would reuse the memory under the copy (torn buffer) —
                # defer to read_done / the expiry sweep in _spill_one
                self._pending_delete.add(object_id)
                return
            self._pending_delete.discard(object_id)
            self._drop_locked(object_id)

    def read_bytes(self, object_id: ObjectID, offset: int = 0,
                   size: int | None = None):
        out = self._b.read_bytes(object_id, offset, size)
        if out is not None:
            return out
        with self._lock:
            if object_id in self._spilled and self._restore(object_id):
                return self._b.read_bytes(object_id, offset, size)
        return None

    def write_bytes(self, object_id: ObjectID, data: bytes):
        with self._lock:
            self._maybe_spill(len(data))
            self._alloc_with_forced_spill(
                lambda: self._b.write_bytes(object_id, data), len(data))
            self._lru[object_id] = len(data)
            self._sealed.add(object_id)

    def write_chunk(self, object_id: ObjectID, offset: int, data: bytes,
                    total: int):
        if offset == 0:
            with self._lock:
                self._maybe_spill(total)
                # first chunk allocates the extent: grind through spill on
                # pressure like every other allocating path
                self._alloc_with_forced_spill(
                    lambda: self._b.write_chunk(object_id, offset, data,
                                                total), total)
        else:
            self._b.write_chunk(object_id, offset, data, total)
        with self._lock:
            self._lru[object_id] = total
            if offset + len(data) >= total:
                self._sealed.add(object_id)

    def stats(self) -> dict:
        out = self._b.stats()
        out["num_spilled"] = self.num_spilled
        out["num_restored"] = self.num_restored
        out["spilled_bytes"] = sum(self._spilled.values())
        return out

    def shutdown(self):
        import shutil as _sh
        self._b.shutdown()
        _sh.rmtree(self._dir, ignore_errors=True)


def make_store(capacity_bytes: int, prefix: str = "rtpu"):
    """Pick the store backend per config.use_native_object_store (falling
    back to the pure-python per-object-segment store when the native library
    cannot be built), wrapped with disk spilling when enabled."""
    import os

    from ray_tpu.core.config import get_config

    cfg = get_config()
    backend = None
    if cfg.use_native_object_store:
        try:
            backend = NativeShmStore(capacity_bytes, prefix)
        except Exception as e:
            import logging
            logging.getLogger(__name__).warning(
                "native object store unavailable (%s); falling back to the "
                "pure-python store", e)
    if backend is None:
        backend = ShmStore(capacity_bytes, prefix)
    store = backend
    if cfg.enable_object_spilling:
        spill_dir = os.path.join(cfg.spill_dir or "/tmp/ray_tpu_spill",
                                 prefix)
        store = SpillingStore(backend, spill_dir, capacity_bytes)
    # which implementation this node ended up with ("NativeShmStore" or the
    # python "ShmStore"), so a run can report the switch instead of only
    # logging it
    store.backend_name = type(backend).__name__
    return store
