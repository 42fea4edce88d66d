"""ctypes binding for the native shared-memory store.

Builds ``shm_store.cpp`` with g++ on first use; the library is named by
a hash of the source, so a copied or checked-out tree (where mtimes
mean nothing) never loads a library built from other source.  Reads are
zero-copy: Python mmaps the same shm segment and returns memoryview
slices at the (offset, size) handles the C++ side hands out — the same
client model as plasma's mmap'd object views
(src/ray/object_manager/plasma/client.cc).
"""

from __future__ import annotations

import ctypes
import hashlib
import mmap
import os
import subprocess
import threading
import uuid
from typing import Optional

_BUILD_LOCK = threading.Lock()
_SRC = os.path.join(os.path.dirname(__file__), "shm_store.cpp")
_BUILD_DIR = os.path.join(os.path.dirname(__file__), "_build")

# tmpfs pages are first-touch, so `df /dev/shm` does not reflect open
# (sparse) segments — a sizing decision based on free space alone
# over-commits, and filling over-committed segments later dies with
# SIGBUS, not a catchable error.  Track this process's outstanding
# segment capacity so sizing (raylet._maybe_native_store) can subtract
# its own reservations.
_RESERVED_LOCK = threading.Lock()
_RESERVED_BYTES = 0

#: ``try_create`` status codes — the retriable-OOM create surface
#: (plasma ``PlasmaError``: OK / ObjectExists / OutOfMemory).  OOM is a
#: CODE, not an exception: the caller's create-request queue retries it
#: as seals/evictions/spills free space instead of unwinding.
CREATE_OK = 0
CREATE_DUPLICATE = 1     # key already present (sealed or mid-write)
CREATE_PENDING = 2       # deleted-pending: freed on last client unpin
CREATE_OOM = 3           # retriable: no block fits right now


def reserved_bytes() -> int:
    """Total capacity of segments currently open in THIS process."""
    with _RESERVED_LOCK:
        return _RESERVED_BYTES


def _reserve(delta: int) -> None:
    global _RESERVED_BYTES
    with _RESERVED_LOCK:
        _RESERVED_BYTES += delta


def _build() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    so = os.path.join(_BUILD_DIR, f"libshm_store-{digest}.so")
    with _BUILD_LOCK:
        if os.path.exists(so):
            return so
        os.makedirs(_BUILD_DIR, exist_ok=True)
        # Node-host children may build at the same moment: each links
        # to its own temporary name and renames into place atomically.
        tmp = f"{so}.{os.getpid()}.tmp"
        cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", _SRC,
               "-o", tmp, "-lrt"]
        try:
            subprocess.run(cmd, check=True, capture_output=True)
            os.replace(tmp, so)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        return so


def _load() -> ctypes.CDLL:
    lib = ctypes.CDLL(_build())
    lib.store_open.restype = ctypes.c_void_p
    lib.store_open.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
    lib.store_close.argtypes = [ctypes.c_void_p]
    lib.store_put.restype = ctypes.c_int64
    lib.store_put.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                              ctypes.c_uint32, ctypes.c_char_p,
                              ctypes.c_uint64]
    lib.store_get.restype = ctypes.c_int
    lib.store_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                              ctypes.c_uint32,
                              ctypes.POINTER(ctypes.c_uint64),
                              ctypes.POINTER(ctypes.c_uint64)]
    lib.store_delete.restype = ctypes.c_int
    lib.store_delete.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                 ctypes.c_uint32]
    lib.store_used.restype = ctypes.c_uint64
    lib.store_used.argtypes = [ctypes.c_void_p]
    lib.store_capacity.restype = ctypes.c_uint64
    lib.store_capacity.argtypes = [ctypes.c_void_p]
    lib.store_num_objects.restype = ctypes.c_uint64
    lib.store_num_objects.argtypes = [ctypes.c_void_p]
    lib.store_create.restype = ctypes.c_int64
    lib.store_create.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                 ctypes.c_uint32, ctypes.c_uint64]
    lib.store_seal.restype = ctypes.c_int
    lib.store_seal.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                               ctypes.c_uint32]
    lib.store_pin.restype = ctypes.c_int
    lib.store_pin.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                              ctypes.c_uint32]
    lib.store_unpin.restype = ctypes.c_int
    lib.store_unpin.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                ctypes.c_uint32]
    lib.store_choose_victims.restype = ctypes.c_int
    lib.store_choose_victims.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_char_p,
        ctypes.c_uint32, ctypes.POINTER(ctypes.c_uint64)]
    lib.store_largest_free.restype = ctypes.c_uint64
    lib.store_largest_free.argtypes = [ctypes.c_void_p]
    return lib


class NativeShmStore:
    """One shm segment + object table; zero-copy mmap reads."""

    def __init__(self, capacity: int = 256 * 1024 * 1024,
                 name: Optional[str] = None):
        self._lib = _load()
        self._name = name or f"/raytpu-{uuid.uuid4().hex[:12]}"
        self._handle = self._lib.store_open(self._name.encode(), capacity)
        if not self._handle:
            raise OSError("native shm store open failed")
        # Map the same segment for zero-copy reads.
        fd = os.open(f"/dev/shm{self._name}", os.O_RDWR)
        try:
            self._mm = mmap.mmap(fd, capacity)
        finally:
            os.close(fd)
        self.capacity = capacity
        self._closed = False
        _reserve(capacity)

    def put(self, key: bytes, data: bytes) -> None:
        rc = self._lib.store_put(self._handle, key, len(key), data,
                                 len(data))
        if rc == -1:
            raise MemoryError("native store full")
        if rc == -3:
            # Deleted-pending: a client still holds the old bytes
            # pinned; the key is unusable until the last release.
            raise KeyError("object key awaiting deferred free")
        if rc == -2:
            return  # idempotent re-put

    def get(self, key: bytes) -> Optional[memoryview]:
        off = ctypes.c_uint64()
        size = ctypes.c_uint64()
        rc = self._lib.store_get(self._handle, key, len(key),
                                 ctypes.byref(off), ctypes.byref(size))
        if rc != 0:
            return None
        return memoryview(self._mm)[off.value:off.value + size.value]

    def delete(self, key: bytes) -> bool:
        return self._lib.store_delete(self._handle, key, len(key)) == 0

    def view(self, offset: int, size: int) -> memoryview:
        """Writable view over a reserved block — the create/seal write
        surface for the owning process (clients use AttachedSegment)."""
        return memoryview(self._mm)[offset:offset + size]

    # ---- plasma create/seal lifecycle (client writes through shm) -----
    def try_create(self, key: bytes, size: int):
        """Reserve ``size`` bytes without throwing: returns
        ``(status, offset)`` where status is one of the ``CREATE_*``
        codes and offset is valid only for ``CREATE_OK``.  ``CREATE_OOM``
        is RETRIABLE — the caller's create-request queue evicts/spills
        and retries rather than failing the put
        (create_request_queue.h semantics)."""
        off = self._lib.store_create(self._handle, key, len(key), size)
        if off >= 0:
            return CREATE_OK, int(off)
        if off == -1:
            return CREATE_OOM, -1
        if off == -3:
            return CREATE_PENDING, -1
        return CREATE_DUPLICATE, -1

    def create(self, key: bytes, size: int) -> Optional[int]:
        """Legacy throwing wrapper over :meth:`try_create` (kept for
        direct store users/tests): returns the offset, None on
        duplicate/deleted-pending, raises MemoryError on OOM."""
        status, off = self.try_create(key, size)
        if status == CREATE_OOM:
            raise MemoryError("native store full")
        return off if status == CREATE_OK else None

    def seal(self, key: bytes) -> bool:
        return self._lib.store_seal(self._handle, key, len(key)) == 0

    def locate(self, key: bytes) -> Optional[tuple]:
        """(offset, size) of a sealed object, touching its LRU slot."""
        off = ctypes.c_uint64()
        size = ctypes.c_uint64()
        rc = self._lib.store_get(self._handle, key, len(key),
                                 ctypes.byref(off), ctypes.byref(size))
        return None if rc != 0 else (off.value, size.value)

    def pin(self, key: bytes) -> bool:
        return self._lib.store_pin(self._handle, key, len(key)) == 0

    def unpin(self, key: bytes) -> bool:
        return self._lib.store_unpin(self._handle, key, len(key)) == 0

    def choose_victims(self, needed: int):
        """Best-effort LRU victims toward freeing >= needed bytes;
        empty when nothing is evictable."""
        cap = 1 << 20
        buf = ctypes.create_string_buffer(cap)
        covered = ctypes.c_uint64()
        n = self._lib.store_choose_victims(
            self._handle, needed, buf, cap, ctypes.byref(covered))
        if n < 0:
            return []
        keys, pos = [], 0
        raw = buf.raw
        for _ in range(n):
            ln = int.from_bytes(raw[pos:pos + 4], "little")
            keys.append(raw[pos + 4:pos + 4 + ln])
            pos += 4 + ln
        return keys

    @property
    def name(self) -> str:
        return self._name

    def used_bytes(self) -> int:
        return self._lib.store_used(self._handle)

    def largest_free_block(self) -> int:
        """Largest contiguous hole the allocator could hand out right
        now (coalesces the bins first) — OOM diagnostics: total free
        can exceed a request while no single hole fits it."""
        return self._lib.store_largest_free(self._handle)

    def num_objects(self) -> int:
        return self._lib.store_num_objects(self._handle)

    def close(self):
        if not self._closed:
            self._closed = True
            _reserve(-self.capacity)
            try:
                self._mm.close()
            except BufferError:
                pass  # exported memoryviews still alive
            self._lib.store_close(self._handle)

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def open_store(capacity: int = 256 * 1024 * 1024) -> NativeShmStore:
    return NativeShmStore(capacity=capacity)


class AttachedSegment:
    """Client-side mapping of a store segment owned by another process
    (plasma client model, ``plasma/client.cc``): metadata — offsets,
    pins, create/seal — travels over the worker's RPC channel to the
    node; the BYTES are read and written directly through mmaps,
    zero-copy.

    Two mappings: reads go through a READ-ONLY map, so deserialized
    arrays are read-only views (plasma maps client reads read-only for
    the same reason — an in-place ``a += 1`` on a task arg must raise,
    not silently corrupt the shared object); create/seal writes go
    through a separate read-write map."""

    def __init__(self, name: str, capacity: int):
        self.name = name
        self.capacity = capacity
        fd = os.open(f"/dev/shm{name}", os.O_RDWR)
        try:
            self._ro = mmap.mmap(fd, capacity, prot=mmap.PROT_READ)
            self._rw = mmap.mmap(fd, capacity)
        finally:
            os.close(fd)

    def read(self, offset: int, size: int) -> memoryview:
        return memoryview(self._ro)[offset:offset + size]

    def write(self, offset: int, data) -> None:
        self._rw[offset:offset + len(data)] = data

    def view(self, offset: int, size: int) -> memoryview:
        """Writable view over a create-reservation: the worker's
        single-copy return path serializes straight into this."""
        return memoryview(self._rw)[offset:offset + size]

    def close(self):
        for mm in (self._ro, self._rw):
            try:
                mm.close()
            except (BufferError, ValueError):
                pass
