"""Native C++ shm store tests (plasma-equivalent,
reference: src/ray/object_manager/plasma/test/)."""

import numpy as np
import pytest

from ray_tpu.native.shm_store import NativeShmStore


def test_library_is_named_by_its_source(tmp_path, monkeypatch):
    """A tree that was copied or checked out has meaningless mtimes:
    the built library carries a hash of the source, is built on first
    use, and a changed source never loads the old one."""
    import os

    from ray_tpu.native import shm_store
    src = tmp_path / "shm_store.cpp"
    with open(shm_store._SRC, "rb") as f:
        src.write_bytes(f.read())
    monkeypatch.setattr(shm_store, "_SRC", str(src))
    monkeypatch.setattr(shm_store, "_BUILD_DIR", str(tmp_path / "_build"))
    first = shm_store._build()
    assert os.path.exists(first) and shm_store._build() == first
    os.utime(first, (0, 0))                      # older than the source
    assert shm_store._build() == first
    src.write_bytes(src.read_bytes() + b"\n// changed\n")
    second = shm_store._build()
    assert second != first and os.path.exists(second)


@pytest.fixture
def store():
    s = NativeShmStore(capacity=16 * 1024 * 1024)
    yield s
    s.close()


def test_put_get_roundtrip(store):
    store.put(b"k", b"payload")
    assert bytes(store.get(b"k")) == b"payload"


def test_get_missing(store):
    assert store.get(b"nope") is None


def test_zero_copy_view(store):
    data = np.arange(1000, dtype=np.int64).tobytes()
    store.put(b"arr", data)
    view = store.get(b"arr")
    arr = np.frombuffer(view, dtype=np.int64)
    assert arr[999] == 999
    del view, arr


def test_delete_and_reuse(store):
    store.put(b"a", b"x" * 1024)
    used = store.used_bytes()
    assert store.delete(b"a")
    assert store.used_bytes() < used
    assert store.get(b"a") is None
    store.put(b"b", b"y" * 1024)  # reuses freed space
    assert bytes(store.get(b"b")) == b"y" * 1024


def test_allocator_coalescing(store):
    keys = [f"k{i}".encode() for i in range(64)]
    for k in keys:
        store.put(k, b"z" * 100_000)
    for k in keys[::2]:
        store.delete(k)
    # A larger object must fit into coalesced adjacent free blocks.
    store.put(b"big", b"B" * 150_000)
    assert bytes(store.get(b"big"))[:1] == b"B"


def test_capacity_exhaustion(store):
    with pytest.raises(MemoryError):
        store.put(b"huge", b"h" * (32 * 1024 * 1024))


def test_idempotent_put(store):
    store.put(b"k", b"v1")
    store.put(b"k", b"v2")  # no-op, no error
    assert bytes(store.get(b"k")) == b"v1"


class TestShmAbort:
    """The host's ``shm_abort`` handler must reclaim ONLY unsealed
    create-reservations: a worker fires abort on any mid-write failure,
    including a timed-out seal reply that actually landed — deleting
    the now-sealed (registered, locatable) object would corrupt it for
    every other reader."""

    def _host_stub(self, native):
        import threading
        from types import SimpleNamespace

        from ray_tpu._private.worker_pool import WorkerHostService
        stub = SimpleNamespace(
            _node=SimpleNamespace(
                object_store=SimpleNamespace(_native=native)),
            _shm_seal_lock=threading.Lock())
        stub._native_store = \
            WorkerHostService._native_store.__get__(stub)
        return stub

    def test_abort_reclaims_unsealed_reservation(self, store):
        from ray_tpu._private.worker_pool import WorkerHostService
        stub = self._host_stub(store)
        off = store.create(b"pending", 4096)
        assert off is not None
        used = store.used_bytes()
        assert WorkerHostService._shm_abort(stub,
                                            {"object_id": b"pending"})
        assert store.used_bytes() < used
        # The key is reusable again (the reservation really went away).
        assert store.create(b"pending", 4096) is not None

    def test_abort_spares_sealed_object(self, store):
        from ray_tpu._private.worker_pool import WorkerHostService
        stub = self._host_stub(store)
        off = store.create(b"sealed", 8)
        store._mm[off:off + 8] = b"payload!"
        assert store.seal(b"sealed")
        # Late abort (e.g. the worker timed out on the seal reply that
        # actually landed): must be refused, bytes must survive.
        assert WorkerHostService._shm_abort(
            stub, {"object_id": b"sealed"}) is False
        assert bytes(store.get(b"sealed")) == b"payload!"

    def test_abort_missing_key_is_noop(self, store):
        from ray_tpu._private.worker_pool import WorkerHostService
        stub = self._host_stub(store)
        used = store.used_bytes()
        WorkerHostService._shm_abort(stub, {"object_id": b"ghost"})
        assert store.used_bytes() == used


def test_integration_with_node_store(ray_start_regular):
    """Large puts flow through the native backend when available."""
    import ray_tpu
    from ray_tpu._private import worker as worker_mod
    x = np.random.rand(512, 512)  # 2MB > inline threshold
    ref = ray_tpu.put(x)
    got = ray_tpu.get(ref)
    np.testing.assert_array_equal(x, got)
    head = worker_mod.global_worker().cluster.head_node
    assert head.object_store.num_objects() >= 1


class TestNativeEviction:
    """LRU victim selection, pin protection, deferred delete
    (eviction_policy.h / create_request_queue.h parity)."""

    def test_choose_victims_lru_order(self, store):
        store.put(b"a", b"x" * 1024)
        store.put(b"b", b"y" * 1024)
        store.put(b"c", b"z" * 1024)
        store.locate(b"a")           # touch a -> b is now least recent
        victims = store.choose_victims(512)
        assert victims == [b"b"]

    def test_pinned_objects_never_victims(self, store):
        store.put(b"a", b"x" * 1024)
        store.put(b"b", b"y" * 1024)
        store.pin(b"a")
        victims = store.choose_victims(512)
        assert victims == [b"b"]
        # Everything pinned -> cannot cover -> None.
        store.pin(b"b")
        assert not store.choose_victims(512)
        store.unpin(b"a")
        assert store.choose_victims(512) == [b"a"]

    def test_deferred_delete_while_pinned(self, store):
        store.put(b"a", b"q" * 256)
        off, size = store.locate(b"a")
        store.pin(b"a")
        assert store.delete(b"a")
        # Hidden from lookups but the bytes stay valid for the reader.
        assert store.locate(b"a") is None
        view = memoryview(store._mm)[off:off + size]
        assert bytes(view) == b"q" * 256
        del view
        used_before = store.used_bytes()
        store.unpin(b"a")            # last unpin frees
        assert store.used_bytes() < used_before

    def test_node_store_evicts_to_native_oom(self, tmp_path):
        """Python store + native OOM: LRU victims are spilled through
        the Python IO path and the put retries (retriable-OOM create
        queue); evicted objects restore from disk on demand."""
        from ray_tpu._private.ids import ObjectID
        from ray_tpu._private.object_store import NodeObjectStore
        from ray_tpu._private.serialization import serialize

        native = NativeShmStore(capacity=4 * 1024 * 1024)
        store = NodeObjectStore(
            node_id=ObjectID.from_random(), capacity_bytes=64 * 1024 * 1024,
            spill_dir=str(tmp_path), native_backend=native)
        try:
            oids = [ObjectID.from_random() for _ in range(4)]
            blobs = [np.full(300_000, i, dtype=np.uint8) for i in range(4)]
            for oid, arr in zip(oids, blobs):
                store.put(oid, serialize(arr), pin=False)
            from ray_tpu._private.object_store import _NativeHandle
            assert all(isinstance(store.get(o).data, _NativeHandle)
                       for o in oids)
            # A 3MB put cannot fit beside 4x300KB in 4MB: LRU victims
            # get spilled, the put lands natively.
            big = ObjectID.from_random()
            store.put(big, serialize(np.zeros(3_000_000, np.uint8)),
                      pin=False)
            assert isinstance(store.get(big).data, _NativeHandle)
            assert store.stats["evicted_objects"] > 0
            assert store.stats["spilled_objects"] > 0
            # Evicted entries restore transparently.
            from ray_tpu._private.object_store import entry_value
            for oid, arr in zip(oids, blobs):
                np.testing.assert_array_equal(entry_value(store.get(oid)),
                                              arr)
        finally:
            native.close()

    def test_fallback_to_python_buffers_when_segment_too_small(
            self, tmp_path):
        """An object larger than the whole segment falls back to
        python-held buffers (plasma fallback allocation) instead of
        failing the put."""
        from ray_tpu._private.ids import ObjectID
        from ray_tpu._private.object_store import (NodeObjectStore,
                                                   _NativeHandle)
        from ray_tpu._private.serialization import (SerializedObject,
                                                    serialize)

        native = NativeShmStore(capacity=1 * 1024 * 1024)
        store = NodeObjectStore(
            node_id=ObjectID.from_random(), capacity_bytes=64 * 1024 * 1024,
            spill_dir=str(tmp_path), native_backend=native)
        try:
            oid = ObjectID.from_random()
            store.put(oid, serialize(np.zeros(2_000_000, np.uint8)),
                      pin=False)
            e = store.get(oid)
            assert not isinstance(e.data, _NativeHandle)
            assert isinstance(e.data, SerializedObject)
        finally:
            native.close()


class TestCrossProcessZeroCopy:
    """Process-mode workers mmap the node's segment: args are read and
    big returns written through shm, never the socket
    (plasma/client.cc model)."""

    def test_worker_reads_arg_through_shm(self):
        import ray_tpu
        ray_tpu.init(num_cpus=2, _system_config={
            "worker_process_mode": "process",
            "scheduler_backend": "native",
        })
        try:
            from ray_tpu._private.worker import global_worker
            node = global_worker().cluster.head_node
            assert node.object_store._native is not None, \
                "native store must be active for this test"
            host = node.worker_pool.host_service()

            arr = np.arange(500_000, dtype=np.float64)   # 4MB > inline max
            ref = ray_tpu.put(arr)

            @ray_tpu.remote
            def total(a):
                return float(a.sum()), bool(a.flags["OWNDATA"])

            s, owndata = ray_tpu.get(total.remote(ref), timeout=120)
            assert s == float(arr.sum())
            assert not owndata, "arg should be a view, not a copy"
            assert host.shm_locate_count > 0, \
                "worker never read through the shm surface"
            # Task-scoped pins are released with the task (async).
            import time as time_mod
            deadline = time_mod.monotonic() + 5.0
            while any(host._shm_pins.values()) and \
                    time_mod.monotonic() < deadline:
                time_mod.sleep(0.05)
            assert not any(host._shm_pins.values())
        finally:
            ray_tpu.shutdown()

    def test_big_return_written_through_shm(self):
        import ray_tpu
        ray_tpu.init(num_cpus=2, _system_config={
            "worker_process_mode": "process",
            "scheduler_backend": "native",
        })
        try:
            from ray_tpu._private.object_store import _NativeHandle
            from ray_tpu._private.worker import global_worker
            node = global_worker().cluster.head_node
            assert node.object_store._native is not None

            @ray_tpu.remote
            def make():
                return np.ones(500_000, dtype=np.float64)

            ref = make.remote()
            out = ray_tpu.get(ref, timeout=120)
            assert out.shape == (500_000,)
            e = node.object_store.get(ref.object_id())
            assert e is not None and isinstance(e.data, _NativeHandle), \
                "return should have been sealed into the native segment"
        finally:
            ray_tpu.shutdown()


class TestSanitizers:
    """Native-store sanitizer story (SURVEY §5.2: the reference runs
    plasma under TSAN/ASAN bazel configs + valgrind).  The concurrency
    test binary is compiled and executed under ASan+UBSan and TSan;
    any data race on the object table / allocator / LRU clock or heap
    error in the eviction path fails the run."""

    @pytest.mark.parametrize("flags,tag", [
        ("-fsanitize=address,undefined", "asan"),
        ("-fsanitize=thread", "tsan"),
        # Spill-callback variant (graftcheck PR): evictors copy victim
        # payloads out through their own mapping while pinned — the
        # exact read the Python LocalObjectManager performs — so TSan
        # sweeps payload reads racing allocator reuse on the OOM/evict
        # path, not just the metadata tables.
        ("-fsanitize=thread -DGRAFT_SPILL_CALLBACKS", "tsan-spill"),
    ])
    def test_concurrent_store_under_sanitizer(self, flags, tag,
                                              tmp_path):
        import os
        import subprocess
        src_dir = os.path.join(os.path.dirname(__file__), "..",
                               "ray_tpu", "native")
        binary = tmp_path / f"shm_store_test_{tag}"
        build = subprocess.run(
            ["g++", "-O1", "-g", "-std=c++17", *flags.split(),
             os.path.join(src_dir, "shm_store.cpp"),
             os.path.join(src_dir, "shm_store_test.cpp"),
             "-o", str(binary), "-lrt", "-pthread"],
            capture_output=True, text=True, timeout=300)
        assert build.returncode == 0, build.stderr
        run = subprocess.run([str(binary)], capture_output=True,
                             text=True, timeout=300)
        assert run.returncode == 0, \
            f"{tag} run failed:\n{run.stderr[-3000:]}"
        assert "failures=0" in run.stderr
