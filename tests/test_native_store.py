"""Native C++ shared-arena object store (native/store/store.cc; plays the
reference's plasma store + dlmalloc arena role,
src/ray/object_manager/plasma/store.h:53)."""

import numpy as np
import pytest

from ray_tpu._private.ids import ObjectID
from ray_tpu.native.store import NativeObjectStore, native_store_available

pytestmark = pytest.mark.skipif(not native_store_available(),
                                reason="no C++ toolchain")


@pytest.fixture
def store(tmp_path):
    s = NativeObjectStore(str(tmp_path / "arena"), capacity=32 << 20,
                          max_objects=4096)
    yield s
    s.close()


def test_create_seal_get_roundtrip(store):
    oid = ObjectID.from_random()
    data = np.arange(4096, dtype=np.float32)
    buf = store.create(oid, data.nbytes)
    buf.view[:] = memoryview(data).cast("B")
    buf.close()
    assert not store.contains(oid)  # unsealed objects are invisible
    store.seal(oid)
    assert store.contains(oid)
    out = store.get(oid)
    back = np.frombuffer(out.view, dtype=np.float32)
    np.testing.assert_array_equal(back, data)
    out.close()


def test_delete_frees_and_coalesces(store):
    ids = [ObjectID.from_random() for _ in range(64)]
    for oid in ids:
        store.put_bytes(oid, b"y" * 100_000)
    used_full = store.stats()["used"]
    for oid in ids:
        assert store.delete(oid) > 0
    assert store.stats()["used"] == 0
    assert store.stats()["num_objects"] == 0
    # after full free, one allocation of (almost) everything must succeed
    big = ObjectID.from_random()
    store.put_bytes(big, b"z" * (used_full // 2))
    assert store.contains(big)


def test_out_of_space_raises(store):
    with pytest.raises(MemoryError):
        store.put_bytes(ObjectID.from_random(), b"x" * (1 << 30))


def test_reput_overwrites_like_files_backend(store):
    """Re-putting an existing object replaces it (files-backend parity:
    lineage reconstruction re-produces return objects)."""
    oid = ObjectID.from_random()
    store.put_bytes(oid, b"first-version")
    store.put_bytes(oid, b"second")
    out = store.get(oid)
    assert bytes(out.view) == b"second"
    out.close()
    assert store.stats()["num_objects"] == 1


def test_runtime_end_to_end_with_native_backend():
    """The whole task/object plane on the native store: driver, raylet and
    workers all share one arena per node."""
    import ray_tpu

    ray_tpu.init(num_cpus=2,
                 _system_config={"object_store_backend": "native"})
    try:
        @ray_tpu.remote
        def produce():
            return np.full((512, 256), 7, dtype=np.int32)

        @ray_tpu.remote
        def consume(arr):
            return int(arr.sum())

        ref = produce.remote()
        assert ray_tpu.get(consume.remote(ref),
                           timeout=60) == 512 * 256 * 7
        big = ray_tpu.put(np.ones(3_000_000, dtype=np.uint8))
        assert int(ray_tpu.get(big).sum()) == 3_000_000
    finally:
        ray_tpu.shutdown()


def test_shutdown_unlinks_the_sessions_arena():
    """`shutdown()` leaves nothing of the session in /dev/shm: the arena
    file (2 GiB by default, resident as far as the run touched it) goes
    with the raylet that served it. What the driver still holds of it —
    an array it got zero-copy — stays readable until dropped."""
    import os

    import ray_tpu
    from ray_tpu._private.object_store import default_store_root

    session = ray_tpu.init(num_cpus=1)["session_dir"]
    shm = os.path.dirname(default_store_root(session))
    try:
        kept = ray_tpu.get(ray_tpu.put(np.arange(1 << 20, dtype=np.int32)))
        arenas = [os.path.join(d, f) for d, _, fs in os.walk(shm)
                  for f in fs if f == "arena.rts"]
        assert len(arenas) == 1 and os.path.getsize(arenas[0]) > 1 << 30
    finally:
        ray_tpu.shutdown()
    assert not os.path.exists(shm)
    assert int(kept[-1]) == (1 << 20) - 1     # the mapping outlives the file


def test_pinned_read_survives_delete(store):
    """Reader pins: deleting (or overwriting) an object under a live
    zero-copy view must not corrupt the view; the block frees only when
    the last view dies (plasma Get/Release parity)."""
    oid = ObjectID.from_random()
    payload = bytes(range(256)) * 40
    store.put_bytes(oid, payload)
    buf = store.get(oid)
    view = bytes(buf.view[:16])  # touch before delete
    assert view == payload[:16]

    # delete while pinned: lookups must miss immediately...
    assert store.delete(oid) > 0
    assert store.get(oid) is None
    # ...but the pinned view still reads the ORIGINAL bytes, even after
    # allocation churn that would reuse a freed block
    for _ in range(20):
        churn = ObjectID.from_random()
        store.put_bytes(churn, b"\xff" * len(payload))
        store.delete(churn)
    assert bytes(buf.view[: len(payload)]) == payload
    buf.close()  # last view dies -> block actually frees

    # the freed block is reusable afterwards
    before = store.stats()["used"]
    oid3 = ObjectID.from_random()
    store.put_bytes(oid3, b"y" * len(payload))
    assert store.stats()["used"] <= before + len(payload) + 128


def test_overwrite_while_pinned_keeps_generations_apart(store):
    """Overwrite of a pinned object creates a NEW block; releases must
    target their own generation (regression: an id-keyed release freed
    the old generation out from under its reader)."""
    oid = ObjectID.from_random()
    store.put_bytes(oid, b"a" * 4096)
    old = store.get(oid)  # pin generation 1

    store.put_bytes(oid, b"b" * 4096)  # overwrite: gen-1 zombies
    new = store.get(oid)  # pin generation 2
    assert bytes(new.view[:4]) == b"bbbb"

    # releasing the NEW generation must not free the OLD block
    new.close()
    for _ in range(10):
        churn = ObjectID.from_random()
        store.put_bytes(churn, b"\xee" * 4096)
        store.delete(churn)
    assert bytes(old.view[:4]) == b"aaaa", \
        "old generation corrupted by new generation's release"
    old.close()

    # both generations now released; current value still readable
    cur = store.get(oid)
    assert bytes(cur.view[:4]) == b"bbbb"
    cur.close()


def test_arena_spill_overfill_and_recover():
    """Overfill the arena: the raylet spills residents to disk (delete
    zombifies under live pins, so readers are safe), the driver's put
    retries through a synchronous spill_now when the async pass loses the
    race, and EVERY object reads back intact afterwards (reference:
    local_object_manager.h:96-112 spill/restore)."""
    import ray_tpu

    ray_tpu.init(num_cpus=1, _system_config={
        "object_store_backend": "native",
        "object_store_memory": 8 << 20,     # 8MB arena
        "object_spilling_threshold": 0.5,
    })
    try:
        # 10 x 2MB = 20MB logical through an 8MB arena
        refs = [ray_tpu.put(np.full(2_000_000, i, dtype=np.uint8))
                for i in range(10)]
        for i, ref in enumerate(refs):
            arr = ray_tpu.get(ref, timeout=60)
            assert arr.shape == (2_000_000,)
            assert int(arr[0]) == i and int(arr[-1]) == i
        # and again in reverse (restores evict others back out)
        for i, ref in reversed(list(enumerate(refs))):
            assert int(ray_tpu.get(ref, timeout=60)[1000]) == i
    finally:
        ray_tpu.shutdown()


# ---------------------------------------------------------------------
# put_serialized: what goes in comes out, through both backends
# ---------------------------------------------------------------------

@pytest.fixture(params=["native", "files"])
def either_store(request, tmp_path):
    from ray_tpu._private.object_store import LocalObjectStore

    if request.param == "files":
        yield LocalObjectStore(str(tmp_path / "files"))
        return
    s = NativeObjectStore(str(tmp_path / "arena"), capacity=32 << 20)
    yield s
    s.close()


def _buffers(kind):
    rng = np.random.default_rng(11)
    if kind == "1KiB":
        return [memoryview(rng.bytes(1 << 10))]
    if kind == "1MiB":
        return [memoryview(rng.bytes(1 << 20))]
    if kind == "9MiB":      # large buffers of an odd size side by side
        return [memoryview(rng.bytes((9 << 20) + 3)),
                memoryview(rng.bytes(1 << 20))]
    if kind == "many":      # a snapshot's reply: hundreds of small leaves
        return [memoryview(rng.bytes(int(n)))
                for n in rng.integers(1, 5000, size=1500)]
    # shapes, formats, an empty and a read-only buffer side by side
    a = rng.standard_normal((3, 4)).astype(np.float32)
    return [memoryview(a), memoryview(b""), memoryview(rng.bytes(7)),
            memoryview(np.arange(5, dtype=np.int16)),
            memoryview(a.T.copy()).toreadonly()]


@pytest.mark.parametrize("kind", ["1KiB", "1MiB", "9MiB", "many", "odd"])
def test_put_serialized_reads_back_bit_equal(either_store, kind):
    oid = ObjectID.from_random()
    header, buffers = b"\x01header\x00", _buffers(kind)
    want = header + b"".join(bytes(b) for b in buffers)
    assert either_store.put_serialized(oid, header, buffers) == len(want)
    out = either_store.get(oid)
    assert bytes(out.view[:len(want)]) == want
    out.close()
