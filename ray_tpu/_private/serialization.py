"""Value serialization: msgpack envelope + pickle5 out-of-band buffers.

Mirrors the reference's SerializationContext capability (reference:
python/ray/serialization.py:66,:251 _serialize_to_pickle5): values are
cloudpickled with protocol 5; large contiguous buffers (numpy arrays, the
host copy of jax.Arrays) ride out-of-band so the object-store write and the
deserializing read are zero-copy. The envelope is
    msgpack([meta, pickled_bytes, nbuffers]) + raw buffer concatenation
with buffer sizes recorded in meta, so a reader can mmap the object and map
each out-of-band buffer straight onto the shared memory.

ObjectRefs and ActorHandles found inside values are swapped for plain
descriptors at serialize time and rehydrated at deserialize time through
thread-local hooks installed by the core worker — this is what lets refs and
handles be passed freely between processes while the owner tracks borrows.
"""

from __future__ import annotations

import pickle
import threading
from typing import Any, Callable

import cloudpickle
import msgpack
import numpy as np

_local = threading.local()


def set_context(
    serialize_ref: Callable[[Any], dict] | None,
    deserialize_ref: Callable[[dict], Any] | None,
    serialize_handle: Callable[[Any], dict] | None = None,
    deserialize_handle: Callable[[dict], Any] | None = None,
):
    _local.serialize_ref = serialize_ref
    _local.deserialize_ref = deserialize_ref
    _local.serialize_handle = serialize_handle
    _local.deserialize_handle = deserialize_handle


def get_ref_serializer():
    return getattr(_local, "serialize_ref", None)


def get_ref_deserializer():
    return getattr(_local, "deserialize_ref", None)


def get_handle_serializer():
    return getattr(_local, "serialize_handle", None)


def get_handle_deserializer():
    return getattr(_local, "deserialize_handle", None)


def _to_host(value):
    """Convert device-resident arrays to host buffers for serialization.

    jax.Array is serialized as its numpy host copy; fully-sharded arrays must
    be gathered by the caller first (the trainer checkpoints sharded state via
    orbax instead of passing it through the object store).
    """
    import numpy as np

    try:
        import jax
    except Exception:  # pragma: no cover - jax always present in this image
        return value
    if isinstance(value, jax.Array):
        return np.asarray(value)
    return value


class _Pickler(cloudpickle.Pickler):
    def __init__(self, file, buffers):
        super().__init__(file, protocol=5, buffer_callback=buffers.append)

    def persistent_id(self, obj):
        return None

    def reducer_override(self, obj):
        import jax

        if isinstance(obj, jax.Array):
            arr = _to_host(obj)
            return (_rebuild_jax_array, (arr,))
        if type(obj) is np.ndarray and not obj.dtype.hasobject and not (
                obj.flags.c_contiguous or obj.flags.f_contiguous):
            # numpy pickles a strided array IN-BAND (`tobytes` into the
            # stream, copied again into the header: 3.3-4.8 s for a
            # 0.5 GB snapshot leaf whose padded rows the device hands
            # back as a view); one dense copy goes out of band
            return np.ascontiguousarray(obj).__reduce_ex__(5)
        # Delegate to cloudpickle's reducer, NOT NotImplemented: cloudpickle
        # implements by-value pickling of local/interactively-defined
        # functions and classes through reducer_override, so returning
        # NotImplemented here silently downgraded task args to stock
        # pickle (locally-defined functions inside args failed to ship).
        return super().reducer_override(obj)


def _rebuild_jax_array(np_arr):
    # Rehydrate lazily as numpy; callers move data to device explicitly
    # (device placement is a property of the computation, not the value).
    return np_arr


def serialize(value: Any) -> tuple[bytes, list[memoryview]]:
    """Returns (envelope_header, buffers). The full object payload is
    header + b''.join(buffers); buffers may be written directly to shm."""
    import io

    buffers: list[pickle.PickleBuffer] = []
    bio = io.BytesIO()
    _Pickler(bio, buffers).dump(value)
    pickled = bio.getvalue()
    raw: list[memoryview] = []
    sizes: list[int] = []
    for buf in buffers:
        mv = buf.raw()
        raw.append(mv)
        sizes.append(mv.nbytes)
    meta = {"buffer_sizes": sizes}
    header = msgpack.packb([meta, pickled, len(raw)], use_bin_type=True)
    return _frame_header(header), raw


def _frame_header(header: bytes) -> bytes:
    import struct

    return struct.pack(">I", len(header)) + header


def deserialize(payload: memoryview | bytes) -> Any:
    import struct

    payload = memoryview(payload)
    (hlen,) = struct.unpack(">I", payload[:4])
    meta, pickled, nbuf = msgpack.unpackb(payload[4 : 4 + hlen], raw=False)
    offset = 4 + hlen
    buffers = []
    for size in meta["buffer_sizes"]:
        buffers.append(payload[offset : offset + size])
        offset += size
    return pickle.loads(pickled, buffers=buffers)


def total_size(header: bytes, buffers: list[memoryview]) -> int:
    return len(header) + sum(b.nbytes for b in buffers)


def dumps(value: Any) -> bytes:
    """One-shot serialize to contiguous bytes (for RPC payloads)."""
    header, buffers = serialize(value)
    if not buffers:
        return header
    return b"".join([header, *[bytes(b) for b in buffers]])


def loads(data: bytes | memoryview) -> Any:
    return deserialize(data)
