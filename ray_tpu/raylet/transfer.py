"""Bulk object-transfer data plane between raylets.

The control-plane rpc layer (rpc.py) is msgpack frames multiplexed on ONE
connection per peer pair — fine for leases and heartbeats, wrong for bulk
data: a 5MB chunk rides the same socket as heartbeats (head-of-line
blocking), costs a bytes() copy out of the arena plus a msgpack copy on
each side, and the old stop-and-wait fetch_chunk loop paid a full RTT per
chunk. This module is the dedicated data plane (reference:
src/ray/object_manager/object_manager.h chunked push/pull +
pull_manager.h admission; design lineage: Ownership NSDI'21, Hoplite's
pipelined multi-source fetch):

* Each raylet serves a **bulk channel** — a sibling TCP listener (plus a
  same-node UDS twin, like the worker direct task channel) speaking the
  normal frame protocol for requests, served entirely by blocking
  threads. A pull is ONE request followed by a stream of chunk records;
  the sender `sendmsg`s memoryview slices straight out of the mmap'd
  store buffer (no bytes() copy-out, no pickle for payloads) and the
  receiver `recv_into`s directly into the `store.create`d buffer. The
  kernel socket buffer keeps chunks in flight ahead of the receiver's
  arena writes, so transmission overlaps storage — and the control
  connection never carries a bulk frame.

* **Multi-source striping**: when the GCS directory lists several
  holders, stripe ranges are pulled off a shared work-stealing queue by
  one worker thread per source — a slow source naturally moves fewer
  bytes, and a source dying mid-stream has its unfinished remainder
  resumed by survivors instead of restarting the pull.

* **Transfer pins**: the sender pins an object for the duration of a
  registered transfer (plus a TTL lease so a dead puller can't pin
  forever); free/eviction of a pinned object is deferred until the last
  pin drops or expires.

Chunk record wire format (after the REPLY_OK control frame):
    8-byte big-endian offset | 4-byte big-endian length | payload
terminated by the sentinel record (offset=2^64-1, length=0).

Note on copies: with the native arena store on Python >= 3.12 the send
side is true zero-copy (pinned arena view straight into sendmsg); on
3.10/3.11 NativeObjectStore.get() copies the payload out once (PEP-688
gate), so the win there is pipelining + no-pickle + control-plane
isolation rather than zero copies.

Failpoint seams: transfer.register (sender, per pull request),
transfer.chunk_send / transfer.chunk_recv (per chunk record),
transfer.pin_expire (sweep expiring a pin lease).
"""

from __future__ import annotations

import collections
import logging
import os
import pickle
import socket
import struct
import threading
import time
import traceback

import msgpack

from ray_tpu import exceptions as exc
from ray_tpu._private import failpoints as _fp
from ray_tpu._private import rpc
from ray_tpu._private import stats as _stats
from ray_tpu._private import tracing as _tracing
from ray_tpu._private.ids import ObjectID

logger = logging.getLogger("ray_tpu.transfer")

_HDR = struct.Struct(">I")        # control-frame length prefix (rpc format)
_CHUNK = struct.Struct(">QI")     # per-chunk record header: offset, length
_DONE_OFFSET = (1 << 64) - 1      # sentinel offset terminating a stream

M_PULL_BYTES = _stats.Count(
    "raylet.pull_bytes_total", "object bytes pulled from remote nodes")
M_PULLS_STRIPED = _stats.Count(
    "raylet.pulls_striped_total",
    "pulls that actually striped across >=2 sources")
M_INFLIGHT_CHUNKS = _stats.Gauge(
    "raylet.transfer_inflight_chunks",
    "bulk-transfer chunk records currently being sent/received")
M_PULL_S = _stats.Histogram(
    "transfer.pull_s", _stats.LATENCY_BOUNDARIES_S,
    "bulk pull wall time, registration -> object sealed (receiver "
    "side); exemplar links the pulling request's trace")

# ---------------------------------------------------------------------------
# live-transfer registry (debug_state / stall doctor): every in-flight
# streaming pull (receiver side) and serve stream (sender side) in this
# process, with age + progress — so `ray-tpu state transfers` can answer
# "which stream is stuck and how far did it get" for a live raylet.
# ---------------------------------------------------------------------------

import itertools as _itertools

_active_lock = threading.Lock()
_active_pulls: dict[int, dict] = {}
_active_serves: dict[int, dict] = {}
_active_ids = _itertools.count(1)


def _track(table: dict, entry: dict) -> int:
    token = next(_active_ids)
    with _active_lock:
        table[token] = entry
    return token


def _untrack(table: dict, token: int) -> None:
    with _active_lock:
        table.pop(token, None)


# Advisory purpose labels for upcoming pulls ("kv_warm", ...): a worker
# that knows WHY it is about to resolve a ref registers the label with
# its raylet (hint_pull_purpose rpc) before the get; the raylet's
# streaming pull consumes it so `ray-tpu state transfers` attributes the
# bytes instead of showing anonymous traffic. Bounded and best-effort —
# a missed hint only costs the label.
_pull_hints: dict[bytes, str] = {}


def hint_pull(oid: bytes, purpose: str) -> None:
    with _active_lock:
        if len(_pull_hints) >= 256:
            _pull_hints.clear()
        _pull_hints[oid] = str(purpose)[:64]


def take_pull_hint(oid: bytes) -> str:
    with _active_lock:
        return _pull_hints.pop(oid, "")


def debug_transfers(pins: "TransferPins | None" = None) -> dict:
    """Msgpack-safe snapshot of this process's in-flight transfers."""
    now = time.monotonic()
    out = {"pulls": [], "serves": []}
    with _active_lock:
        items = ([("pulls", e) for e in _active_pulls.values()]
                 + [("serves", e) for e in _active_serves.values()])
    for kind, e in items:
        remaining = e.get("remaining")
        size = e.get("size", 0)
        done = (size - remaining[0]) if remaining else e.get("sent", 0)
        out[kind].append({
            "object_id": e["object_id"],
            "age_s": round(now - e["t0"], 3),
            "size": size,
            "progress": f"{done}/{size}",
            "sources": e.get("sources", 1),
            "trace_id": e.get("trace_id", ""),
            "purpose": e.get("purpose", ""),
        })
    if pins is not None:
        out["pins"] = pins.debug()
    return out


class PullError(Exception):
    """Streaming pull failed on every source; carries per-source causes."""

    def __init__(self, oid: bytes, errors):
        self.errors = list(errors)
        detail = "; ".join(f"{a}: {type(e).__name__}: {e}"
                           for a, e in self.errors) or "no reachable source"
        super().__init__(f"pull of {oid[:6].hex()} failed: {detail}")


# ---------------------------------------------------------------------------
# sender-side transfer pins
# ---------------------------------------------------------------------------


class TransferPins:
    """Thread-safe registry of sender-side transfer pins with TTL leases.

    A pin names (token, oid): the bulk server uses one token per
    connection (released when the connection dies), the control-path
    object_info/fetch_chunk pull uses one per rpc connection (released
    only by TTL/disconnect). While any unexpired pin exists for an oid,
    free/eviction is deferred: callers record the free via defer_free()
    and complete it when release/sweep reports the oid freeable."""

    def __init__(self):
        self._lock = threading.Lock()
        self._leases: dict[tuple, float] = {}   # (token, oid) -> expires_at
        self._count: dict[bytes, int] = {}      # oid -> live pin count
        self._deferred_free: set[bytes] = set()

    def pin(self, oid: bytes, token, ttl: float) -> None:
        """Take (or refresh) one pin lease on `oid` for `token`."""
        now = time.monotonic()
        with self._lock:
            key = (token, oid)
            if key not in self._leases:
                self._count[oid] = self._count.get(oid, 0) + 1
            self._leases[key] = now + ttl

    def pinned(self, oid: bytes) -> bool:
        with self._lock:
            return self._count.get(oid, 0) > 0

    def cancel_deferred_free(self, oid: bytes) -> None:
        """The object was re-created (re-seal by a retried producer, a
        fresh pull): a stale deferral from its PREVIOUS incarnation must
        not delete the new, legitimate copy when the old pins drop."""
        with self._lock:
            self._deferred_free.discard(oid)

    def defer_free_if_pinned(self, oid: bytes) -> bool:
        """Atomically: if `oid` is still pinned, record that it should be
        freed once its last pin drops and return True; else return False
        (the caller frees now). One atomic step — a separate
        pinned()-then-defer would race a concurrent release dropping the
        last pin in between, stranding the deferred free forever."""
        with self._lock:
            if self._count.get(oid, 0) > 0:
                self._deferred_free.add(oid)
                return True
            return False

    def unpin(self, oid: bytes, token) -> list[bytes]:
        """Release ONE (token, oid) lease — not the token's whole pin
        set. Returns [oid] if its deferred free became runnable."""
        with self._lock:
            key = (token, oid)
            if key not in self._leases:
                return []
            del self._leases[key]
            freed = self._drop(key)
            return [freed] if freed is not None else []

    def _drop(self, key) -> bytes | None:
        """Lock held. Drop one lease; returns the oid if it became
        freeable (last pin gone AND a free was deferred)."""
        oid = key[1]
        n = self._count.get(oid, 1) - 1
        if n <= 0:
            self._count.pop(oid, None)
            if oid in self._deferred_free:
                self._deferred_free.discard(oid)
                return oid
        else:
            self._count[oid] = n
        return None

    def release_token(self, token) -> list[bytes]:
        """Release every pin held by `token` (connection closed).
        Returns oids whose deferred free became runnable."""
        freeable = []
        with self._lock:
            for key in [k for k in self._leases if k[0] == token]:
                del self._leases[key]
                oid = self._drop(key)
                if oid is not None:
                    freeable.append(oid)
        return freeable

    def sweep(self, now: float | None = None) -> list[bytes]:
        """Expire stale leases (dead pullers). Returns freeable oids."""
        now = time.monotonic() if now is None else now
        freeable = []
        with self._lock:
            for key, expires in [(k, v) for k, v in self._leases.items()]:
                if expires > now:
                    continue
                if _fp.ARMED:
                    # pin-expiry seam: `raise` aborts this sweep pass
                    # (retried next tick); `delay` stretches the lease
                    _fp.fire("transfer.pin_expire")
                del self._leases[key]
                oid = self._drop(key)
                if oid is not None:
                    freeable.append(oid)
            # belt-and-braces: a deferred free whose pins are already
            # all gone (e.g. recorded after a racing release) completes
            # on the next sweep instead of stranding forever
            for oid in list(self._deferred_free):
                if self._count.get(oid, 0) <= 0:
                    self._deferred_free.discard(oid)
                    freeable.append(oid)
        return freeable

    def count(self) -> int:
        with self._lock:
            return len(self._leases)

    def debug(self) -> dict:
        """Per-object pin state for debug_state: live pin count and the
        seconds until the soonest lease expiry (negative = overdue for
        the next sweep)."""
        now = time.monotonic()
        out: dict[str, dict] = {}
        with self._lock:
            for (token, oid), expires in self._leases.items():
                rec = out.setdefault(oid.hex()[:12], {
                    "pins": 0, "expires_in_s": None, "deferred_free": False})
                rec["pins"] += 1
                left = round(expires - now, 3)
                if rec["expires_in_s"] is None or left < rec["expires_in_s"]:
                    rec["expires_in_s"] = left
            for oid in self._deferred_free:
                out.setdefault(oid.hex()[:12], {
                    "pins": 0, "expires_in_s": None,
                    "deferred_free": True})["deferred_free"] = True
        return out


# ---------------------------------------------------------------------------
# low-level socket helpers (blocking sockets, bulk-channel threads only)
# ---------------------------------------------------------------------------


def _recv_exact(sock, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("bulk channel closed")
        buf.extend(chunk)
    return bytes(buf)


def _recv_exact_into(sock, view: memoryview) -> None:
    got = 0
    while got < len(view):
        n = sock.recv_into(view[got:])
        if n == 0:
            raise ConnectionError("bulk channel closed mid-chunk")
        got += n


def _sendmsg_all(sock, *parts) -> None:
    """Vectored sendall: one sendmsg per syscall-burst, straight from the
    caller's buffers (no join, no copy), with partial-send resume."""
    bufs = [memoryview(p).cast("B") for p in parts if len(p)]
    while bufs:
        n = sock.sendmsg(bufs)
        while bufs and n >= len(bufs[0]):
            n -= len(bufs[0])
            bufs.pop(0)
        if bufs and n:
            bufs[0] = bufs[0][n:]


def _read_control_frame(sock):
    (length,) = _HDR.unpack(_recv_exact(sock, _HDR.size))
    return msgpack.unpackb(_recv_exact(sock, length), raw=False)


def _dial(address: str, connect_timeout: float, io_timeout: float):
    """Dial a bulk address: 'unix:/path' or 'host:port'."""
    if address.startswith("unix:"):
        sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        sock.settimeout(connect_timeout)
        sock.connect(address[len("unix:"):])
    else:
        host, _, port = address.rpartition(":")
        sock = socket.create_connection((host, int(port)),
                                        timeout=connect_timeout)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    sock.settimeout(io_timeout)
    return sock


# ---------------------------------------------------------------------------
# sender: the bulk channel server
# ---------------------------------------------------------------------------


class BulkTransferServer:
    """Serves streaming pulls out of this node's object store.

    Runs entirely on daemon threads (one acceptor per listener, one per
    connection): bulk byte-pushing must never occupy the raylet's asyncio
    loop, which carries heartbeats and lease grants. Raylet state it
    reads (local_objects) is GIL-atomic dict access; spill restores are
    delegated to the raylet loop via run_coroutine_threadsafe."""

    def __init__(self, raylet):
        self.raylet = raylet
        self.address = ""          # advertised host:port
        self._listeners: list = []
        self._shutdown = False

    def start(self, bind_host: str, advertise_ip: str,
              uds_dir: str | None) -> str:
        tcp = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        tcp.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        tcp.bind((bind_host, 0))
        tcp.listen(16)
        port = tcp.getsockname()[1]
        self.address = f"{advertise_ip}:{port}"
        self._listeners.append(tcp)
        threading.Thread(target=self._accept_loop, args=(tcp,),
                         name="bulk-accept-tcp", daemon=True).start()
        if uds_dir is not None:
            # Same-node twin keyed by the TCP port, so rpc.prefer_uds
            # rewrites the advertised address exactly like rpc listeners.
            try:
                os.makedirs(uds_dir, exist_ok=True)
                path = rpc.uds_address(uds_dir, port)[len("unix:"):]
                try:
                    os.unlink(path)
                except FileNotFoundError:
                    pass
                uds = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
                uds.bind(path)
                uds.listen(16)
                self._listeners.append(uds)
                threading.Thread(target=self._accept_loop, args=(uds,),
                                 name="bulk-accept-uds", daemon=True).start()
            except OSError as e:  # pragma: no cover - fs quirks
                logger.warning("no UDS twin for bulk port %d: %s", port, e)
        return self.address

    def close(self):
        self._shutdown = True
        for sock in self._listeners:
            try:
                sock.close()
            except OSError:
                pass

    def _accept_loop(self, listener):
        while not self._shutdown:
            try:
                sock, _ = listener.accept()
            except OSError:
                return
            threading.Thread(target=self._serve, args=(sock,),
                             name="bulk-serve", daemon=True).start()

    def _serve(self, sock):
        if sock.family in (socket.AF_INET, socket.AF_INET6):
            try:
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            except OSError:
                pass
        raylet = self.raylet
        # Both directions bounded: a puller that stops READING mid-stream
        # (wedged process, netsplit without RST) times this send side out
        # instead of parking the serve thread — and its pinned buffer —
        # forever; an idle connection is reaped the same way (pullers
        # dial per transfer, so reaping idle conns costs nothing).
        sock.settimeout(
            max(raylet.config.bulk_transfer_io_timeout_s, 30.0) * 2)
        pins: TransferPins = raylet.transfer_pins
        token = ("bulk", id(sock), os.getpid())
        open_bufs: dict[bytes, object] = {}  # oid -> held store buffer
        try:
            while not self._shutdown:
                msg = _read_control_frame(sock)
                _msgtype, msgid, method, data = msg
                if method == "ping":
                    sock.sendall(rpc._pack([rpc.REPLY_OK, msgid, method,
                                            "pong"]))
                    continue
                if method != "bulk_pull":
                    err = rpc.RpcError(
                        f"bulk channel carries bulk_pull/ping only, "
                        f"not {method!r}")
                    sock.sendall(rpc._pack([rpc.REPLY_ERR, msgid, method,
                                            [pickle.dumps(err), ""]]))
                    continue
                self._handle_pull(sock, msgid, data, token, open_bufs)
        except (ConnectionError, OSError, _fp.FailpointError, struct.error):
            pass
        except Exception:
            logger.exception("bulk serve loop error")
        finally:
            for buf in open_bufs.values():
                try:
                    buf.close()
                except Exception:
                    pass
            freeable = pins.release_token(token)
            if freeable:
                raylet.complete_deferred_frees_threadsafe(freeable)
            try:
                sock.close()
            except OSError:
                pass

    def _handle_pull(self, sock, msgid, data, token, open_bufs):
        raylet = self.raylet
        oid = data["object_id"]
        offset = int(data.get("offset", 0))
        length = int(data.get("length", 0))  # 0 = stat/pin only
        chunk = int(data.get("chunk", 0)) or \
            raylet.config.object_transfer_chunk_size
        # puller's sampled trace context (tracing.py wire format): this
        # source's serve span joins the puller's transfer tree
        _trace_start = time.time()
        _trace_ctx = _tracing.from_wire(data.get("trace"))
        if _fp.ARMED:
            # transfer registration seam: `raise` -> typed error reply
            # (puller fails this source over); `drop_conn` kills the
            # stream; `exit` kills this (source) raylet mid-transfer
            try:
                if _fp.fire("transfer.register") == "drop_conn":
                    raise ConnectionError("transfer.register drop_conn")
            except _fp.FailpointError as e:
                self._send_err(sock, msgid, e)
                return
        try:
            rec = raylet.local_objects.get(oid)
            if rec is not None and rec.get("spilled"):
                # restore rides the raylet loop (store mutation + spill
                # bookkeeping are loop-confined)
                import asyncio

                asyncio.run_coroutine_threadsafe(
                    raylet._restore_spilled(oid),
                    raylet._loop).result(timeout=60)
                rec = raylet.local_objects.get(oid)
            # The pin outlives this request: held under `token` until the
            # connection closes or the TTL lease lapses, so the object
            # cannot be freed/evicted between two range requests of one
            # registered transfer.
            pins_ttl = raylet.config.transfer_pin_ttl_s
            raylet.transfer_pins.pin(oid, token, pins_ttl)
            buf = open_bufs.get(oid)
            if buf is None:
                # get_raw: pinned view straight into the arena, explicit
                # close at connection teardown — zero-copy on every
                # Python version (get() copies the payload out on <3.12)
                getter = getattr(raylet.store, "get_raw", raylet.store.get)
                buf = getter(ObjectID(oid))
                if buf is None:
                    # drop only THIS object's pin — the connection may be
                    # mid-transfer on other (live) objects
                    freeable = raylet.transfer_pins.unpin(oid, token)
                    if freeable:
                        raylet.complete_deferred_frees_threadsafe(freeable)
                    raise exc.ObjectLostError(oid.hex())
                open_bufs[oid] = buf
            size = buf.size
        except exc.ObjectLostError as e:
            self._send_err(sock, msgid, e)
            return
        if length < 0:
            length = max(0, size - offset)
        end = min(size, offset + length)
        sock.sendall(rpc._pack([rpc.REPLY_OK, msgid, "bulk_pull",
                                {"size": size}]))
        pos = offset
        view = buf.view
        serve_entry = {"object_id": oid.hex()[:12], "t0": time.monotonic(),
                       "size": end - offset, "sent": 0,
                       "purpose": str(data.get("purpose") or "")[:64],
                       "trace_id": (_trace_ctx.trace_id.hex()
                                    if _trace_ctx is not None else "")}
        serve_token = _track(_active_serves, serve_entry)
        try:
            while pos < end:
                n = min(chunk, end - pos)
                if _fp.ARMED:
                    if _fp.fire("transfer.chunk_send") == "drop_conn":
                        raise ConnectionError(
                            "transfer.chunk_send drop_conn")
                M_INFLIGHT_CHUNKS.add(1)
                try:
                    _sendmsg_all(sock, _CHUNK.pack(pos, n),
                                 view[pos:pos + n])
                finally:
                    M_INFLIGHT_CHUNKS.add(-1)
                pos += n
                serve_entry["sent"] = pos - offset
            sock.sendall(_CHUNK.pack(_DONE_OFFSET, 0))
        finally:
            _untrack(_active_serves, serve_token)
        if _trace_ctx is not None and length:
            _tracing.record_span(
                "transfer.serve", _trace_start, time.time(),
                _tracing.child(_trace_ctx),
                {"object_id": oid[:6].hex(), "bytes": end - offset})

    @staticmethod
    def _send_err(sock, msgid, e: BaseException):
        try:
            sock.sendall(rpc._pack([rpc.REPLY_ERR, msgid, "bulk_pull",
                                    [pickle.dumps(e),
                                     traceback.format_exc()]]))
        except (OSError, ConnectionError):
            pass


# ---------------------------------------------------------------------------
# receiver: striped streaming pull
# ---------------------------------------------------------------------------


class _Source:
    """One dialed bulk connection (blocking; lives on its worker thread)."""

    def __init__(self, address: str, connect_timeout: float,
                 io_timeout: float, purpose: str = ""):
        self.address = address
        self.sock = _dial(address, connect_timeout, io_timeout)
        self._msgid = 0
        self.purpose = purpose  # echoed in requests -> source serve rows

    def close(self):
        try:
            self.sock.close()
        except OSError:
            pass

    def _request(self, oid: bytes, offset: int, length: int,
                 chunk: int, trace: list | None = None) -> int:
        """Send one bulk_pull request; returns the object's total size.
        Raises the sender's typed error on REPLY_ERR."""
        self._msgid += 1
        req = {"object_id": oid, "offset": offset, "length": length,
               "chunk": chunk}
        if self.purpose:
            req["purpose"] = self.purpose
        if trace is not None:
            # puller's sampled trace context: the source raylet's serve
            # span joins the pull's trace tree (tracing.py wire format)
            req["trace"] = trace
        self.sock.sendall(rpc._pack([
            rpc.REQUEST, self._msgid, "bulk_pull", req]))
        msg = _read_control_frame(self.sock)
        if msg[0] == rpc.REPLY_ERR:
            e = pickle.loads(msg[3][0])
            raise e
        return int(msg[3]["size"])

    def stat(self, oid: bytes) -> int:
        """Pin + size probe: a zero-length pull (stream is just the
        terminator record)."""
        size = self._request(oid, 0, 0, 1)
        self._drain_stream(None, 0, 0)
        return size

    def pull_range(self, oid: bytes, offset: int, length: int, chunk: int,
                   view: memoryview, progress: list,
                   trace: list | None = None) -> None:
        """Stream one contiguous range into `view` at its true offsets.
        `progress[0]` tracks contiguous bytes landed so a failure mid-
        range lets the caller requeue only the remainder."""
        self._request(oid, offset, length, chunk, trace)
        self._drain_stream(view, offset, length, progress)

    def _drain_stream(self, view, offset, length, progress=None):
        expect = offset
        end = offset + length
        while True:
            pos, n = _CHUNK.unpack(_recv_exact(self.sock, _CHUNK.size))
            if pos == _DONE_OFFSET and n == 0:
                break
            if view is None or pos != expect or pos + n > end:
                raise ConnectionError(
                    f"bulk stream protocol error: chunk [{pos},{pos + n}) "
                    f"outside expected [{expect},{end})")
            if _fp.ARMED:
                if _fp.fire("transfer.chunk_recv") == "drop_conn":
                    raise ConnectionError("transfer.chunk_recv drop_conn")
            M_INFLIGHT_CHUNKS.add(1)
            try:
                _recv_exact_into(self.sock, view[pos:pos + n])
            finally:
                M_INFLIGHT_CHUNKS.add(-1)
            M_PULL_BYTES.inc(n)
            expect = pos + n
            if progress is not None:
                progress[0] = expect - offset
        if view is not None and expect != end:
            raise ConnectionError(
                f"bulk stream ended early at {expect} of [{offset},{end})")


def streaming_pull(oid: bytes, object_id: ObjectID, store,
                   addresses: list[str], *, chunk: int, stripe: int,
                   max_sources: int = 4, connect_timeout: float = 5.0,
                   io_timeout: float = 30.0,
                   trace: list | None = None,
                   purpose: str = "") -> int:
    """Pull one object over the bulk plane, striping across up to
    `max_sources` of `addresses`. Creates, fills and seals the store
    entry; aborts it on failure. Blocking — run on an executor thread.
    Returns the object size. Raises PullError when every source fails."""
    errors: list = []
    first: _Source | None = None
    size = None
    usable: list[str] = []
    for addr in addresses:
        if first is None:
            # stat probe: sizes the buffer AND registers the transfer
            # pin on this source before any byte flows
            try:
                src = _Source(addr, connect_timeout, io_timeout, purpose)
            except OSError as e:
                errors.append((addr, e))
                continue
            try:
                size = src.stat(oid)
            except Exception as e:
                errors.append((addr, e))
                src.close()
                continue
            first = src
        # further sources are dialed lazily on their worker threads —
        # an unreachable one just records its error and drops out
        usable.append(addr)
        if len(usable) >= max_sources:
            break
    if first is None or size is None:
        raise PullError(oid, errors)
    # directory entries beyond max_sources are failover SPARES: tried
    # sequentially if every striped source fails (dead stat probes are
    # not retried)
    dead = {a for a, _ in errors}
    spares = [a for a in addresses if a not in usable and a not in dead]

    try:
        try:
            buf = store.create(object_id, size)
        except FileExistsError:
            # stale .build from an earlier abandoned pull (files
            # backend's O_EXCL create has no delete-and-retry like the
            # native arena)
            store.abort(object_id)
            buf = store.create(object_id, size)
    except BaseException:
        # e.g. MemoryError on a full arena: don't leak the stat-probe
        # connection and its sender-side transfer pin across retries
        first.close()
        raise
    wedged = False  # a live writer thread forbids store.abort (below)
    pull_token = None
    try:
        view = buf.view
        unit = max(chunk, stripe)
        queue: collections.deque = collections.deque()
        pos = 0
        while pos < size:
            queue.append((pos, min(unit, size - pos)))
            pos += unit
        if not queue:
            queue.append((0, 0))  # zero-byte object: one empty range
        lock = threading.Lock()
        remaining = [size]
        bytes_by_source: dict[str, int] = {}
        pull_token = _track(_active_pulls, {
            "object_id": oid.hex()[:12], "t0": time.monotonic(),
            "size": size, "remaining": remaining,
            "sources": len(usable), "purpose": purpose,
            "trace_id": (bytes(trace[0]).hex() if trace else "")})

        nsources = max(1, len(usable))
        conns: list[_Source] = []  # live worker connections (abort hook)

        def work(addr: str, conn: _Source | None):
            moved = 0
            try:
                if conn is None:
                    conn = _Source(addr, connect_timeout, io_timeout,
                                   purpose)
                with lock:
                    conns.append(conn)
                while True:
                    with lock:
                        if not queue:
                            return
                        off, ln = queue.popleft()
                        # guided self-scheduling: coalesce ADJACENT
                        # queued units into one request, sized to the
                        # remaining work over 2x the sources — few
                        # request round trips up front, fine-grained
                        # stealing for the tail
                        target = max(unit, remaining[0] // (2 * nsources))
                        while (queue and queue[0][0] == off + ln
                               and ln < target):
                            _o2, l2 = queue.popleft()
                            ln += l2
                    progress = [0]
                    try:
                        conn.pull_range(oid, off, ln, chunk, view, progress,
                                        trace)
                        moved += ln
                        with lock:
                            remaining[0] -= ln
                    except Exception:
                        got = progress[0]
                        moved += got
                        with lock:
                            remaining[0] -= got
                            if ln - got:
                                queue.append((off + got, ln - got))
                        raise
            except Exception as e:
                with lock:
                    errors.append((addr, e))
            finally:
                with lock:
                    bytes_by_source[addr] = moved
                if conn is not None:
                    conn.close()

        if len(usable) == 1 or len(queue) == 1:
            # sequential: sole source, or a single-range object — the
            # other usable sources serve as failover, not parallelism
            # (the queue requeues a failed range's remainder, so the
            # next source resumes where the dead one stopped)
            for i, addr in enumerate(usable):
                work(addr, first if i == 0 else None)
                if remaining[0] <= 0:
                    break
        else:
            threads = []
            for i, addr in enumerate(usable):
                t = threading.Thread(
                    target=work, args=(addr, first if i == 0 else None),
                    name=f"bulk-pull-{i}", daemon=True)
                threads.append(t)
                t.start()
            for t in threads:
                # bounded by per-socket io timeouts; the join timeout is
                # a backstop against a wedged thread leaking the pull
                t.join(timeout=io_timeout * 4)
            if any(t.is_alive() for t in threads):
                # a source trickling >=1 byte per io_timeout defeats the
                # per-recv socket timeout: close the sockets out from
                # under the wedged recvs to break them loose
                with lock:
                    for c in conns:
                        c.close()
                for t in threads:
                    t.join(timeout=5.0)
                wedged = any(t.is_alive() for t in threads)
        if wedged:
            # NEVER abort with a live writer thread: store.abort would
            # recycle the arena range under its recv_into and corrupt
            # whatever lands there next. Leak the unsealed create — the
            # daemon thread dies with the process, and the next pull
            # attempt replaces the stale entry (native create deletes-
            # and-retries; the files path aborts on FileExistsError
            # above).
            logger.error("streaming pull of %s: worker thread wedged "
                         "past every timeout; leaking the unsealed "
                         "create instead of aborting under it",
                         oid[:6].hex())
            buf.close()
            raise PullError(oid, errors + [
                ("local", RuntimeError("pull worker thread wedged"))])
        if remaining[0] > 0:
            for addr in spares:  # every striped source failed: failover
                work(addr, None)
                if remaining[0] <= 0:
                    break
        if remaining[0] > 0:
            raise PullError(oid, errors)
        if sum(1 for b in bytes_by_source.values() if b > 0) >= 2:
            M_PULLS_STRIPED.inc()
        buf.close()
        store.seal(object_id)
    except BaseException:
        buf.close()
        if not wedged:
            store.abort(object_id)
        raise
    finally:
        if pull_token is not None:
            _untrack(_active_pulls, pull_token)
    return size
