"""HOST backend: cross-process CPU collectives with a tiered data plane.

The gloo-equivalent of the reference's collective backends (reference:
python/ray/util/collective/collective_group/ — NCCLGroup :115 and the MPI
stub). Rendezvous goes through the GCS KV (the reference used a named
"Info" actor, util.py) — rank 0 binds a TCP hub, publishes its address
under `collective/<group>`, and every other rank connects.

Four transports, selected per op by payload placement, size and node
placement:

device — the accelerator plane: when every rank's payload is a
        jax.Array and the group's processes share one jax.distributed
        runtime (parallel/multihost), the op dispatches through
        xla_backend.DeviceTransport — cached jitted shard_map
        collectives over a one-device-per-process mesh — so bytes ride
        ICI/XLA and never touch host RAM. The vote is per op and
        unanimous (a 1-byte kind-tagged hub ctl round, like the shm
        ok-flag exchange); any rank holding a host array vetoes and the
        op falls back to the tiers below.
hub   — star topology, all contributions through rank 0's socket +
        shared op table. Latency-optimal for control-sized tensors
        (metrics, barriers, rendezvous); carries every op kind.
ring  — direct rank-to-rank TCP ring for large tensors: reduce-scatter
        + allgather schedules for allreduce/reducescatter, block
        rotation for allgather, a pipelined relay chain for broadcast.
        Steps are chunk-pipelined (the reduce of chunk k overlaps the
        receive of chunk k+1) and zero-copy (memoryview slices of the
        work buffer go straight to sendall; recv_into fills scratch or
        the destination — no tobytes per step). With `quantize="int8"` the
        allreduce wire format becomes block-scaled int8 (EQuARX-style:
        per-QUANT_BLOCK f32 scales ride ahead of each chunk's int8
        payload, the reduce runs on dequantized float32) — ~4x fewer
        socket bytes for float32 gradients.
shm   — ranks that rendezvous on the same node map one tmpfs segment
        (native/store segment alloc) and collectives become pure memory
        traffic: write slot, counter-barrier, reduce a 1/w stripe,
        read result — zero socket syscalls, zero serialization
        (shm_transport.py).

Every tier keeps the abort-not-hang contract: a dead peer turns into a
TimeoutError within the group timeout on every survivor (hub per-op
timeouts, ring socket timeouts + teardown, shm barrier deadline + abort
word, device vote round bounded by the hub deadline — a rank that dies
inside an in-flight XLA collective is bounded by the device runtime's
own failure detection), so the SGD layer above can resize the group.
"""

from __future__ import annotations

import functools
import logging
import os
import socket
import struct
import threading
import time

import msgpack
import numpy as np

from ray_tpu._private import failpoints as _fp
from ray_tpu.collective.types import (_NUMPY_REDUCE, QUANT_BLOCK, ReduceOp,
                                      Transport, normalize_quantize)

logger = logging.getLogger(__name__)

_HDR = struct.Struct(">I")


def _op_entry(name: str):
    """Wrap a public collective op: tracks (op, phase, age) in the
    group's debug row — the `ray-tpu state collectives` / stall-doctor
    feed — and makes group-timeout hangs self-describing by attaching a
    bounded state snapshot to the raised TimeoutError (it travels inside
    pickled rpc error replies via the exception __dict__, so the driver
    sees WHICH op wedged on which rank without a reproduction run)."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(self, *args, **kwargs):
            dbg = self._dbg
            dbg["op"] = name
            dbg["phase"] = "route"
            dbg["t0"] = time.monotonic()
            try:
                return fn(self, *args, **kwargs)
            except TimeoutError as e:
                if not hasattr(e, "state_snapshot"):
                    from ray_tpu._private import debug_state as _ds

                    try:
                        e.state_snapshot = _ds.bounded(self.debug_state())
                    except Exception:
                        pass
                raise
            finally:
                dbg["ops_done"] = dbg.get("ops_done", 0) + 1
                dbg["op"] = None
                dbg["phase"] = "idle"
        return wrapper
    return deco

# ops the int8 block-scaled wire format can carry (the reduce happens on
# dequantized float32; PRODUCT would compound the per-hop error
# multiplicatively, so it stays exact)
_QUANT_OPS = (ReduceOp.SUM, ReduceOp.MEAN, ReduceOp.MAX, ReduceOp.MIN)


def _quant_np(x: np.ndarray):
    """Block-scaled symmetric int8 (numpy twin of
    xla_backend.quantize_blocks — same block size and scale rule, so the
    host-ring and device-ring formats agree, and so does the analytic
    error bound): flat float32 [n] (n % QUANT_BLOCK == 0) ->
    (int8 [n], float32 scales [n // QUANT_BLOCK])."""
    b = x.reshape(-1, QUANT_BLOCK)
    absmax = np.max(np.abs(b), axis=1)
    scale = np.where(absmax > 0, absmax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.rint(b / scale[:, None]), -127, 127).astype(np.int8)
    return q.reshape(-1), scale


def _dequant_np(q: np.ndarray, scale: np.ndarray) -> np.ndarray:
    return (q.reshape(-1, QUANT_BLOCK).astype(np.float32)
            * scale[:, None]).reshape(-1)


def _send_msg(sock: socket.socket, header: dict, payload: bytes = b""):
    h = msgpack.packb(header, use_bin_type=True)
    sock.sendall(_HDR.pack(len(h)) + h + _HDR.pack(len(payload)) + payload)


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("collective peer disconnected")
        buf.extend(chunk)
    return bytes(buf)


def _recv_msg(sock: socket.socket):
    (hlen,) = _HDR.unpack(_recv_exact(sock, 4))
    header = msgpack.unpackb(_recv_exact(sock, hlen), raw=False)
    (plen,) = _HDR.unpack(_recv_exact(sock, 4))
    payload = _recv_exact(sock, plen) if plen else b""
    return header, payload


def _arr_meta(arr: np.ndarray) -> dict:
    return {"dtype": arr.dtype.str, "shape": list(arr.shape)}


def _arr_from(meta: dict, payload: bytes) -> np.ndarray:
    return np.frombuffer(payload, dtype=np.dtype(meta["dtype"])).reshape(
        meta["shape"]).copy()


def _reduce(arrays: list[np.ndarray], op: ReduceOp) -> np.ndarray:
    if op == ReduceOp.MEAN:
        return np.mean(np.stack(arrays), axis=0)
    ufunc = getattr(np, _NUMPY_REDUCE[ReduceOp(op)])
    out = arrays[0].copy()
    for arr in arrays[1:]:
        out = ufunc(out, arr)
    return out


class _CollectiveState:
    """Hub-side shared op table. contribute() blocks until the op's result
    is ready; the last contributor computes it."""

    def __init__(self, world_size: int, sweep_timeout: float = 600.0):
        self.world_size = world_size
        self.sweep_timeout = sweep_timeout
        self.lock = threading.Lock()
        self.cv = threading.Condition(self.lock)
        self.ops: dict[int, dict] = {}
        self.mailboxes: dict[tuple[int, int, int], tuple[dict, bytes]] = {}

    def _sweep_locked(self):
        """Completed-but-unread ops leak when a rank dies after
        contributing but before reading (e.g. rank 0 interrupted inside
        its local contribute — its arrival completes the op later, but
        its reader slot never fills, so `readers` can't reach
        world_size). Drop done ops past the sweep deadline, mirroring
        the timeout-withdraw path for incomplete ones."""
        now = time.monotonic()
        dead = [op_id for op_id, op in self.ops.items()
                if op.get("done")
                and now - op.get("done_at", now) > self.sweep_timeout]
        for op_id in dead:
            del self.ops[op_id]

    def contribute(self, op_id: int, kind: str, rank: int, meta: dict,
                   payload: bytes, timeout: float = 300.0):
        with self.cv:
            self._sweep_locked()
            op = self.ops.setdefault(op_id, {"arrivals": {}, "result": None,
                                             "done": False})
            op["arrivals"][rank] = (kind, meta, payload)
            if len(op["arrivals"]) == self.world_size:
                try:
                    op["result"] = self._compute(kind, op["arrivals"])
                except Exception as e:  # mismatched kinds/dtypes: surface
                    op["error"] = str(e)  # to every rank, don't hang them
                op["done"] = True
                op["done_at"] = time.monotonic()
                self.cv.notify_all()
            else:
                deadline = time.monotonic() + timeout
                while not op["done"]:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        # Withdraw this rank's contribution so a late
                        # straggler can't complete the op with data the
                        # timed-out ranks already abandoned (silent
                        # divergence); last withdrawer frees the op.
                        op["arrivals"].pop(rank, None)
                        if not op["arrivals"]:
                            self.ops.pop(op_id, None)
                        raise TimeoutError(
                            f"collective op {op_id} ({kind}) timed out: "
                            f"{len(op['arrivals'])}/{self.world_size} arrived")
                    self.cv.wait(remaining)
            result = op["result"]
            err = op.get("error")
            # last reader cleans up (pop: the sweep may have beaten us)
            op.setdefault("readers", set()).add(rank)
            if len(op["readers"]) == self.world_size:
                self.ops.pop(op_id, None)
        if err is not None:
            raise ValueError(f"collective op {op_id} failed: {err}")
        return result

    def _compute(self, kind: str, arrivals: dict):
        ranks = sorted(arrivals)
        kinds = {arrivals[r][0] for r in ranks}
        if len(kinds) != 1:  # not an assert: must survive python -O —
            # this is the loud-failure net for route divergence
            raise ValueError(f"mismatched collective kinds: {kinds}")
        metas = {r: arrivals[r][1] for r in ranks}
        payloads = {r: arrivals[r][2] for r in ranks}
        if kind == "barrier":
            return {"kind": "barrier"}
        if kind == "broadcast":
            src = metas[ranks[0]]["src"]
            return {"kind": "bcast", "meta": metas[src],
                    "payload": payloads[src]}
        if kind in ("allreduce", "reduce"):
            op = ReduceOp(metas[ranks[0]]["op"])
            arrays = [_arr_from(metas[r], payloads[r]) for r in ranks]
            out = _reduce(arrays, op)
            return {"kind": kind, "meta": _arr_meta(out),
                    "payload": out.tobytes(),
                    "dst": metas[ranks[0]].get("dst", -1)}
        if kind in ("allgather", "allgather_ctl_shm",
                    "allgather_ctl_ring", "allgather_ctl_device",
                    "allgather_ctl_pallas"):
            # ctl kinds: transport-plumbing exchanges (ring addresses,
            # shm ok flags), one kind EACH so a rank whose ROUTE diverged
            # (ragged sizes straddling RING_MIN_BYTES) pairs with a real
            # allgather as a kind mismatch — a loud ValueError on every
            # rank, never a silent payload swap.
            return {"kind": "allgather",
                    "metas": [metas[r] for r in ranks],
                    "payloads": [payloads[r] for r in ranks]}
        if kind == "allgather_meta":
            # metadata-only control round for the ring data plane: a rank
            # that routed the payload to the ring must never pair with a
            # payload-carrying hub allgather (kind mismatch asserts above)
            return {"kind": "allgather",
                    "metas": [metas[r] for r in ranks],
                    "payloads": [b"" for _ in ranks]}
        if kind == "reducescatter":
            op = ReduceOp(metas[ranks[0]]["op"])
            arrays = [_arr_from(metas[r], payloads[r]) for r in ranks]
            out = _reduce(arrays, op)
            chunks = np.array_split(out, len(ranks), axis=0)
            return {"kind": "reducescatter",
                    "metas": [_arr_meta(c) for c in chunks],
                    "payloads": [np.ascontiguousarray(c).tobytes()
                                 for c in chunks]}
        raise ValueError(f"unknown collective kind {kind!r}")

    # p2p
    def post(self, src: int, dst: int, tag: int, meta: dict, payload: bytes):
        with self.cv:
            self.mailboxes[(src, dst, tag)] = (meta, payload)
            self.cv.notify_all()

    def take(self, src: int, dst: int, tag: int, timeout: float = 300.0):
        deadline = time.monotonic() + timeout
        with self.cv:
            while (src, dst, tag) not in self.mailboxes:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError(f"recv from {src} tag {tag} timed out")
                self.cv.wait(remaining)
            return self.mailboxes.pop((src, dst, tag))


class HostGroup:
    def __init__(self, group_name: str, world_size: int, rank: int,
                 timeout: float = 60.0, transport: str = "auto",
                 quantize=None, placement_plan: dict | None = None):
        from ray_tpu.experimental import internal_kv

        self.group_name = group_name
        self.world_size = world_size
        self.rank = rank
        # live-op debug row (debug_state.py; _op_entry maintains it)
        self._dbg: dict = {"op": None, "phase": "idle", "t0": 0.0,
                           "ops_done": 0}
        # Rendezvous AND per-op timeout: ops abort (not hang) when a peer
        # dies mid-collective, so the SGD layer can resize the group.
        self._timeout = timeout
        self._op_id = 0
        self._key = f"collective/{group_name}"
        self._sock: socket.socket | None = None
        self._destroyed = False
        # Data-plane state: force_transport pins every op to one tier
        # (tests/benchmarks); "auto" routes by size and node placement.
        tr = Transport(transport)
        self.force_transport = None if tr == Transport.AUTO else tr.value
        # Placement-derived tier (topology.transport_plan riding the
        # gang's ICI_RING record): pins the transport WITHOUT the probe
        # rounds the auto router pays (shm ok-flag exchange on non-shm
        # groups, device vote). Explicit transport= wins over the plan.
        self._transport_derived = False
        self._placement_plan = placement_plan
        self._probe_rounds = 0  # auto-router discovery rounds paid
        if (placement_plan and placement_plan.get("transport")
                and self.force_transport is None):
            self.force_transport = Transport(
                placement_plan["transport"]).value
            self._transport_derived = True
            from ray_tpu.collective import metrics as _metrics

            _metrics.TRANSPORT_DERIVED.inc()
        # Group-default wire quantization (per-op quantize= overrides)
        self.quantize = normalize_quantize(quantize)
        # DEVICE tier state: built lazily on the first unanimous vote;
        # _device_shaped is the group-uniform round-entry gate, decided
        # ONCE at construction (ranks create the group at the same
        # protocol step, so the multihost-runtime facts they read here
        # agree by contract — a lazy read could catch ranks on opposite
        # sides of a late multihost.initialize); _device_disabled is
        # this rank's veto after a device failure
        self._device = None
        self._device_disabled = False
        self._device_shaped: bool = self._compute_device_shaped()
        # PALLAS (fused-kernel) tier state: same construction-time shape
        # gate as the device tier; _pallas_disabled is this rank's veto
        # after a kernel failure (the device tier stays routable — the
        # planes fail independently)
        self._pallas = None
        self._pallas_disabled = False
        self._shm = None
        self._shm_gen = 0
        self._shm_disabled = False
        self._shm_keys: list[str] = []
        # buffered peer-direct sends awaiting their receiver, ONE per
        # (dst, tag): a re-send overwrites the unclaimed predecessor
        # (hub-mailbox semantics — keeps loop-sends to a wedged receiver
        # from pinning unbounded snapshots/fds); destroy() reaps the rest
        self._p2p_direct: dict[tuple[int, int], socket.socket] = {}
        self._p2p_lock = threading.Lock()
        if world_size == 1:
            self._state = _CollectiveState(1, sweep_timeout=timeout * 2)
            return
        if rank == 0:
            self._state = _CollectiveState(world_size,
                                           sweep_timeout=timeout * 2)
            self._listener = socket.socket()
            self._listener.bind(("127.0.0.1", 0))
            self._listener.listen(world_size)
            port = self._listener.getsockname()[1]
            # group metadata rides the rendezvous KV entry: a derived
            # tier (and its per-rank placement rows) is visible to every
            # joining rank, so an ad-hoc member initialized WITHOUT the
            # plan (probe fallback path) still adopts the gang's tier
            internal_kv._kv_put(
                self._key,
                msgpack.packb({"addr": f"127.0.0.1:{port}",
                               "world_size": world_size,
                               "transport": (self.force_transport
                                             if self._transport_derived
                                             else None),
                               "plan": self._placement_plan}))
            self._conn_threads = []
            accept_thread = threading.Thread(target=self._accept_loop,
                                             daemon=True)
            accept_thread.start()
        else:
            deadline = time.monotonic() + timeout
            info = None
            while time.monotonic() < deadline:
                data = internal_kv._kv_get(self._key)
                if data:
                    info = msgpack.unpackb(data, raw=False)
                    break
                time.sleep(0.05)
            if info is None:
                raise TimeoutError(
                    f"rendezvous for group {group_name!r} timed out")
            if info["world_size"] != world_size:
                raise ValueError("world_size mismatch at rendezvous")
            if (info.get("transport") and self.force_transport is None
                    and not self._transport_derived):
                # adopt the leader's placement-derived tier from the KV
                # metadata (this rank joined without the plan)
                self.force_transport = Transport(info["transport"]).value
                self._transport_derived = True
                self._placement_plan = info.get("plan")
                from ray_tpu.collective import metrics as _metrics

                _metrics.TRANSPORT_DERIVED.inc()
            host, port = info["addr"].rsplit(":", 1)
            self._sock = socket.create_connection((host, int(port)),
                                                  timeout=timeout)
            self._sock.settimeout(None)
            _send_msg(self._sock, {"hello": rank})

    # ---- hub side ----
    def _accept_loop(self):
        joined = 0
        while joined < self.world_size - 1:
            conn, _ = self._listener.accept()
            hello, _ = _recv_msg(conn)
            t = threading.Thread(target=self._serve_conn,
                                 args=(conn, hello["hello"]), daemon=True)
            t.start()
            self._conn_threads.append(t)
            joined += 1

    def _serve_conn(self, conn: socket.socket, peer_rank: int):
        try:
            while True:
                header, payload = _recv_msg(conn)
                kind = header["kind"]
                if kind == "p2p_send":
                    self._state.post(peer_rank, header["dst"], header["tag"],
                                     header["meta"], payload)
                    _send_msg(conn, {"ok": True})
                elif kind == "p2p_recv":
                    try:
                        meta, data = self._state.take(
                            header["src"], peer_rank, header["tag"],
                            timeout=self._timeout)
                    except TimeoutError as e:
                        # TimeoutError is an OSError: without this reply
                        # the outer except would eat it and the client
                        # would block forever on a reply that never comes
                        _send_msg(conn, {"error": str(e), "timeout": True})
                        continue
                    _send_msg(conn, {"meta": meta}, data)
                else:
                    try:
                        result = self._state.contribute(
                            header["op_id"], kind, peer_rank, header["meta"],
                            payload, timeout=self._timeout)
                    except Exception as e:
                        _send_msg(conn, {
                            "error": str(e),
                            "timeout": isinstance(e, TimeoutError)})
                        continue
                    reply, data = self._slice_result(result, peer_rank, kind)
                    _send_msg(conn, reply, data)
        except (ConnectionError, OSError):
            pass

    @staticmethod
    def _slice_result(result: dict, rank: int, kind: str):
        if result["kind"] == "barrier":
            return {"barrier": True}, b""
        if result["kind"] in ("bcast", "allreduce"):
            return {"meta": result["meta"]}, result["payload"]
        if result["kind"] == "reduce":
            if rank == result["dst"]:
                return {"meta": result["meta"]}, result["payload"]
            return {"meta": None}, b""
        if result["kind"] == "allgather":
            return ({"metas": result["metas"],
                     "sizes": [len(p) for p in result["payloads"]]},
                    b"".join(result["payloads"]))
        if result["kind"] == "reducescatter":
            return {"meta": result["metas"][rank]}, result["payloads"][rank]
        raise ValueError(result["kind"])

    # ---- participant ----
    def _next_op(self) -> int:
        self._op_id += 1
        return self._op_id

    def debug_state(self) -> dict:
        """Msgpack-safe live row: which op this rank is inside, at which
        transport phase, for how long (the stall doctor's collective
        feed; also attached to group-timeout errors by _op_entry)."""
        dbg = self._dbg
        op = dbg.get("op")
        return {
            "group": self.group_name,
            "rank": self.rank,
            "world_size": self.world_size,
            "backend": "host",
            "transport": self._forced() or "auto",
            "transport_derived": self._transport_derived,
            "probe_rounds": self._probe_rounds,
            "quantize": self.quantize or "",
            "op": op or "",
            "phase": dbg.get("phase", "idle"),
            "age_s": (round(time.monotonic() - dbg["t0"], 3)
                      if op else 0.0),
            "ops_done": dbg.get("ops_done", 0),
            "op_seq": self._op_id,
            "timeout_s": self._timeout,
        }

    def _collective(self, kind: str, meta: dict, payload: bytes):
        self._dbg["phase"] = f"hub:{kind}"
        op_id = self._next_op()
        if self.rank == 0 or self.world_size == 1:
            result = self._state.contribute(op_id, kind, 0, meta, payload,
                                            timeout=self._timeout)
            return self._slice_result(result, 0, kind)
        _send_msg(self._sock, {"kind": kind, "op_id": op_id, "meta": meta},
                  payload)
        reply, data = _recv_msg(self._sock)
        if "error" in reply:
            if reply.get("timeout", True):
                raise TimeoutError(reply["error"])
            raise ValueError(reply["error"])
        return reply, data

    def _hub_allgather(self, arr: np.ndarray,
                       kind: str = "allgather") -> list[np.ndarray]:
        reply, data = self._collective(kind, _arr_meta(arr),
                                       arr.tobytes())
        out, offset = [], 0
        for m, size in zip(reply["metas"], reply["sizes"]):
            out.append(_arr_from(m, data[offset:offset + size]))
            offset += size
        return out

    def _hub_allgather_meta(self, arr: np.ndarray) -> list[dict]:
        """Metadata-only allgather (control round for the ring plane)."""
        reply, _ = self._collective("allgather_meta", _arr_meta(arr), b"")
        return reply["metas"]

    # ---- transport routing ----
    # The hub is latency-optimal for control-sized tensors but serializes
    # all-to-hub bandwidth through one socket — wrong for gradients
    # (reference role: gloo's ring algorithms behind torch.distributed).
    # Large tensors take the shm segment when the whole group shares a
    # node, else the direct rank-to-rank TCP ring.

    RING_MIN_BYTES = 1 << 16
    _PIPE_BYTES = 1 << 18  # ring pipeline slice: reduce(k) overlaps recv(k+1)
    # PALLAS tier size ceiling: only small latency-critical ops (the
    # decode-step allreduce regime) take the fused kernel; larger
    # payloads fall through to DEVICE, whose shard_map pipeline is the
    # bandwidth shape. Group-uniform by the collective contract (same-
    # geometry payloads; ragged allgather is caught by the meta round).
    PALLAS_MAX_BYTES = int(os.environ.get(
        "RAY_TPU_COLLECTIVE_PALLAS_MAX_KB", "64")) << 10
    # Segments grow by rebuild but never shrink, so one oversize op would
    # pin (w+2)*slot of tmpfs for the group's life; above the cap the
    # ring carries the op with no resident cost. Forced shm overrides.
    SHM_MAX_SLOT_BYTES = int(os.environ.get(
        "RAY_TPU_COLLECTIVE_SHM_MAX_MB", "32")) << 20

    def _forced(self) -> str | None:
        f = self.force_transport or os.environ.get(
            "RAY_TPU_COLLECTIVE_TRANSPORT", "")
        f = (f or "").strip().lower()
        if not f or f == Transport.AUTO.value:
            return None
        return Transport(f).value  # validates the name

    def _route(self, arr: np.ndarray) -> list[str]:
        """Ordered transport candidates for one op. All ranks compute the
        same route (collectives pass same-geometry tensors by contract;
        ragged allgather is caught by the allgather_meta control round)."""
        f = self._forced()
        if f:
            return [f]
        if (self._destroyed or self.world_size == 1 or arr.ndim == 0
                or arr.ndim > 24 or arr.nbytes < self.RING_MIN_BYTES):
            return [Transport.HUB.value]
        tiers = []
        if (not self._shm_disabled
                and arr.nbytes <= self.SHM_MAX_SLOT_BYTES):
            tiers.append(Transport.SHM.value)
        if self.world_size > 2:  # 2-rank ring degenerates to pairwise
            tiers.append(Transport.RING.value)
        tiers.append(Transport.HUB.value)
        return tiers

    def _forced_unavailable(self, tr: str):
        if self._forced() == tr:
            raise RuntimeError(
                f"forced collective transport {tr!r} is unavailable for "
                f"group {self.group_name!r} (world={self.world_size})")

    def _demote_derived(self) -> None:
        """A placement-DERIVED pin (not user-forced) turned out
        unbuildable on this rank's runtime: fall back to auto routing.
        Only called at group-uniform decision points (device shape
        check, post-allgather vote result, the shm ok-flag exchange),
        so every rank demotes in the same op and the routes stay
        aligned."""
        logger.warning(
            "group %s: placement-derived transport %r unavailable; "
            "demoting to auto routing", self.group_name,
            self.force_transport)
        self.force_transport = None
        self._transport_derived = False

    def _tier_unavailable(self, tr: str) -> bool:
        """A routed tier could not be built. A placement-derived pin is
        SOFT: demote to auto routing and tell the caller to re-route
        (returns True). A user-forced pin raises."""
        if self._transport_derived and self.force_transport == tr:
            self._demote_derived()
            return True
        self._forced_unavailable(tr)
        return False

    @staticmethod
    def _abort_not_hang(e: Exception):
        """Normalize transport failures: a dead/stalled peer surfaces as
        TimeoutError on every survivor (the contract the SGD resize path
        keys on); programmer errors (dtype/shape mismatch) pass through."""
        if isinstance(e, (ConnectionError, OSError)) and not isinstance(
                e, TimeoutError):
            raise TimeoutError(f"collective aborted: {e}") from e
        raise e

    # ---- device (ICI/XLA) data plane ----

    @staticmethod
    def _is_device_array(arr) -> bool:
        from ray_tpu.collective.types import is_jax_array

        return is_jax_array(arr)

    def _to_host(self, arr) -> np.ndarray:
        if not isinstance(arr, np.ndarray):
            arr = np.asarray(arr)  # device arrays fall back to host here
        return np.ascontiguousarray(arr)

    def _quantize_mode(self, quantize):
        """Per-op override (False forces exact) else the group default."""
        return (self.quantize if quantize is None
                else normalize_quantize(quantize))

    def _compute_device_shaped(self) -> bool:
        """Whether this GROUP enters the per-op device vote round. Only
        stable, group-uniform facts are read — the multihost runtime
        being active and sized to the group is the same on every rank
        at group creation by contract, so every rank enters (or skips)
        the ctl round together. Volatile, rank-local facts
        (rank/process_index alignment, a one-sided device failure)
        express themselves as a 0 VOTE inside the round instead, so
        they degrade to a clean host-tier fallback rather than a
        ctl-kind mismatch. Free for plain host groups — the multihost
        flag check short-circuits before jax is touched."""
        if self.world_size <= 1:
            return False
        try:
            from ray_tpu.parallel import multihost

            if not multihost.is_initialized():
                return False
            import jax

            return jax.process_count() == self.world_size
        except Exception:
            return False

    def _device_group_shaped(self) -> bool:
        return bool(self._device_shaped) and not self._destroyed

    def _ensure_device(self):
        if self._device is None:
            from ray_tpu.collective.backends.xla_backend import (
                DeviceTransport)

            # raises when rank != process_index — surfaces as a 0 vote
            self._device = DeviceTransport(self.world_size, self.rank)
        return self._device

    def _device_route(self, arr) -> bool:
        """Per-op DEVICE-tier agreement. True when EVERY rank voted
        device (its payload is a jax.Array of a device-safe dtype, or
        the tier is forced). The vote rides a 1-byte hub ctl round with
        its own kind tag — like the shm ok-flag exchange — so a rank
        whose route diverged pairs as a loud kind mismatch, never a
        silent payload swap. Only multihost-shaped groups pay the
        round; any host-array (or device-incapable) rank vetoes and
        every rank falls back together."""
        forced = self._forced()
        # a PALLAS pin is a refinement of the device plane: ops above
        # pallas_max_bytes and op kinds the kernel tier does not carry
        # fall through HERE, so the pin behaves like a device pin for
        # them instead of raising
        device_like = (Transport.DEVICE.value, Transport.PALLAS.value)
        if forced is not None and forced not in device_like:
            return False
        if not self._device_group_shaped():
            if forced in device_like:
                # the shape gate is decided once at construction and is
                # group-uniform by contract, so a derived-pin demotion
                # here happens on every rank together
                self._tier_unavailable(forced)
            return False
        self._dbg["phase"] = "device_vote"
        self._probe_rounds += 1
        if _fp.ARMED:
            # fires BEFORE the agreement round: a rank hard-killed here
            # leaves every survivor timing out in the hub exchange
            # (abort-not-hang). Once ranks enter the XLA dispatch the op
            # inherits the device runtime's own failure detection.
            _fp.fire_strict("collective.device_dispatch")
        vote = 0
        if not self._device_disabled and (
                forced in device_like
                or self._is_device_array(arr)):
            try:
                dev = self._ensure_device()
                vote = 1 if dev.dtype_ok(arr.dtype) else 0
            except Exception:
                self._device_disabled = True
        flags = self._hub_allgather(np.array([vote], np.uint8),
                                    kind="allgather_ctl_device")
        agreed = all(int(f[0]) for f in flags)
        if not agreed and forced == Transport.DEVICE.value:
            if self._transport_derived:
                # the vote result is an allgather — identical on every
                # rank, so a derived pin demotes in unison here
                self._demote_derived()
                return False
            raise RuntimeError(
                f"forced collective transport 'device' is unavailable "
                f"for group {self.group_name!r}: the placement/dtype "
                f"vote was not unanimous")
        return agreed

    def _device_op(self, fn):
        from ray_tpu.collective import metrics  # noqa: F401 (register)

        self._dbg["phase"] = "device"
        try:
            return fn()
        except Exception as e:
            # a failed/interrupted device op leaves the runtime's
            # collective state unknown: stop routing this group to the
            # device plane and surface abort-not-hang semantics
            self._device_disabled = True
            self._abort_not_hang(e)

    def _ensure_pallas(self):
        if self._pallas is None:
            from ray_tpu.collective.backends.pallas_backend import (
                PallasTransport, pallas_supported)

            # either raise surfaces as a 0 vote: the tier is unavailable
            # in this process (a live TPU backend), or rank !=
            # process_index
            if not pallas_supported():
                raise RuntimeError("pallas tier unavailable here")
            self._pallas = PallasTransport(self.world_size, self.rank)
        return self._pallas

    def _pallas_route(self, arr) -> bool:
        """Per-op PALLAS-tier agreement, mirroring _device_route: a
        1-byte hub ctl round with its own kind tag decides whether
        EVERY rank runs the fused kernel. Ops above PALLAS_MAX_BYTES
        skip the round entirely and fall through to _device_route —
        the threshold reads only the local payload size, which is
        group-uniform for collectives by contract, so every rank skips
        (or votes) together."""
        forced = self._forced()
        if forced is not None and forced != Transport.PALLAS.value:
            return False
        if not self._device_group_shaped():
            if forced == Transport.PALLAS.value:
                self._tier_unavailable(forced)
            return False
        if getattr(arr, "nbytes", 0) > self.PALLAS_MAX_BYTES:
            # large ops fall through to the DEVICE tier (a forced
            # pallas pin is device-like there), keeping the kernel
            # tier on the latency-critical small-op path it was built
            # for
            return False
        self._dbg["phase"] = "pallas_vote"
        self._probe_rounds += 1
        if _fp.ARMED:
            # fires BEFORE the agreement round, like
            # collective.device_dispatch: a rank hard-killed here
            # leaves every survivor timing out in the hub exchange
            # (abort-not-hang)
            _fp.fire_strict("collective.pallas_dispatch")
        vote = 0
        if not self._pallas_disabled and (
                forced == Transport.PALLAS.value
                or self._is_device_array(arr)):
            try:
                pal = self._ensure_pallas()
                vote = 1 if pal.dtype_ok(arr.dtype) else 0
            except Exception:
                self._pallas_disabled = True
        flags = self._hub_allgather(np.array([vote], np.uint8),
                                    kind="allgather_ctl_pallas")
        agreed = all(int(f[0]) for f in flags)
        if not agreed and forced == Transport.PALLAS.value:
            if self._transport_derived:
                # the vote result is an allgather — identical on every
                # rank, so a derived pin demotes in unison here
                self._demote_derived()
                return False
            raise RuntimeError(
                f"forced collective transport 'pallas' is unavailable "
                f"for group {self.group_name!r}: the placement/dtype "
                f"vote was not unanimous")
        return agreed

    def _pallas_op(self, fn):
        from ray_tpu.collective import metrics  # noqa: F401 (register)

        self._dbg["phase"] = "pallas"
        try:
            return fn()
        except Exception as e:
            # the kernel tier fails independently of the device plane:
            # disable only pallas so the next op can still vote device
            self._pallas_disabled = True
            self._abort_not_hang(e)

    def _shm_op(self, fn):
        self._dbg["phase"] = "shm"
        try:
            return fn()
        except Exception as e:
            # any failure mid-op leaves ranks at different barrier phases:
            # poison the segment (peers abort, not hang) and never reuse it
            t, self._shm = self._shm, None
            if t is not None:
                try:
                    t.abort()
                finally:
                    # EVERY survivor unlinks, not just rank 0: if the
                    # crash that tripped this op was rank 0 dying between
                    # segment map and its post-fence unlink, nobody else
                    # would ever remove the file and the tmpfs bytes leak
                    # forever (unlink is idempotent; live mappings keep
                    # their pages until released)
                    t.close(unlink=True)
            self._shm_disabled = True
            self._abort_not_hang(e)

    def _ring_op(self, fn):
        self._dbg["phase"] = "ring"
        try:
            return fn()
        except Exception as e:
            # a failed ring op leaves peers at different steps: the
            # connections are unusable, rebuild from scratch next op
            self._ring_teardown()
            self._abort_not_hang(e)

    # ---- shm data plane ----

    @staticmethod
    def _node_token() -> str | None:
        try:
            from ray_tpu._private import global_state

            cw = global_state.get_core_worker()
            if cw is not None and cw.node_id is not None:
                return cw.node_id.hex()
        except Exception:
            pass
        return None

    def _ensure_shm(self, need_bytes: int):
        """Map (or grow) the group's shared segment. Every rank computes
        the same need (collective contract), so rebuild generations stay
        aligned without extra coordination; the ok-flag allgather through
        the hub makes enable/disable unanimous."""
        if self._shm_disabled or self.world_size == 1 or self._destroyed:
            return None
        if (need_bytes > self.SHM_MAX_SLOT_BYTES
                and self._forced() != Transport.SHM.value):
            # result-dtype promotion (e.g. int8 MEAN -> float64) can
            # inflate the slot well past the routed nbytes; enforce the
            # tmpfs budget on the real slot need (forced shm overrides)
            return None
        if self._shm is not None and self._shm.slot_bytes >= need_bytes:
            return self._shm
        from ray_tpu.collective.backends.shm_transport import ShmTransport
        from ray_tpu.experimental import internal_kv
        from ray_tpu.native.store import is_shared_memory_path

        if self._shm is not None:  # grow: all ranks rebuild together
            self._shm.close()
            self._shm = None
        slot = max(1 << 20, 1 << (need_bytes - 1).bit_length())
        if self._forced() != Transport.SHM.value:
            # auto-routing discovery: the ok-flag exchange below is a
            # probe round (a placement-derived/forced shm group pays
            # the segment setup but not a *probe* — the tier was known)
            self._probe_rounds += 1
        self._shm_gen += 1
        key = f"{self._key}/shm{self._shm_gen}"
        seg, ok = None, 0
        if self.rank == 0:
            self._shm_keys.append(key)  # destroy() clears even fail markers
        try:
            if self.rank == 0:
                cookie = os.urandom(16)
                name = (f"{self.group_name}_g{self._shm_gen}_"
                        f"{cookie.hex()[:8]}.seg")
                try:
                    seg = ShmTransport.create(name, cookie, self.world_size,
                                              0, slot, self._timeout)
                    token = self._node_token()
                    if token is None and not is_shared_memory_path(seg.path):
                        # without a node id, only /dev/shm placement
                        # proves the mapping is node-local memory
                        raise RuntimeError(
                            "no node identity and segment not on /dev/shm")
                    internal_kv._kv_put(key, msgpack.packb(
                        {"path": seg.path, "cookie": cookie, "slot": slot,
                         "node": token}, use_bin_type=True))
                except Exception:
                    internal_kv._kv_put(key, msgpack.packb(
                        {"fail": True}, use_bin_type=True))
                    raise
            else:
                deadline = time.monotonic() + self._timeout
                info = None
                while time.monotonic() < deadline:
                    data = internal_kv._kv_get(key)
                    if data:
                        info = msgpack.unpackb(data, raw=False)
                        break
                    time.sleep(0.02)
                if info is None:
                    raise TimeoutError("shm segment rendezvous timed out")
                if info.get("fail"):
                    raise RuntimeError("rank 0 could not create the segment")
                token = self._node_token()
                if info["node"] is not None and token is not None:
                    if info["node"] != token:
                        raise RuntimeError(
                            "rank is on a different node than rank 0")
                elif not is_shared_memory_path(info["path"]):
                    raise RuntimeError(
                        "cannot prove node locality for shm segment")
                seg = ShmTransport.open(info["path"], info["cookie"],
                                        self.world_size, self.rank,
                                        info["slot"], self._timeout)
            ok = 1
        except Exception:
            ok = 0
        try:
            flags = self._hub_allgather(np.array([ok], np.uint8),
                                        kind="allgather_ctl_shm")
        except BaseException:
            if seg is not None:
                # every survivor unlinks: rank 0 (the owner) may be the
                # peer that just died mid-exchange
                seg.close(unlink=True)
            raise
        if all(int(f[0]) for f in flags):
            try:
                seg.barrier()  # join fence: everyone mapped before first op
            except BaseException:
                # a peer died between the flag exchange and the fence:
                # every survivor unlinks (rank 0 may BE the dead peer —
                # its segment file must not outlive the group)
                seg.close(unlink=True)
                self._shm_disabled = True
                raise
            if self.rank == 0:
                # every rank is mapped (the fence proves it) and nothing
                # reopens this generation: unlink NOW so the tmpfs bytes
                # die with the last mapping even if rank 0 is SIGKILLed
                try:
                    os.unlink(seg.path)
                except OSError:
                    pass
            self._shm = seg
            return seg
        if seg is not None:
            seg.close()
        self._shm_disabled = True  # unanimous: don't pay the probe again
        return None

    def _shm_need(self, arr: np.ndarray, op: ReduceOp | None) -> int:
        """Slot bytes that fit both the contribution and half the result
        region (the result region is 2 slots; MEAN promotes integers to
        float64, which can outgrow the input slot)."""
        from ray_tpu.collective.backends.shm_transport import result_dtype

        need = arr.nbytes
        if op is not None:
            need = max(need, (arr.size * result_dtype(arr.dtype, op).itemsize
                              + 1) // 2)
        return max(need, 1)

    # ---- ring data plane ----

    def _ensure_ring(self) -> bool:
        if self.world_size <= 2:
            return False  # ring degenerates to pairwise; hub is fine
        if getattr(self, "_ring_next", None) is not None:
            return True
        listener = socket.socket()
        listener.bind(("127.0.0.1", 0))
        listener.listen(2)
        port = listener.getsockname()[1]
        addr = f"127.0.0.1:{port}".encode().ljust(32, b"\0")
        addrs = self._hub_allgather(np.frombuffer(addr, np.uint8),
                                    kind="allgather_ctl_ring")
        nxt = bytes(addrs[(self.rank + 1) % self.world_size]
                    ).rstrip(b"\0").decode()
        host, p = nxt.rsplit(":", 1)

        out: dict = {}

        lock = threading.Lock()

        def _connect():
            try:
                sock = socket.create_connection(
                    (host, int(p)), timeout=self._timeout)
            except OSError as e:  # surfaced by the join below
                out["err"] = e
                return
            with lock:
                if out.get("abandoned"):  # caller already gave up
                    sock.close()
                else:
                    out["sock"] = sock

        t = threading.Thread(target=_connect, daemon=True)
        t.start()
        prev_sock = None
        try:
            listener.settimeout(self._timeout)
            prev_sock, _ = listener.accept()
            # keep the configured timeout on both ring sockets so a
            # stalled (connected but silent) peer raises socket.timeout
            # instead of hanging recv forever — abort-not-hang applies
            # to the data plane
            prev_sock.settimeout(self._timeout)
            t.join(self._timeout)
            with lock:
                if "sock" not in out:
                    out["abandoned"] = True  # late connect self-closes
                    raise ConnectionError(
                        f"ring connect to rank "
                        f"{(self.rank + 1) % self.world_size}"
                        f" failed: {out.get('err')}")
        except BaseException:
            if prev_sock is not None:
                prev_sock.close()
            sock = out.get("sock")
            if sock is not None:
                sock.close()
            raise
        finally:
            listener.close()
        out["sock"].settimeout(self._timeout)
        # pipelined slices are small; don't let Nagle hold the tail
        for s in (out["sock"], prev_sock):
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._ring_next = out["sock"]
        self._ring_prev = prev_sock
        return True

    def _ring_teardown(self):
        """Close and forget both ring sockets. A failed ring op leaves
        peers at different steps, so the connections are unusable; the
        next large allreduce rebuilds the ring from scratch (or fails the
        collective setup, which the caller handles)."""
        for name in ("_ring_next", "_ring_prev"):
            sock = getattr(self, name, None)
            if sock is not None:
                try:
                    sock.close()
                except Exception:
                    pass
            setattr(self, name, None)

    # -- pipelined zero-copy ring --------------------------------------

    def _ring_recv_into(self, mv: memoryview):
        sock = self._ring_prev
        got, n = 0, len(mv)
        while got < n:
            r = sock.recv_into(mv[got:], n - got)
            if not r:
                raise ConnectionError("collective peer disconnected")
            got += r

    def _ring_send_async(self, send_mv: memoryview):
        """Stream a work-buffer slice to the next rank in _PIPE_BYTES
        pieces (memoryview slices — no tobytes copy), off-thread so the
        caller can consume the previous rank's stream concurrently.
        Small steps send inline: a <=16KB sendall into a peer buffer
        that the previous step fully drained cannot block (SO_SNDBUF
        floors are far larger), and skipping the thread keeps
        just-over-threshold collectives from paying thread churn per
        step."""
        if not len(send_mv):
            return None, []
        if len(send_mv) <= (1 << 14):
            self._ring_next.sendall(send_mv)
            return None, []
        err: list = []

        def _send():
            try:
                off, n = 0, len(send_mv)
                while off < n:
                    self._ring_next.sendall(
                        send_mv[off:off + self._PIPE_BYTES])
                    off += self._PIPE_BYTES
            except Exception as e:
                err.append(e)

        t = threading.Thread(target=_send, daemon=True)
        t.start()
        return t, err

    def _ring_join(self, t, err):
        if t is None:
            return
        t.join(self._timeout)
        if t.is_alive() or err:
            raise TimeoutError(
                f"ring send stalled/failed: {err or 'timeout'}")

    def _ring_step_reduce(self, send_mv: memoryview, dst: np.ndarray,
                          scratch: np.ndarray, combine):
        """One pipelined reduce step: stream `send_mv` out while pulling
        dst.nbytes from prev in slices; each slice is combined into `dst`
        the moment it lands, so the reduce of slice k overlaps the
        receive of slice k+1 (the peer keeps filling the socket buffer
        while we compute). No frame headers: both sides derive the same
        chunk schedule, so the stream is self-describing."""
        t, err = self._ring_send_async(send_mv)
        smv = memoryview(scratch).cast("B")
        isz = dst.itemsize
        total, off = dst.nbytes, 0
        while off < total:
            n = min(self._PIPE_BYTES, total - off)
            self._ring_recv_into(smv[:n])
            k = n // isz
            lo = off // isz
            combine(dst[lo:lo + k], scratch[:k], out=dst[lo:lo + k])
            off += n
        self._ring_join(t, err)

    def _ring_step_gather(self, send_mv: memoryview, recv_mv: memoryview):
        """One pipelined gather step: stream out while receiving straight
        into the destination region (recv_into — zero-copy)."""
        t, err = self._ring_send_async(send_mv)
        self._ring_recv_into(recv_mv)
        self._ring_join(t, err)

    def _prep_ring_work(self, arr: np.ndarray, op: ReduceOp):
        flat = arr.reshape(-1)
        # MEAN matches hub np.mean semantics: float64 accumulate and a
        # float result for integer inputs (also dodges overflow), f32
        # intermediates for f16 (np.mean does the same; a raw f16 add
        # chain loses whole units at a few thousand)
        if op == ReduceOp.MEAN and not np.issubdtype(arr.dtype,
                                                     np.floating):
            work = flat.astype(np.float64)
        elif op == ReduceOp.MEAN and arr.dtype == np.float16:
            work = flat.astype(np.float32)
        else:
            work = flat.copy()
        combine = getattr(
            np, _NUMPY_REDUCE[ReduceOp.SUM if op == ReduceOp.MEAN
                              else ReduceOp(op)])
        return work, combine

    def _ring_scratch(self, work: np.ndarray, bounds: list[int]):
        maxel = max((bounds[i + 1] - bounds[i] for i in range(len(bounds) - 1)),
                    default=0)
        n = min(maxel, self._PIPE_BYTES // work.itemsize)
        return np.empty(max(n, 1), work.dtype)

    def _ring_reduce_scatter_phase(self, work, bounds, combine, scratch,
                                   delta: int):
        """w-1 pipelined reduce steps; with delta=0 rank r ends holding
        reduced chunk r+1 (the allreduce schedule), with delta=-1 it ends
        holding chunk r (the reducescatter schedule)."""
        w = self.world_size
        wv = memoryview(work).cast("B")
        isz = work.itemsize

        def mv(i):
            i %= w
            return wv[bounds[i] * isz:bounds[i + 1] * isz]

        def el(i):
            i %= w
            return work[bounds[i]:bounds[i + 1]]

        for step in range(w - 1):
            send_i = self.rank - step + delta
            recv_i = send_i - 1
            self._ring_step_reduce(mv(send_i), el(recv_i), scratch, combine)

    def _ring_allreduce_pipelined(self, arr: np.ndarray,
                                  op: ReduceOp) -> np.ndarray:
        from ray_tpu.collective.backends.shm_transport import split_bounds

        w = self.world_size
        work, combine = self._prep_ring_work(arr, op)
        bounds = split_bounds(work.size, w)
        scratch = self._ring_scratch(work, bounds)
        self._ring_reduce_scatter_phase(work, bounds, combine, scratch, 0)
        wv = memoryview(work).cast("B")
        isz = work.itemsize

        def mv(i):
            i %= w
            return wv[bounds[i] * isz:bounds[i + 1] * isz]

        for step in range(w - 1):  # allgather of reduced chunks
            self._ring_step_gather(mv(self.rank + 1 - step),
                                   mv(self.rank - step))
        if op == ReduceOp.MEAN:
            work = work / w  # float result, like the hub's np.mean
            if arr.dtype == np.float16:
                work = work.astype(np.float16)  # f32 accumulate, f16 out
        return work.reshape(arr.shape)

    def _ring_reducescatter_pipelined(self, arr: np.ndarray,
                                      op: ReduceOp) -> np.ndarray:
        from ray_tpu.collective.backends.shm_transport import split_bounds

        w = self.world_size
        work, combine = self._prep_ring_work(arr, op)
        # hub semantics: np.array_split along axis 0 — row blocks are
        # contiguous element ranges in C order
        rows = arr.shape[0] if arr.ndim else 1
        rowsz = arr.size // rows if rows else 0
        rb = split_bounds(rows, w)
        bounds = [r * rowsz for r in rb]
        scratch = self._ring_scratch(work, bounds)
        self._ring_reduce_scatter_phase(work, bounds, combine, scratch, -1)
        res = work[bounds[self.rank]:bounds[self.rank + 1]]
        if op == ReduceOp.MEAN:
            res = res / w
            if arr.dtype == np.float16:
                res = res.astype(np.float16)  # f32 accumulate, f16 out
        return res.reshape((rb[self.rank + 1] - rb[self.rank],)
                           + arr.shape[1:]).copy()

    def _ring_allgather_pipelined(self, arr: np.ndarray):
        """Block-rotation allgather over uniform-shape contributions
        (the caller's meta round guarantees uniformity)."""
        w = self.world_size
        n = arr.nbytes
        out = np.empty(w * arr.size, arr.dtype)
        ov = memoryview(out).cast("B")
        ov[self.rank * n:(self.rank + 1) * n] = memoryview(arr).cast("B")

        def mv(i):
            i %= w
            return ov[i * n:(i + 1) * n]

        for step in range(w - 1):
            self._ring_step_gather(mv(self.rank - step),
                                   mv(self.rank - step - 1))
        return [out[i * arr.size:(i + 1) * arr.size].reshape(arr.shape)
                for i in range(w)]

    def _ring_broadcast_pipelined(self, arr: np.ndarray,
                                  src_rank: int) -> np.ndarray:
        """Pipelined relay chain src → src+1 → … → src-1: each slice is
        forwarded the moment it lands, so after the w-hop fill the whole
        chain streams concurrently. Acyclic per slice — no deadlock."""
        w = self.world_size
        out = arr if self.rank == src_rank else np.empty_like(arr)
        ov = memoryview(out).cast("B")
        do_recv = self.rank != src_rank
        do_send = (self.rank + 1) % w != src_rank
        total, off = out.nbytes, 0
        while off < total:
            n = min(self._PIPE_BYTES, total - off)
            if do_recv:
                self._ring_recv_into(ov[off:off + n])
            if do_send:
                self._ring_next.sendall(ov[off:off + n])
            off += n
        # fresh writable result on every rank/tier, like the hub
        return out.copy() if out is arr else out

    # -- quantized (int8 block-scaled) pipelined ring ------------------

    def _fire_quantize(self):
        if _fp.ARMED:
            _fp.fire_strict("collective.quantize")

    def _ring_send_seq_async(self, parts: list[memoryview]):
        """Stream a sequence of buffers (scales header, then payload) to
        the next rank in order. Like _ring_send_async, tiny totals send
        inline; anything larger rides one thread — the HEADER must not
        be a blocking main-thread sendall, or every rank can sit in it
        simultaneously once scales outgrow the socket buffers (circular
        stall, spurious timeout) while nobody drains its peer."""
        if sum(len(p) for p in parts) <= (1 << 14):
            for p in parts:
                self._ring_next.sendall(p)
            return None, []
        err: list = []

        def _send():
            try:
                for p in parts:
                    off, n = 0, len(p)
                    while off < n:
                        self._ring_next.sendall(
                            p[off:off + self._PIPE_BYTES])
                        off += self._PIPE_BYTES
            except Exception as e:
                err.append(e)

        t = threading.Thread(target=_send, daemon=True)
        t.start()
        return t, err

    def _ring_step_qreduce(self, send_chunk: np.ndarray, dst: np.ndarray,
                           combine):
        """One quantized ring step: quantize and stream the outgoing
        chunk (per-block f32 scales ride ahead of the int8 payload)
        while receiving the peer's, dequantizing and combining
        pipeline-slice by slice into `dst` (float32). Wire bytes per
        chunk: elems * (1 + 4/QUANT_BLOCK) instead of elems * 4."""
        self._fire_quantize()
        q, scales = _quant_np(send_chunk)
        t, err = self._ring_send_seq_async(
            [memoryview(scales).cast("B"), memoryview(q).cast("B")])
        n = dst.size  # elements == int8 payload bytes
        rscales = np.empty(n // QUANT_BLOCK, np.float32)
        self._ring_recv_into(memoryview(rscales).cast("B"))
        rq = np.empty(min(self._PIPE_BYTES, n), np.int8)
        off = 0
        while off < n:  # slices stay QUANT_BLOCK-aligned (2^18 % 256 == 0)
            k = min(self._PIPE_BYTES, n - off)
            self._ring_recv_into(memoryview(rq).cast("B")[:k])
            deq = (rq[:k].reshape(-1, QUANT_BLOCK).astype(np.float32)
                   * rscales[off // QUANT_BLOCK:
                             (off + k) // QUANT_BLOCK, None]).reshape(-1)
            combine(dst[off:off + k], deq, out=dst[off:off + k])
            off += k
        self._ring_join(t, err)

    def _ring_allreduce_quantized(self, arr: np.ndarray,
                                  op: ReduceOp) -> np.ndarray:
        """EQuARX-style quantized pipelined ring allreduce: every hop of
        the reduce-scatter phase re-quantizes the partial chunk to
        int8 + per-block f32 scales and combines on the dequantized
        float32 values; the allgather phase quantizes the reduced chunk
        ONCE and relays the same bytes, so every rank dequantizes
        identical data and the (lossy) result agrees bitwise across
        ranks. Analytic error bound: each of the <= world quantization
        steps that touch an output element perturbs it by at most
        scale/2 <= absmax/254 of the partial it quantized."""
        w = self.world_size
        in_dt = arr.dtype
        n = arr.size
        # uniform block-aligned chunks (zero padding never inflates a
        # block's absmax, and the pad region is sliced off at the end)
        per_rank = -(-n // w)
        C = -(-per_rank // QUANT_BLOCK) * QUANT_BLOCK
        work = np.zeros(w * C, np.float32)
        work[:n] = arr.reshape(-1)
        combine = getattr(np, _NUMPY_REDUCE[
            ReduceOp.SUM if op == ReduceOp.MEAN else ReduceOp(op)])

        def chunk(i):
            i %= w
            return work[i * C:(i + 1) * C]

        # reduce-scatter (delta=0 schedule): w-1 quantized hops — rank r
        # ends holding the fully-reduced chunk (r+1) % w
        for step in range(w - 1):
            send_i = self.rank - step
            self._ring_step_qreduce(chunk(send_i), chunk(send_i - 1),
                                    combine)
        # allgather: quantize the reduced chunk once, relay the same
        # bytes around the ring; the own chunk goes through the same
        # dequant so all ranks hold bit-identical results
        self._fire_quantize()
        own = (self.rank + 1) % w
        q, scales = _quant_np(chunk(own))
        work[own * C:(own + 1) * C] = _dequant_np(q, scales)
        rq = np.empty(C, np.int8)
        rscales = np.empty(C // QUANT_BLOCK, np.float32)
        for step in range(w - 1):
            t, err = self._ring_send_seq_async(
                [memoryview(scales).cast("B"), memoryview(q).cast("B")])
            self._ring_recv_into(memoryview(rscales).cast("B"))
            self._ring_recv_into(memoryview(rq).cast("B"))
            self._ring_join(t, err)
            idx = (self.rank - step) % w
            work[idx * C:(idx + 1) * C] = _dequant_np(rq, rscales)
            q, scales = rq.copy(), rscales.copy()  # relay onward
        # socket bytes saved vs the exact tier's wire dtype
        wire_elems = 2 * (w - 1) * C
        exact_item = (4 if (op == ReduceOp.MEAN and in_dt == np.float16)
                      else in_dt.itemsize)
        saved = wire_elems * exact_item - wire_elems * (
            1 + 4 / QUANT_BLOCK)
        if saved > 0:
            from ray_tpu.collective import metrics as _cm

            _cm.QUANT_SAVED.inc(int(saved))
        out = work[:n]
        if op == ReduceOp.MEAN:
            out = out / w
        return out.astype(in_dt, copy=False).reshape(arr.shape).copy()

    def _ring_reducescatter_quantized(self, arr: np.ndarray,
                                      op: ReduceOp) -> np.ndarray:
        """Quantized pipelined ring reduce-scatter — the reduce half of
        _ring_allreduce_quantized on the delta=-1 schedule, so rank r
        ends holding reduced chunk r (hub/np.array_split semantics).
        The dispatch admits only flat buckets whose size is a multiple
        of world * QUANT_BLOCK — exactly the sharded trainer's padded
        grad bucket (train/sharding.py layout) — so chunks are uniform
        and block-aligned with no re-marshalling. Lossy, but each output
        element is perturbed by <= scale/2 per hop that touched it, and
        the result is rank-local (no cross-rank divergence to agree
        on)."""
        w = self.world_size
        in_dt = arr.dtype
        C = arr.size // w
        work = arr.reshape(-1).astype(np.float32)  # fresh f32 accumulator
        combine = getattr(np, _NUMPY_REDUCE[
            ReduceOp.SUM if op == ReduceOp.MEAN else ReduceOp(op)])

        def chunk(i):
            i %= w
            return work[i * C:(i + 1) * C]

        for step in range(w - 1):
            send_i = self.rank - step - 1
            self._ring_step_qreduce(chunk(send_i), chunk(send_i - 1),
                                    combine)
        # socket bytes saved vs the exact pipelined tier's wire dtype
        wire_elems = (w - 1) * C
        saved = wire_elems * in_dt.itemsize - wire_elems * (
            1 + 4 / QUANT_BLOCK)
        if saved > 0:
            from ray_tpu.collective import metrics as _cm

            _cm.QUANT_SAVED.inc(int(saved))
        res = chunk(self.rank)
        if op == ReduceOp.MEAN:
            res = res / w
        return res.astype(in_dt, copy=False).reshape(
            (arr.shape[0] // w,) + arr.shape[1:]).copy()

    # ---- collectives (routed) ----

    def _run_routed(self, arr: np.ndarray, shm_need: int, shm_fn, ring_fn,
                    hub_fn):
        """One route/fallback/poison dispatch for the uniform-geometry
        collectives (allgather is bespoke: its geometry may be ragged).
        shm_fn(transport), ring_fn(), hub_fn(). A
        placement-derived pin whose tier can't be built demotes
        (group-uniformly — shm's ok-flag exchange / the uniform ring
        build result) and re-routes, instead of raising like a
        user-forced one."""
        while True:
            rerouted = False
            for tr in self._route(arr):
                if tr == Transport.SHM.value:
                    t = self._ensure_shm(shm_need)
                    if t is None:
                        if self._tier_unavailable(tr):
                            rerouted = True
                            break
                        continue
                    return self._shm_op(lambda: shm_fn(t))
                if tr == Transport.RING.value:
                    if not self._ring_op(self._ensure_ring):
                        if self._tier_unavailable(tr):
                            rerouted = True
                            break
                        continue
                    return self._ring_op(ring_fn)
                return hub_fn()
            if not rerouted:
                raise RuntimeError("no collective transport available")

    @_op_entry("allreduce")
    def allreduce(self, arr: np.ndarray, op: ReduceOp = ReduceOp.SUM,
                  quantize=None):
        op = ReduceOp(op)
        q = self._quantize_mode(quantize)
        if self._pallas_route(arr):
            return self._pallas_op(
                lambda: self._pallas.allreduce(arr, op, quantize=q))
        if self._device_route(arr):
            return self._device_op(
                lambda: self._device.allreduce(arr, op, quantize=q))
        arr = self._to_host(arr)

        def hub():
            reply, data = self._collective(
                "allreduce", {**_arr_meta(arr), "op": op.value},
                arr.tobytes())
            return _arr_from(reply["meta"], data)

        def ring():
            # int payloads and PRODUCT stay exact by definition
            if (q and op in _QUANT_OPS
                    and np.issubdtype(arr.dtype, np.floating)):
                return self._ring_allreduce_quantized(arr, op)
            return self._ring_allreduce_pipelined(arr, op)

        return self._run_routed(
            arr, self._shm_need(arr, op),
            lambda t: t.allreduce(arr, op),
            ring, hub)

    @_op_entry("reduce")
    def reduce(self, arr: np.ndarray, dst_rank: int = 0,
               op: ReduceOp = ReduceOp.SUM):
        arr = self._to_host(arr)
        reply, data = self._collective(
            "reduce", {**_arr_meta(arr), "op": op.value, "dst": dst_rank},
            arr.tobytes())
        if self.rank == dst_rank:
            return _arr_from(reply["meta"], data)
        return arr

    @_op_entry("broadcast")
    def broadcast(self, arr: np.ndarray, src_rank: int = 0):
        if self._device_route(arr):
            return self._device_op(
                lambda: self._device.broadcast(arr, src_rank))
        arr = self._to_host(arr)

        def hub():
            payload = arr.tobytes() if self.rank == src_rank else b""
            meta = {**_arr_meta(arr), "src": src_rank}
            reply, data = self._collective("broadcast", meta, payload)
            return _arr_from(reply["meta"], data)

        return self._run_routed(
            arr, self._shm_need(arr, None),
            lambda t: t.broadcast(arr, src_rank),
            lambda: self._ring_broadcast_pipelined(arr, src_rank),
            hub)

    @_op_entry("allgather")
    def allgather(self, arr: np.ndarray) -> list[np.ndarray]:
        # allgather is the one op whose per-rank GEOMETRY may
        # legitimately differ, so local-size routing can diverge (ragged
        # sizes straddling RING_MIN_BYTES). Every rank therefore opens
        # with the SAME metadata-only hub round and routes on the union:
        # fast tiers only for uniform shapes, the hub (which supports
        # ragged gathers natively) otherwise. One extra control
        # round-trip, paid once, instead of per-tier probing — and no
        # route divergence is possible.
        if not self._is_device_array(arr):
            arr = np.ascontiguousarray(arr)
        if self.world_size == 1 or self._destroyed:
            return self._hub_allgather(self._to_host(arr))
        metas = self._hub_allgather_meta(arr)
        uniform = all(m == metas[0] for m in metas[1:])
        # the pallas/device votes only happen on the uniform path, so
        # every rank enters (or skips) the ctl rounds together
        if uniform and self._pallas_route(arr):
            return self._pallas_op(lambda: self._pallas.allgather(arr))
        if uniform and self._device_route(arr):
            return self._device_op(lambda: self._device.allgather(arr))
        arr = self._to_host(arr)
        for tr in self._route(arr) if uniform else [Transport.HUB.value]:
            if tr == Transport.SHM.value:
                t = self._ensure_shm(self._shm_need(arr, None))
                if t is None:
                    # derived pin demotes (uniform) and this op falls
                    # through to the unconditional hub below
                    self._tier_unavailable(tr)
                    continue
                out = self._shm_op(lambda: t.allgather(arr))
                if out is not None:
                    return out
                continue  # defense-in-depth: shm saw ragged metas
            if tr == Transport.RING.value:
                if not self._ring_op(self._ensure_ring):
                    self._tier_unavailable(tr)
                    continue
                return self._ring_op(
                    lambda: self._ring_allgather_pipelined(arr))
            return self._hub_allgather(arr)
        # pinned non-hub transport exhausted (e.g. forced shm + ragged):
        # the hub is the only tier that can express it
        return self._hub_allgather(arr)

    @_op_entry("reducescatter")
    def reducescatter(self, arr: np.ndarray, op: ReduceOp = ReduceOp.SUM,
                      quantize=None):
        op = ReduceOp(op)
        q = self._quantize_mode(quantize)
        if self._pallas_route(arr):
            return self._pallas_op(
                lambda: self._pallas.reducescatter(arr, op, quantize=q))
        if self._device_route(arr):
            return self._device_op(
                lambda: self._device.reducescatter(arr, op, quantize=q))
        arr = self._to_host(arr)

        def hub():
            reply, data = self._collective(
                "reducescatter", {**_arr_meta(arr), "op": op.value},
                arr.tobytes())
            return _arr_from(reply["meta"], data)

        def ring():
            # quantized wire only for flat world*QUANT_BLOCK-aligned
            # float buckets (uniform block-aligned chunks — the
            # sharded-trainer grad layout); anything else takes the
            # exact tier
            if (q and op in _QUANT_OPS
                    and np.issubdtype(arr.dtype, np.floating)
                    and arr.ndim == 1
                    and arr.size % (self.world_size * QUANT_BLOCK) == 0):
                return self._ring_reducescatter_quantized(arr, op)
            return self._ring_reducescatter_pipelined(arr, op)

        return self._run_routed(
            arr, self._shm_need(arr, op),
            lambda t: t.reducescatter(arr, op),
            ring, hub)

    @_op_entry("barrier")
    def barrier(self):
        self._collective("barrier", {}, b"")

    # ---- p2p ----
    # The hub mailbox always carries the rendezvous/control message;
    # payloads above RING_MIN_BYTES go peer-direct (one rank-to-rank
    # connection) instead of double-copying through rank 0.

    def send(self, arr: np.ndarray, dst_rank: int, tag: int = 0):
        arr = np.ascontiguousarray(arr)
        if (arr.nbytes >= self.RING_MIN_BYTES and self.world_size > 1
                and dst_rank != self.rank and not self._destroyed):
            return self._send_direct(arr, dst_rank, tag)
        if self.rank == 0:
            self._state.post(0, dst_rank, tag, _arr_meta(arr), arr.tobytes())
            return
        _send_msg(self._sock, {"kind": "p2p_send", "dst": dst_rank,
                               "tag": tag, "meta": _arr_meta(arr)},
                  arr.tobytes())
        _recv_msg(self._sock)  # ack

    def _send_direct(self, arr: np.ndarray, dst_rank: int, tag: int):
        """Post the rendezvous control message to the hub mailbox, then
        serve the payload from a background thread — send() keeps the
        hub path's buffered semantics (returns without waiting for the
        receiver, so symmetric send/send-then-recv/recv patterns can't
        deadlock). The payload is snapshotted first, so mutating the
        tensor after send() returns cannot corrupt the transfer. The
        listener has NO deadline of its own: like a hub mailbox entry,
        the buffered payload stays claimable until the receiver takes it
        or the group is destroyed (destroy() closes the listener, which
        frees the thread and the snapshot) — recv-side timeouts still
        bound every blocking reader, so there is no expiry cliff at the
        RING_MIN_BYTES threshold."""
        arr = arr.copy()
        listener = socket.socket()
        listener.bind(("127.0.0.1", 0))
        listener.listen(1)
        key = (dst_rank, tag)
        with self._p2p_lock:
            stale = self._p2p_direct.pop(key, None)
            self._p2p_direct[key] = listener
        if stale is not None:
            try:  # overwrite the unclaimed predecessor, like the mailbox
                stale.close()  # (an in-flight transfer keeps its conn fd)
            except Exception:
                pass
        port = listener.getsockname()[1]
        ctrl = {**_arr_meta(arr), "peer_direct": f"127.0.0.1:{port}"}

        def _serve():
            conn = None
            try:
                conn, _ = listener.accept()  # until taken/overwritten/
                conn.settimeout(self._timeout)  # destroyed
                conn.sendall(memoryview(arr).cast("B"))
                conn.recv(1)  # receiver ack bounds arr's lifetime
            except OSError:
                pass  # abort-not-hang: the receiver sees a short read
            finally:
                if conn is not None:
                    conn.close()
                listener.close()
                with self._p2p_lock:
                    if self._p2p_direct.get(key) is listener:
                        del self._p2p_direct[key]

        t = threading.Thread(target=_serve, daemon=True)
        t.start()
        try:
            if self.rank == 0:
                self._state.post(0, dst_rank, tag, ctrl, b"")
            else:
                _send_msg(self._sock, {"kind": "p2p_send", "dst": dst_rank,
                                       "tag": tag, "meta": ctrl})
                _recv_msg(self._sock)  # hub ack
        except BaseException:
            listener.close()  # unblocks the serve thread
            raise

    def recv(self, src_rank: int, tag: int = 0) -> np.ndarray:
        if self.rank == 0:
            meta, data = self._state.take(src_rank, 0, tag,
                                          timeout=self._timeout)
        else:
            _send_msg(self._sock, {"kind": "p2p_recv", "src": src_rank,
                                   "tag": tag})
            reply, data = _recv_msg(self._sock)
            if "error" in reply:
                raise TimeoutError(reply["error"])
            meta = reply["meta"]
        if meta and meta.get("peer_direct"):
            return self._recv_direct(meta)
        return _arr_from(meta, data)

    def _recv_direct(self, meta: dict) -> np.ndarray:
        host, port = meta["peer_direct"].rsplit(":", 1)
        out = np.empty(meta["shape"], np.dtype(meta["dtype"]))
        try:
            sock = socket.create_connection((host, int(port)),
                                            timeout=self._timeout)
        except OSError as e:
            raise TimeoutError(
                f"peer-direct recv: sender unreachable: {e}") from e
        try:
            sock.settimeout(self._timeout)
            mv = memoryview(out).cast("B")
            got, n = 0, out.nbytes
            while got < n:
                r = sock.recv_into(mv[got:], n - got)
                if not r:
                    raise TimeoutError(  # abort-not-hang: peer died
                        "peer-direct sender disconnected mid-transfer")
                got += r
            sock.sendall(b"\x01")
        finally:
            sock.close()
        return out

    def destroy(self):
        if self._destroyed:
            return
        self._destroyed = True
        self._ring_teardown()
        if self._pallas is not None:
            try:
                self._pallas.destroy()  # drops the pallas jit cache
            except Exception:
                pass
            self._pallas = None
        if self._device is not None:
            try:
                self._device.destroy()  # drops the jit cache; the jax
            except Exception:           # runtime itself outlives groups
                pass
            self._device = None
        with self._p2p_lock:
            pending = list(self._p2p_direct.values())
            self._p2p_direct.clear()
        for listener in pending:
            try:
                listener.close()  # frees the serve thread + snapshot
            except Exception:
                pass
        if self._shm is not None:
            try:
                # unlink from every rank (idempotent): rank 0 may already
                # be gone, and group destroy is the last chance to keep
                # the segment's tmpfs bytes from outliving the group
                self._shm.close(unlink=True)
            except Exception:
                pass
            self._shm = None
        if self.rank == 0 and self.world_size > 1:
            try:
                self._listener.close()
            except Exception:
                pass
            from ray_tpu.experimental import internal_kv

            for key in [self._key, *self._shm_keys]:
                try:
                    internal_kv._kv_del(key)
                except Exception:
                    pass
        if self._sock is not None:
            try:
                self._sock.close()
            except Exception:
                pass
