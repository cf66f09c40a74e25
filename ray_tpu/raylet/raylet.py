"""Raylet — the per-node manager process.

Capability parity with the reference raylet (reference: src/ray/raylet/
node_manager.h:133): grants worker leases (HandleRequestWorkerLease,
node_manager.cc:1318), runs the local scheduler with spillback
(src/ray/raylet/scheduling/cluster_resource_scheduler.cc:217 hybrid policy),
manages the pool of Python worker processes (worker_pool.h:92), tracks and
transfers local objects (object_manager.h:107 + local_object_manager.h:38
spilling), and executes the GCS's actor-creation and placement-group bundle
requests (placement_group_resource_manager.h:51 2PC prepare/commit).

Differences by design: task *data* never flows through the raylet — owners
push tasks directly to leased workers over their own connections (same
direct-call architecture as the reference's CoreWorkerDirectTaskSubmitter);
the raylet is control-plane plus bulk object transfer only.
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import os
import signal
import subprocess
import sys
import time

from ray_tpu._private import debug_state as _debug
from ray_tpu._private import failpoints as _fp
from ray_tpu._private import rpc
from ray_tpu._private import sampling_profiler as _sprof
from ray_tpu._private import topology as _topo
from ray_tpu._private import tracing
from ray_tpu._private.common import InsufficientResources, ResourceSet
from ray_tpu._private.config import Config, get_config, set_config
from ray_tpu._private.ids import NodeID, ObjectID
from ray_tpu._private.node import has_left, wait_until_left
from ray_tpu._private.object_store import make_store
from ray_tpu.raylet import transfer

logger = logging.getLogger("ray_tpu.raylet")


class WorkerHandle:
    def __init__(self, worker_id: bytes, address: str, pid: int, conn):
        self.worker_id = worker_id
        self.address = address
        self.pid = pid
        self.conn = conn
        self.actor_id: bytes | None = None
        self.lease_id: bytes | None = None
        self.lease_resources: ResourceSet | None = None
        self.lease_pg: tuple[bytes, int] | None = None
        self.flavor: str = "cpu"  # "cpu" | "tpu" — which env it spawned with
        self.task_channel: str = ""  # same-node direct task UDS ("" = none)


class Raylet:
    def __init__(self, *, node_id: NodeID, session_dir: str, gcs_address: str,
                 resources: dict[str, float], store_root: str,
                 is_head: bool, labels: dict[str, str], config: Config,
                 tpu_slice: dict | None = None,
                 topology: dict | None = None,
                 tpu_worker_platforms: str = "tpu"):
        self.node_id = node_id
        self.tpu_worker_platforms = tpu_worker_platforms
        self.session_dir = session_dir
        self.gcs_address = gcs_address
        self.config = config
        self.is_head = is_head
        self.labels = labels
        # this node's position in the pod's physical shape (topology.py):
        # explicit (--topology / cluster_utils), RAY_TPU_TOPOLOGY env, or
        # derived from the slice descriptor — deterministic, so a raylet
        # restart lands on the same coord. None = unlocated (ICI_RING
        # counts the fallback; spillback ordering stays random).
        self.topology = _topo.derive_coord(
            node_id_hex=node_id.hex(), tpu_slice=tpu_slice,
            labels=labels, explicit=topology)
        # TPU slice membership (util/accelerators.TpuSliceDescriptor as a
        # dict): declares this host's ICI domain. Implies TPU chips and
        # the accelerator_type:<gen> constraint resource if absent.
        self.tpu_slice = tpu_slice
        if tpu_slice:
            from ray_tpu.util.accelerators import accelerator_resource

            resources = dict(resources)
            resources.setdefault("TPU",
                                 float(tpu_slice["chips_per_host"]))
            resources.setdefault(
                accelerator_resource(tpu_slice["generation"]), 1.0)
        self.total = ResourceSet(resources)
        self.available = self.total.copy()
        self.store = make_store(store_root, config)
        self.store_root = store_root

        # worker pool — two flavors: plain CPU workers (started with
        # JAX_PLATFORMS=cpu) and TPU workers (the one process that may
        # initialise the TPU backend). A worker's flavor is fixed at
        # spawn; leases route to the matching pool so only leases that
        # declare TPU resources ever run in a process that can claim
        # the chip.
        self.workers: dict[bytes, WorkerHandle] = {}  # registered, by worker_id
        self.idle: list[WorkerHandle] = []
        self.idle_tpu: list[WorkerHandle] = []
        self.starting = 0
        self.starting_tpu = 0
        self._worker_waiters: list[tuple[asyncio.Future, bool]] = []
        # Spawned-but-unregistered worker processes, so a worker that dies
        # during startup (import error, chip already claimed, OOM)
        # is reaped and its `starting` slot released instead of wedging
        # _pop_worker forever.
        self._starting_procs: list = []  # [(Popen, flavor)]
        self._warned_infeasible: set[tuple] = set()
        self._metric_merge_logged: set[str] = set()

        # metrics (reference: src/ray/stats/metric_defs.cc raylet set)
        from ray_tpu._private import stats

        self.m_leases_granted = stats.Count(
            "raylet.leases_granted_total", "worker leases granted")
        self.m_spillbacks = stats.Count(
            "raylet.spillbacks_total", "lease requests redirected away")
        self.m_workers_started = stats.Count(
            "raylet.workers_started_total", "worker processes spawned")
        self.m_objects_pulled = stats.Count(
            "raylet.objects_pulled_total", "objects pulled from peers")
        self.m_locality_spillbacks = stats.Count(
            "raylet.locality_spillbacks_total",
            "lease requests redirected to the node holding their args")
        self.m_spillback_forwards = stats.Count(
            "raylet.spillback_forwards_total",
            "lease requests forwarded raylet->raylet instead of bounced "
            "back to the owner")
        self.m_spillback_grants = stats.Count(
            "raylet.spillback_grants_total",
            "leases granted here for a forwarded (spillback-chain) request")
        self.m_topo_reroutes = stats.Count(
            "raylet.spillback_topo_reroutes_total",
            "spillback/locality decisions where the topology distance "
            "metric differentiated the candidates and picked a nearer "
            "node than a blind choice could guarantee")
        self.m_lease_grant_s = stats.Histogram(
            "raylet.lease_grant_s", stats.LATENCY_BOUNDARIES_S,
            "lease request arrival -> grant (queue + worker startup)")
        self.m_drains = stats.Count(
            "raylet.drains_total", "graceful drains started on this raylet")
        self.m_drain_migrated_bytes = stats.Count(
            "raylet.drain_migrated_bytes_total",
            "plasma bytes pushed to survivors during drain")
        self.num_cpus = int(resources.get("CPU", os.cpu_count() or 1))

        # trace spans (tracing.py) recorded by this raylet — lease grants
        # and object-transfer hops — flushed to the GCS on the heartbeat
        # cadence (~2s)
        from ray_tpu._private.profiling import ProfileBuffer

        self._profile = ProfileBuffer("raylet")
        tracing.bind_buffer(self._profile)
        self._last_profile_flush = 0.0
        self._beat_n = 0

        # scheduling
        self._lease_seq = 0
        self.pending_leases: list[tuple[dict, asyncio.Future]] = []
        # lease_id -> monotonic deadline for grants made on behalf of a
        # FORWARDED request (spillback chain): the true holder (the task
        # owner) claims them via adopt_leases over its own connection;
        # one that never does (owner died between grant and adoption) is
        # reclaimed by the reap loop at the deadline.
        self._unadopted: dict[bytes, float] = {}

        # placement group bundles: (pg_id, index) -> {"resources", "available",
        # "state"}
        self.bundles: dict[tuple[bytes, int], dict] = {}
        # pg_id -> resources leased out of bundles that were since removed;
        # returned to self.available as those leases end.
        self._removed_bundles: dict[bytes, ResourceSet] = {}

        # object manager
        self.local_objects: dict[bytes, dict] = {}  # oid -> {size, pinned, spilled}
        self.object_waiters: dict[bytes, list[asyncio.Future]] = {}
        self.store_used = 0
        self.spill_dir = os.path.join(session_dir, "spill")
        self._pulls_inflight: set[bytes] = set()
        self._pull_sem_obj = None

        # bulk transfer data plane (raylet/transfer.py): dedicated
        # streaming channel for object bytes and sender-side transfer pins
        self.transfer_pins = transfer.TransferPins()
        self.bulk = transfer.BulkTransferServer(self)
        self.bulk_address = ""
        self._loop: asyncio.AbstractEventLoop | None = None
        # arg-id set -> monotonic expiry of a NO-redirect locality
        # decision: repeated lease requests for the SAME pending task's
        # args (the retry/escalation pattern) skip the per-request GCS
        # directory round trip. Keyed by the args — not the scheduling
        # key — so one small-arg call can't suppress redirects for a
        # later call of the same function with different, remote-resident
        # args. Positive redirects are never cached (must see fresh
        # locations).
        self._locality_negcache: dict[tuple, float] = {}

        # cluster view (from GCS pubsub)
        self.cluster_nodes: dict[bytes, dict] = {}

        self.gcs: rpc.Connection | None = None
        self.server = rpc.Server(self._handlers(),
                                 on_disconnect=self._on_disconnect,
                                 name="raylet")
        self.address = ""  # tcp address, set in run()
        self._raylet_conns: dict[str, rpc.Connection] = {}
        self._raylet_dial_locks: dict[str, asyncio.Lock] = {}
        self._shutting_down = False
        # Elastic membership: set by h_drain (GCS-initiated or a
        # preemption notice). A draining raylet grants no new leases,
        # reserves no bundles, and is skipped as a spillback/locality
        # target by peers (they read state=DRAINING off the nodes
        # channel); the background _drain task migrates plasma to
        # survivors, waits out in-flight leases, checkpoints actors,
        # then exits through node_drained — never the crash path.
        self._draining = False
        self._drain_task: asyncio.Task | None = None

    def _handlers(self):
        return {
            # worker/driver-facing
            "register_client": self.h_register_client,
            "request_worker_lease": self.h_request_worker_lease,
            "adopt_leases": self.h_adopt_leases,
            "return_worker": self.h_return_worker,
            "notify_object_sealed": self.h_notify_object_sealed,
            "wait_object_local": self.h_wait_object_local,
            "hint_pull_purpose": self.h_hint_pull_purpose,
            "free_objects": self.h_free_objects,
            "pin_object": self.h_pin_object,
            "spill_now": self.h_spill_now,
            "get_logs": self.h_get_logs,
            "cluster_info": self.h_cluster_info,
            "get_metrics": self.h_get_metrics,
            "set_resource": self.h_set_resource,
            "actor_exiting": self.h_actor_exiting,
            # gcs-facing
            "drain": self.h_drain,
            "create_actor": self.h_create_actor,
            "kill_actor_worker": self.h_kill_actor_worker,
            "prepare_bundle": self.h_prepare_bundle,
            "commit_bundle": self.h_commit_bundle,
            "cancel_bundle": self.h_cancel_bundle,
            "return_bundle": self.h_return_bundle,
            # raylet-to-raylet object transfer
            "object_info": self.h_object_info,
            "fetch_chunk": self.h_fetch_chunk,
            "push_hint": self.h_push_hint,
            "push_objects_to": self.h_push_objects_to,
            "transfer_done": self.h_transfer_done,
            "peer_ping": self.h_peer_ping,
            "debug_state": self.h_debug_state,
            "debug_stacks": lambda conn, d: _debug.collect_stacks(),
            "ping": lambda conn, d: "pong",
        }

    # ------------------------------------------------------------------
    # worker pool (reference: src/ray/raylet/worker_pool.h)
    # ------------------------------------------------------------------

    def _start_worker_process(self, tpu: bool = False):
        if _fp.ARMED:
            # spawn seam: `raise` -> the pending lease request errors
            # (owner maps it to WorkerCrashedError or backs off)
            _fp.fire_strict("raylet.spawn")
        if tpu:
            self.starting_tpu += 1
        else:
            self.starting += 1
        log_file = os.path.join(
            self.session_dir, "logs",
            f"worker-{self.node_id.hex()[:8]}-{self.starting + self.starting_tpu}"
            f"-{time.time():.0f}.log")
        env = dict(os.environ)
        env.update(self.config.child_env())
        # One rule for the chip (_private/accelerator.py): the
        # TPU-flavour worker is the only process on the node that may
        # initialise the TPU backend; every other worker is started with
        # JAX_PLATFORMS=cpu SET (an inherited "" or "tpu" would let JAX
        # auto-select the chip and take libtpu's lock away from the
        # worker that was leased it). Nothing is probed here: the
        # TPU-flavour worker itself waits, bounded, for device nodes
        # that a process that is ending still holds (`worker/main.py`,
        # `accelerator.wait_for_chips`); a chip that cannot be opened
        # for another reason fails with libtpu's error. The worker
        # echoes its flavour back at registration.
        env["JAX_PLATFORMS"] = self.tpu_worker_platforms if tpu else "cpu"
        env["RAY_TPU_WORKER_FLAVOR"] = "tpu" if tpu else "cpu"
        # the `worker.spawn` span's start (`worker/main.py`): this
        # moment to the child's `main`, one host, one clock
        env["RAY_TPU_WORKER_SPAWNED_AT"] = repr(time.time())
        cmd = [
            sys.executable, "-m", "ray_tpu.worker.main",
            "--raylet-address", self.address,
            "--gcs-address", self.gcs_address,
            "--node-id", self.node_id.hex(),
            "--session-dir", self.session_dir,
            "--store-root", self.store_root,
            "--log-file", log_file,
        ]
        # stderr lands in the worker's log file so crashes (uncaught
        # tracebacks, aborts) are diagnosable post-mortem.
        errf = open(log_file + ".err", "ab") if log_file else subprocess.DEVNULL
        # no session of its own: a worker stays in this raylet's process
        # group (the raylet leads one, `node._spawn`), which is how the
        # node finds and ends everything the raylet started
        proc = subprocess.Popen(
            cmd, env=env,
            stdout=subprocess.DEVNULL, stderr=errf)
        if errf is not subprocess.DEVNULL:
            errf.close()
        self._starting_procs.append((proc, "tpu" if tpu else "cpu"))
        self.m_workers_started.inc()
        logger.info("started %s worker process pid=%d",
                    "tpu" if tpu else "cpu", proc.pid)
        return proc

    def _reap_starting_workers(self):
        """Release `starting` slots held by worker processes that exited
        before registering, and re-wake waiters so they respawn."""
        alive, died = [], []
        for proc, flavor in self._starting_procs:
            (alive if proc.poll() is None else died).append((proc, flavor))
        self._starting_procs = alive
        for proc, flavor in died:
            logger.warning("%s worker pid=%d exited (rc=%s) before "
                           "registering", flavor, proc.pid, proc.returncode)
            if flavor == "tpu":
                self.starting_tpu = max(0, self.starting_tpu - 1)
            else:
                self.starting = max(0, self.starting - 1)
        if died:
            # Wake every waiter; each re-runs its loop and respawns now
            # that the stuck `starting` slot is free.
            for fut, _tpu in self._worker_waiters:
                if not fut.done():
                    fut.set_result(None)
            self._worker_waiters = []

    async def _pop_worker(self, ignore_cap: bool = False,
                          tpu: bool = False) -> WorkerHandle:
        while True:
            pool = self.idle_tpu if tpu else self.idle
            if pool:
                return pool.pop()
            if tpu:
                # TPU workers are dedicated and rare — no cap games.
                if self.starting_tpu == 0:
                    self._start_worker_process(tpu=True)
            else:
                max_workers = (self.config.max_workers_per_node
                               or max(self.num_cpus, 4))
                active = len(self.workers) + self.starting
                if ignore_cap or active < max_workers or self.starting == 0:
                    self._start_worker_process()
            fut = asyncio.get_running_loop().create_future()
            self._worker_waiters.append((fut, tpu))
            await fut

    def _push_worker(self, worker: WorkerHandle):
        worker.lease_id = None
        worker.lease_resources = None
        worker.lease_pg = None
        if worker.conn.closed:
            return
        (self.idle_tpu if worker.flavor == "tpu" else self.idle).append(worker)
        self._wake_worker_waiters()

    def _wake_worker_waiters(self):
        remaining = []
        for fut, tpu in self._worker_waiters:
            pool = self.idle_tpu if tpu else self.idle
            if pool and not fut.done():
                fut.set_result(None)
            elif not fut.done():
                remaining.append((fut, tpu))
        self._worker_waiters = remaining

    async def h_register_client(self, conn, d):
        kind = d["kind"]
        if kind == "worker":
            worker = WorkerHandle(d["worker_id"], d["address"], d["pid"], conn)
            worker.flavor = d.get("flavor", "cpu")
            worker.task_channel = d.get("task_channel") or ""
            self._starting_procs = [(p, f) for p, f in self._starting_procs
                                    if p.pid != d["pid"]]
            self.workers[d["worker_id"]] = worker
            conn.context["worker"] = worker
            if worker.flavor == "tpu":
                self.starting_tpu = max(0, self.starting_tpu - 1)
                self.idle_tpu.append(worker)
            else:
                self.starting = max(0, self.starting - 1)
                self.idle.append(worker)
            self._wake_worker_waiters()
        else:  # driver
            # truthy dict (callers only truth-test it): pid/address let
            # debug_state/doctor reach driver-owned task state from the
            # out-of-process surfaces (ray-tpu state/doctor, dashboard)
            conn.context["driver"] = {"pid": d.get("pid"),
                                      "address": d.get("address", "")}
        return {"node_id": self.node_id.binary(), "address": self.address}

    async def _on_disconnect(self, conn):
        if self._shutting_down:
            return
        # A control-path puller's transfer pins die with its connection (the
        # TTL sweep is only the backstop for pullers that wedge without
        # closing); deferred frees they were blocking run now.
        freeable = self.transfer_pins.release_token(
            self._control_pin_token(conn))
        if freeable:
            await self._complete_deferred_frees(freeable)
        # Lease-holder death: leases granted to this connection (a
        # driver, or a worker that owned subtasks) are returned now —
        # resources released, still-alive workers back in the idle pool —
        # instead of stranding them until node teardown.
        held = conn.context.pop("lease_ids", None)
        if held:
            reclaimed = 0
            for w in list(self.workers.values()):
                if w.lease_id in held:
                    self._release(w.lease_resources, w.lease_pg)
                    self._push_worker(w)
                    reclaimed += 1
            if reclaimed:
                logger.warning(
                    "lease holder disconnected; reclaimed %d leased "
                    "worker(s)", reclaimed)
                await self._dispatch_pending()
        worker: WorkerHandle | None = conn.context.get("worker")
        if worker is None:
            return
        self.workers.pop(worker.worker_id, None)
        if worker in self.idle:
            self.idle.remove(worker)
        if worker in self.idle_tpu:
            self.idle_tpu.remove(worker)
        # release lease resources
        if worker.lease_resources is not None:
            self._release(worker.lease_resources, worker.lease_pg)
            await self._dispatch_pending()
        intended = bool(conn.context.get("intended_exit"))
        if not intended and self.gcs is not None:
            # structured WORKER_DIED event → GCS ring (RAY_EVENT analog)
            from ray_tpu._private import events

            event = events.report_event(
                events.ERROR, "WORKER_DIED",
                f"worker {worker.worker_id.hex()[:8]} "
                f"(pid {worker.pid}) died unexpectedly",
                worker_id=worker.worker_id.hex(), pid=worker.pid)
            try:
                await self.gcs.notify("report_event", event)
            except Exception:
                pass
        if worker.actor_id is not None and self.gcs is not None:
            try:
                await self.gcs.call("report_worker_failure", {
                    "worker_id": worker.worker_id,
                    "actor_ids": [worker.actor_id],
                    "intended": intended,
                })
            except Exception:
                pass

    # ------------------------------------------------------------------
    # scheduling (reference: cluster_task_manager.cc + hybrid policy)
    # ------------------------------------------------------------------

    def _bundle_key(self, spec) -> tuple[bytes, int] | None:
        if spec.get("pg_id") is None:
            return None
        return (spec["pg_id"], spec.get("bundle_index", -1))

    def _try_acquire(self, spec) -> tuple[ResourceSet, tuple | None] | None:
        need = ResourceSet.from_raw(spec["resources"])
        key = self._bundle_key(spec)
        if key is not None:
            bundle = self._find_bundle(key)
            if bundle is None:
                return None
            if not need.is_subset_of(bundle["available"]):
                return None
            bundle["available"].subtract(need)
            return need, key
        if not need.is_subset_of(self.available):
            return None
        self.available.subtract(need)
        return need, None

    def _find_bundle(self, key):
        if key[1] != -1:
            b = self.bundles.get(key)
            return b if b and b["state"] == "COMMITTED" else None
        # wildcard bundle index: any committed bundle of this pg on this node
        for (pg, _idx), b in self.bundles.items():
            if pg == key[0] and b["state"] == "COMMITTED":
                return b
        return None

    def _release(self, res: ResourceSet, pg_key):
        if pg_key is not None:
            bundle = self.bundles.get(pg_key) or self._find_bundle(pg_key)
            if bundle is not None:
                bundle["available"].add(res)
                return
            # Bundle was cancelled/returned while this lease was out: its
            # unleased part already went back to self.available, and the
            # leased part was recorded in _removed_bundles — return it now.
            outstanding = self._removed_bundles.get(pg_key[0])
            if outstanding is not None:
                self.available.add(res)
                outstanding.subtract(res)
                if outstanding.is_empty():
                    del self._removed_bundles[pg_key[0]]
            return
        self.available.add(res)

    def _feasible_ever(self, spec) -> bool:
        need = ResourceSet.from_raw(spec["resources"])
        if self._bundle_key(spec) is not None:
            return True  # bundles are explicit placements; wait for them
        return need.is_subset_of(self.total)

    def _coord_of_node(self, node_id: bytes):
        info = self.cluster_nodes.get(node_id)
        if info is None:
            return None
        return _topo.TopologyCoord.from_dict(info.get("topology"))

    def _topo_prefer(self, node_ids: list[bytes]) -> tuple[bytes, bool]:
        """Choose among candidate nodes: the topologically NEAREST one
        when coords differentiate them (random among equals — the
        PR 5/7 tie-breaker: same-slice ICI hops beat cross-slice/DCN),
        plain random otherwise. Returns (node_id, rerouted); rerouted
        is True only when the distance metric actually changed the
        outcome class, which is what
        `raylet.spillback_topo_reroutes_total` counts."""
        import random

        if len(node_ids) <= 1:
            return node_ids[0], False
        if self.topology is None:
            return random.choice(node_ids), False
        dists = [(_topo.distance(self.topology, self._coord_of_node(n)), n)
                 for n in node_ids]
        dmin = min(d for d, _ in dists)
        dmax = max(d for d, _ in dists)
        best = [n for d, n in dists if d == dmin]
        return random.choice(best), dmax > dmin

    def _pick_spillback(self, spec, exclude=()) -> str | None:
        """Hybrid policy fallback: a remote node whose *total* resources
        fit (reference: cluster_resource_scheduler.cc:320) — the
        topologically nearest such node when coords are registered,
        random otherwise. `exclude`: addresses already visited by a
        forwarded request (cycle guard)."""
        need = ResourceSet.from_raw(spec["resources"])
        cands = []
        for node_id, info in self.cluster_nodes.items():
            if node_id == self.node_id.binary():
                continue
            if info["address"] in exclude:
                continue
            if info.get("state", "ALIVE") != "ALIVE":
                continue  # DRAINING peers accept no new leases
            if need.is_subset_of(ResourceSet.from_raw(info["resources"])):
                cands.append(node_id)
        if not cands:
            return None
        choice, rerouted = self._topo_prefer(cands)
        if rerouted:
            self.m_topo_reroutes.inc()
        return self.cluster_nodes[choice]["address"]

    async def _pick_spillback_load_aware(self, spec, exclude=()) -> str | None:
        """Local node is feasible-by-totals but saturated: find a remote
        node with the capacity available RIGHT NOW (heartbeat-fresh GCS
        view) instead of hoarding the task in the local queue
        (reference: availability-scored hybrid policy,
        cluster_resource_scheduler.cc:217-320)."""
        if self.gcs is None or len(self.cluster_nodes) <= 1:
            return None
        try:
            avail_by_node = await self.gcs.call("get_available_resources", {})
        except Exception:
            return None
        avail = {nid: ResourceSet.from_raw(raw)
                 for nid, raw in avail_by_node.items()}
        return self._pick_from_availability(spec, avail, exclude)

    def _pick_from_availability(self, spec, avail: dict,
                                exclude=()) -> str | None:
        """Synchronous selection from a fetched availability view (callers
        holding the view across multiple picks subtract as they assign).
        Topology-nearest among feasible nodes when coords are known —
        the spillback-chain next hop prefers an ICI neighbor over a
        cross-slice node with identical headroom."""
        need = ResourceSet.from_raw(spec["resources"])
        me = self.node_id.binary()
        cands = []
        for node_id, rs in avail.items():
            if node_id == me or node_id not in self.cluster_nodes:
                continue
            if self.cluster_nodes[node_id]["address"] in exclude:
                continue
            if self.cluster_nodes[node_id].get("state", "ALIVE") != "ALIVE":
                continue  # DRAINING peers accept no new leases
            if need.is_subset_of(rs):
                cands.append(node_id)
        if not cands:
            return None
        node_id, rerouted = self._topo_prefer(cands)
        if rerouted:
            self.m_topo_reroutes.inc()
        avail[node_id].subtract(need)  # so N picks don't dogpile one slot
        return self.cluster_nodes[node_id]["address"]

    async def _locality_spillback(self, spec) -> str | None:
        """Weigh lease targets by resident plasma-arg bytes from the GCS
        object directory (reference: lease_policy.h locality-aware lease
        targeting; extends the h_push_objects_to *hint* into actual
        placement). Returns the address of a remote node holding at
        least locality_min_arg_bytes MORE of this task's args than we
        do, provided its total resources can ever run the task — else
        None (normal local grant / spillback applies)."""
        cfg = self.config
        if (not cfg.locality_aware_leasing or self.gcs is None
                or len(self.cluster_nodes) <= 1
                or spec.get("pg_id") is not None):
            return None
        arg_ids = [a["id"] for a in spec.get("args") or []
                   if a.get("kind") == "ref" and a.get("plasma")]
        if not arg_ids:
            return None
        if all(a in self.local_objects for a in arg_ids):
            # every arg is resident HERE: no remote node can hold more
            # bytes than us, so skip the directory round trip on the
            # lease critical path (the steady state once tasks follow
            # their data)
            return None
        key = tuple(arg_ids)
        now = time.monotonic()
        if self._locality_negcache.get(key, 0) > now:
            return None
        if _fp.ARMED:
            # locality-targeting seam: `raise` models a failed directory
            # lookup — placement falls back to the normal local path
            try:
                await _fp.fire_async_strict("lease.locality_target")
            except _fp.FailpointError:
                return None
        try:
            recs = await self.gcs.call("get_object_locations_batch",
                                       {"object_ids": arg_ids})
        except Exception:
            return None
        by_node: dict[bytes, int] = {}
        for rec in (recs or {}).values():
            size = max(1, int(rec.get("size") or 0))
            for node_id in rec.get("nodes") or []:
                by_node[node_id] = by_node.get(node_id, 0) + size
        if not by_node:
            return None
        me = self.node_id.binary()
        need = ResourceSet.from_raw(spec["resources"])
        my_bytes = by_node.get(me, 0)
        feasible: list[tuple[int, bytes]] = []
        for node_id, nbytes in by_node.items():
            if node_id == me:
                continue
            info = self.cluster_nodes.get(node_id)
            if info is None or info.get("state", "ALIVE") != "ALIVE":
                continue  # a DRAINING holder is migrating those bytes away
            if not need.is_subset_of(ResourceSet.from_raw(info["resources"])):
                continue
            feasible.append((nbytes, node_id))
        best_bytes = max((n for n, _ in feasible), default=0)
        if (not feasible
                or best_bytes - my_bytes < cfg.locality_min_arg_bytes):
            if len(self._locality_negcache) > 1024:
                self._locality_negcache = {
                    k: v for k, v in self._locality_negcache.items()
                    if v > now}
            self._locality_negcache[key] = now + 2.0
            return None
        # byte count decides; topology breaks the byte TIE (several
        # nodes hold the same resident bytes — e.g. a broadcast arg) in
        # favor of the ICI-nearest holder
        ties = [nid for n, nid in feasible if n == best_bytes]
        best, rerouted = self._topo_prefer(ties)
        if rerouted:
            self.m_topo_reroutes.inc()
        return self.cluster_nodes[best]["address"]

    def _warn_infeasible(self, spec):
        shape = tuple(sorted(spec.get("resources", {}).items()))
        if shape not in self._warned_infeasible:
            self._warned_infeasible.add(shape)
            logger.warning(
                "task %s demands resources %s that no node in the cluster "
                "can ever satisfy; it will hang until matching nodes join "
                "(reference warns identically: cluster_task_manager.cc)",
                spec.get("name", "?"), dict(spec.get("resources", {})))

    async def _pg_spillback(self, key) -> str | None:
        """A lease targeting a bundle this node doesn't host: redirect to
        the raylet that committed it (the GCS holds bundle→node placement;
        reference: lease_policy.h locality-aware lease target)."""
        if self.gcs is None:
            return None
        try:
            rec = await self.gcs.call("get_placement_group",
                                      {"pg_id": key[0]})
        except Exception:
            return None
        if rec is None or rec.get("state") != "CREATED":
            return None
        me = self.node_id.binary()
        for b in rec["bundles"]:
            if key[1] in (-1, b["bundle_index"]) and b["node_id"] != me:
                info = self.cluster_nodes.get(b["node_id"])
                if info is not None:
                    return info["address"]
        return None

    def _pop_idle_now(self, tpu: bool):
        """Pop an idle worker if one exists RIGHT NOW — no wait, no spawn
        (the grant path for soft/prewarm lease requests and for the tail
        of a batched grant)."""
        pool = self.idle_tpu if tpu else self.idle
        return pool.pop() if pool else None

    async def h_request_worker_lease(self, conn, d):
        """Grant worker leases. Plain form (no `count`): one lease,
        waiting on worker startup if needed — unchanged round-7 behavior.
        Batched form (`count`=N): grant up to N leases in ONE round trip
        from capacity that is idle now; only a hard request with zero
        idle workers waits (and possibly spawns) for a single worker. A
        `soft` request never spawns and never queues — a dry idle pool
        returns an empty grant list immediately, so owner-side lease
        pre-warm for bursts of tiny tasks cannot spawn-storm the node."""
        spec = d["spec"]
        lease_t0 = time.time()
        if _fp.ARMED:
            # grant seam: `raise` -> RemoteError at the owner's lease
            # request (typed retry/fail path); `exit` kills the raylet
            await _fp.fire_async_strict("lease.grant")
        batched = "count" in d
        count = max(1, int(d.get("count", 1)))
        soft = bool(d.get("soft"))
        hops = int(d.get("hops", 0))
        visited = list(d.get("visited") or ())
        if self._draining:
            # A draining node grants nothing: redirect the request to a
            # survivor (the spillback pickers already exclude DRAINING
            # peers, so two departing nodes can't ping-pong a request).
            # Soft prewarm just comes back empty; with no survivor the
            # owner queues exactly like an infeasible-everywhere task.
            if soft:
                return {"grants": []}
            addr = self._pick_spillback(spec, exclude=visited)
            if addr is not None:
                self.m_spillbacks.inc()
                return await self._spill(d, addr, hops + 1)
            fut = asyncio.get_running_loop().create_future()
            spec.setdefault("_queued_at", time.time())
            self.pending_leases.append((spec, fut))
            result = await fut
            if result.get("granted"):
                self._track_holder(conn, [result])
                self._note_lease_granted(lease_t0, spec, 1)
            if batched and "spillback" not in result:
                return {"grants": [result]}
            return result
        if hops == 0 and not soft:
            # Locality-aware lease targeting (reference: lease_policy.h):
            # a task whose plasma args are resident on another node is
            # leased THERE — moving the task to the data instead of the
            # data to the task. First hop only, so a redirected request
            # can still queue/spill on the target without ping-pong.
            addr = await self._locality_spillback(spec)
            if addr is not None:
                self.m_spillbacks.inc()
                self.m_locality_spillbacks.inc()
                return await self._spill(d, addr, 1)
        tpu = self._needs_tpu(spec)
        grants: list[dict] = []
        while len(grants) < count:
            acquired = self._try_acquire(spec)
            if acquired is None:
                break
            res, pg_key = acquired
            worker = self._pop_idle_now(tpu)
            if worker is None:
                if soft or grants:
                    # soft never spawns; a batch never blocks its
                    # already-granted leases behind worker startup
                    self._release(res, pg_key)
                    break
                try:
                    worker = await self._pop_worker(tpu=tpu)
                except Exception:
                    self._release(res, pg_key)
                    raise
            grants.append(self._lease_reply(worker, res, pg_key))
        if grants:
            if d.get("forwarded"):
                # Spillback-chain grant: the conn is a PEER RAYLET, not
                # the lease holder — the owner claims these via
                # adopt_leases over its own connection; unclaimed grants
                # are reclaimed at the deadline (reap loop).
                self.m_spillback_grants.inc(len(grants))
                self._note_unadopted(grants)
            elif conn.closed:
                # The holder died while we awaited worker spawn: its
                # disconnect callback already ran, so reclaim these
                # grants now — nobody can receive the reply or ever
                # return the leases.
                ids = {g["lease_id"] for g in grants}
                for w in list(self.workers.values()):
                    if w.lease_id in ids:
                        self._release(w.lease_resources, w.lease_pg)
                        self._push_worker(w)
                await self._dispatch_pending()
            else:
                self._track_holder(conn, grants)
            self._note_lease_granted(lease_t0, spec, len(grants))
            return {"grants": grants} if batched else grants[0]
        if soft:
            return {"grants": []}
        key = self._bundle_key(spec)
        if key is not None and self._find_bundle(key) is None:
            addr = await self._pg_spillback(key)
            if addr is not None:
                return await self._spill(d, addr, hops + 1)
        max_hops = self.config.lease_spillback_max_hops
        if not self._feasible_ever(spec):
            addr = self._pick_spillback(spec, exclude=visited)
            if addr is not None:
                self.m_spillbacks.inc()
                return await self._spill(d, addr, hops + 1)
            # Infeasible everywhere: queue until the cluster changes.
            self._warn_infeasible(spec)
        elif key is None and hops < max_hops:
            # Feasible here but saturated: offer it to a node that can run
            # it now rather than hoarding it (hop-capped to stop ping-pong
            # when the whole cluster is saturated).
            addr = await self._pick_spillback_load_aware(spec,
                                                         exclude=visited)
            if addr is not None:
                self.m_spillbacks.inc()
                return await self._spill(d, addr, hops + 1)
        fut = asyncio.get_running_loop().create_future()
        # queue-arrival stamp rides the spec so debug_state/doctor can age
        # the raylet's lease queue (carried along spillback forwards too)
        spec.setdefault("_queued_at", time.time())
        self.pending_leases.append((spec, fut))
        result = await fut
        if result.get("granted"):
            if d.get("forwarded"):
                self.m_spillback_grants.inc()
                self._note_unadopted([result])
            elif conn.closed:
                # The holder died while its request sat in the queue:
                # its disconnect callback already ran (empty lease set),
                # so reclaim this grant NOW — the reply can't be
                # delivered and nobody would ever return the lease.
                for w in list(self.workers.values()):
                    if w.lease_id == result["lease_id"]:
                        self._release(w.lease_resources, w.lease_pg)
                        self._push_worker(w)
                        break
                await self._dispatch_pending()
            else:
                self._track_holder(conn, [result])
            self._note_lease_granted(lease_t0, spec, 1)
        if batched and "spillback" not in result:
            return {"grants": [result]}
        return result

    async def _spill(self, d: dict, addr: str, hops: int):
        """Redirect a lease request to the raylet at `addr` by CHAINING
        it raylet→raylet — this raylet relays the peer's grant back
        toward the owner, so a cross-node burst costs the owner ONE lease
        RPC instead of a redial per hop. The chain is hop-capped
        (lease_spillback_max_hops), cycle-guarded (`visited` addresses are
        never re-picked), and carries the spec unchanged — locality hints
        (args) and the PR 6 trace context ride along. A failed forward or
        an exhausted hop budget degrades to the owner-visible
        {"spillback": addr} reply: the owner redials `addr` itself."""
        if hops > self.config.lease_spillback_max_hops:
            return {"spillback": addr, "hops": hops}
        if _fp.ARMED:
            # forward seam: `raise` degrades to the owner-mediated bounce
            # (liveness must not depend on the chain); `exit` kills this
            # raylet mid-chain (chaos sweep)
            try:
                await _fp.fire_async_strict("lease.spillback")
            except _fp.FailpointError:
                return {"spillback": addr, "hops": hops}
        fwd = dict(d)
        fwd["hops"] = hops
        fwd["forwarded"] = True
        fwd["visited"] = list(d.get("visited") or ()) + [self.address]
        self.m_spillback_forwards.inc()
        try:
            conn = await self._raylet_conn(addr)
            reply = await conn.call("request_worker_lease", fwd)
        except Exception as e:
            # peer died / unreachable mid-chain: degrade to the bounce
            # so the owner can redial (or re-spill elsewhere)
            logger.warning("lease spillback forward to %s failed (%s); "
                           "bouncing to owner", addr, e)
            return {"spillback": addr, "hops": hops}
        root = tracing.from_wire((d.get("spec") or {}).get("trace"))
        if root is not None:
            tracing.record_span("raylet.spillback", time.time(), time.time(),
                                tracing.child(root), {"to": addr,
                                                      "hops": hops})
        return reply

    def _note_unadopted(self, grants):
        # `adopt` tells the owner these grants arrived over a spillback
        # chain: it must claim them (adopt_leases at granted_by) before
        # this deadline, or the reap loop returns them to the idle pool.
        deadline = time.monotonic() + 10.0
        for g in grants:
            g["adopt"] = True
            self._unadopted[g["lease_id"]] = deadline

    async def h_adopt_leases(self, conn, d):
        """The true lease holder claims leases granted for a forwarded
        request: holder-death reclaim (_on_disconnect) now watches the
        OWNER's connection, exactly as for a directly-requested lease.
        Returns the lease_ids actually adopted — one missing means the
        unadopted deadline already reclaimed it (the owner treats that
        lease as lost and re-requests)."""
        held = conn.context.setdefault("lease_ids", set())
        adopted = []
        for lid in d["lease_ids"]:
            if self._unadopted.pop(lid, None) is None:
                continue
            held.add(lid)
            adopted.append(lid)
        return {"adopted": adopted}

    def _reap_unadopted(self):
        """Reclaim forwarded-request grants whose owner never adopted
        them (died between the relayed grant and adopt_leases)."""
        if not self._unadopted:
            return False
        now = time.monotonic()
        expired = [lid for lid, dl in self._unadopted.items() if dl < now]
        reclaimed = False
        for lid in expired:
            del self._unadopted[lid]
            for w in list(self.workers.values()):
                if w.lease_id == lid:
                    logger.warning("reclaiming never-adopted spillback "
                                   "lease %s", lid.hex())
                    self._release(w.lease_resources, w.lease_pg)
                    self._push_worker(w)
                    reclaimed = True
                    break
        return reclaimed

    def _note_lease_granted(self, t0: float, spec, count: int):
        """Raylet-side scheduling hop: histogram always, a `raylet.lease`
        span (child of the requesting task's root) when the spec carries
        a sampled trace context."""
        now = time.time()
        root = tracing.from_wire(spec.get("trace"))
        self.m_lease_grant_s.observe(now - t0,
                                     exemplar=tracing.exemplar_of(root))
        if root is not None:
            tracing.record_span("raylet.lease", t0, now,
                                tracing.child(root),
                                {"name": spec.get("name", "?"),
                                 "count": count})

    @staticmethod
    def _track_holder(conn, grants):
        """Remember which connection holds each lease, so a lease holder
        that crashes (driver killed, owner worker dies mid-pipeline)
        returns its leases instead of stranding workers+resources until
        node death (_on_disconnect reclaims)."""
        held = conn.context.setdefault("lease_ids", set())
        for g in grants:
            held.add(g["lease_id"])

    @staticmethod
    def _needs_tpu(spec) -> bool:
        return float(spec.get("resources", {}).get("TPU") or 0) > 0

    async def _grant_lease(self, spec, acquired):
        res, pg_key = acquired
        try:
            worker = await self._pop_worker(tpu=self._needs_tpu(spec))
        except Exception:
            self._release(res, pg_key)
            raise
        return self._lease_reply(worker, res, pg_key)

    def _lease_reply(self, worker, res, pg_key) -> dict:
        self._lease_seq += 1
        self.m_leases_granted.inc()
        lease_id = self._lease_seq.to_bytes(8, "big")
        worker.lease_id = lease_id
        worker.lease_resources = res
        worker.lease_pg = pg_key
        return {
            "granted": True,
            "lease_id": lease_id,
            "worker_id": worker.worker_id,
            "worker_address": worker.address,
            "task_channel": worker.task_channel,
            # which raylet granted: a forwarded (spillback-chain) grant
            # reaches the owner through its LOCAL raylet's reply, and the
            # owner must return the lease (and adopt it) HERE
            "granted_by": self.address,
        }

    async def h_return_worker(self, conn, d):
        if _fp.ARMED:
            await _fp.fire_async_strict("lease.return")
        held = conn.context.get("lease_ids")
        if held is not None:
            held.discard(d["lease_id"])
        self._unadopted.pop(d["lease_id"], None)
        worker = None
        for w in self.workers.values():
            if w.lease_id == d["lease_id"]:
                worker = w
                break
        if worker is None:
            return False
        self._release(worker.lease_resources, worker.lease_pg)
        if d.get("worker_exiting") or worker.conn.closed:
            self.workers.pop(worker.worker_id, None)
        else:
            self._push_worker(worker)
        await self._dispatch_pending()
        return True

    async def _dispatch_pending(self):
        if self._draining:
            # no grants off the queue while draining; _drain bounces the
            # queue to survivors and the exit-time conn close sends any
            # stragglers through the owner's normal retry path
            return
        if _fp.ARMED:
            # dispatch seam: `raise` leaves queued leases queued (the
            # next return/heartbeat/bundle event re-drives the queue)
            await _fp.fire_async_strict("raylet.dispatch")
        remaining = []
        for spec, fut in self.pending_leases:
            if fut.done():
                continue
            acquired = self._try_acquire(spec)
            if acquired is None:
                remaining.append((spec, fut))
                continue
            try:
                fut.set_result(await self._grant_lease(spec, acquired))
            except Exception as e:  # pragma: no cover
                if not fut.done():
                    fut.set_exception(e)
        self.pending_leases = remaining

    # ------------------------------------------------------------------
    # actors (GCS-driven)
    # ------------------------------------------------------------------

    async def h_create_actor(self, conn, d):
        spec = d["spec"]
        if self._draining:
            # looks like a stale-availability miss to the GCS: it zeroes
            # its view of this node and requeues on an ALIVE one
            raise InsufficientResources("node is draining")
        acquired = self._try_acquire(spec)
        if acquired is None:
            # GCS checked the resource snapshot, but we may have raced.
            raise InsufficientResources("insufficient resources for actor")
        res, pg_key = acquired
        try:
            worker = await asyncio.wait_for(
                self._pop_worker(ignore_cap=True, tpu=self._needs_tpu(spec)),
                self.config.worker_register_timeout_s)
        except Exception:
            self._release(res, pg_key)
            raise
        worker.actor_id = spec["actor_id"]
        worker.lease_resources = res
        worker.lease_pg = pg_key
        try:
            reply = await worker.conn.call("create_actor", {"spec": spec})
            # The worker packs constructor exceptions as an error result
            # instead of raising over RPC — surface them so the GCS records
            # a real death cause (reference: creation failures publish the
            # actor as DEAD with the error, gcs_actor_manager.h:125-127).
            if any(r.get("err") for r in (reply or {}).get("returns", [])):
                raise RuntimeError(
                    f"actor constructor failed: "
                    f"{(reply or {}).get('error_repr', 'unknown error')}")
        except Exception:
            worker.actor_id = None
            self._release(res, pg_key)
            worker.lease_resources = None
            worker.lease_pg = None
            if not worker.conn.closed:
                self._push_worker(worker)
            raise
        return {"worker_address": worker.address,
                "worker_id": worker.worker_id,
                "task_channel": worker.task_channel}

    async def h_kill_actor_worker(self, conn, d):
        """`kill()` is forceful, and done when it is answered: SIGKILL,
        and the reply once the worker has left the process table (its
        chips and arena mappings are free: `node.wait_until_left`) and
        its lease is back. Nothing is left to a timer that would die
        with this raylet when `shutdown()` is the caller's next line."""
        worker = self.workers.get(d["worker_id"])
        if worker is None:
            return False
        worker.conn.context["intended_exit"] = True
        try:
            os.kill(worker.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        took = await asyncio.get_running_loop().run_in_executor(
            None, wait_until_left,
            lambda: [] if has_left(worker.pid) else [worker.pid])
        while (worker.worker_id in self.workers
               and not self._shutting_down):
            await asyncio.sleep(0.002)  # its connection's end, just behind
        logger.info("killed worker pid=%d: it left the process table "
                    "after %.2f s", worker.pid, took)
        return True

    async def h_actor_exiting(self, conn, d):
        """Actor worker announces a clean exit (exit_actor())."""
        conn.context["intended_exit"] = True
        return True

    # ------------------------------------------------------------------
    # placement group bundles (2PC; reference:
    # placement_group_resource_manager.h:51)
    # ------------------------------------------------------------------

    async def h_prepare_bundle(self, conn, d):
        if self._draining:
            return False  # a departing node reserves nothing (2PC abort)
        need = ResourceSet.from_raw(d["resources"])
        if not need.is_subset_of(self.available):
            return False
        self.available.subtract(need)
        self.bundles[(d["pg_id"], d["bundle_index"])] = {
            "resources": need,
            "available": need.copy(),
            "state": "PREPARED",
        }
        return True

    async def h_commit_bundle(self, conn, d):
        bundle = self.bundles.get((d["pg_id"], d["bundle_index"]))
        if bundle is None:
            return False
        bundle["state"] = "COMMITTED"
        await self._dispatch_pending()
        return True

    async def h_cancel_bundle(self, conn, d):
        """Remove a bundle. Only the unleased remainder goes back to
        self.available immediately; the leased portion returns as each
        lease ends (_release tracks it via _removed_bundles). Workers
        still leasing from the removed group are killed, matching the
        reference's kill-tasks-of-removed-PG behavior
        (placement_group_resource_manager.h:51)."""
        key = (d["pg_id"], d["bundle_index"])
        bundle = self.bundles.pop(key, None)
        if bundle is not None:
            self.available.add(bundle["available"])
            outstanding = bundle["resources"].copy()
            outstanding.subtract(bundle["available"])
            if not outstanding.is_empty():
                prior = self._removed_bundles.setdefault(
                    d["pg_id"], ResourceSet({}))
                prior.add(outstanding)
            for w in list(self.workers.values()):
                if w.lease_pg is not None and w.lease_pg[0] == d["pg_id"]:
                    await self.h_kill_actor_worker(
                        conn, {"worker_id": w.worker_id})
            await self._dispatch_pending()
        return True

    async def h_return_bundle(self, conn, d):
        return await self.h_cancel_bundle(conn, d)

    # ------------------------------------------------------------------
    # object manager (reference: object_manager.h, local_object_manager.h)
    # ------------------------------------------------------------------

    async def h_notify_object_sealed(self, conn, d):
        oid = d["object_id"]
        size = d["size"]
        # a deferral recorded against this id's PREVIOUS incarnation must
        # not delete the fresh copy when the old transfer's pins drop
        self.transfer_pins.cancel_deferred_free(oid)
        self.local_objects[oid] = {"size": size, "pinned": True, "spilled": None}
        self.store_used += size
        await self._wake_object_waiters(oid)
        # Location registration + spill check ride a background task: the
        # putting worker shouldn't pay a GCS round trip per large put
        # (remote pulls retry until the directory catches up anyway).
        if self.gcs is not None:
            async def _register():
                await self._register_location(oid, size)
                try:
                    await self._maybe_spill()
                except Exception:
                    # Spill failures (disk full, perms) must be visible,
                    # not an unretrieved-task exception; the next seal
                    # retries.
                    logger.exception("object spill failed")

            asyncio.create_task(_register())
        else:
            await self._maybe_spill()
        return True

    async def _register_location(self, oid: bytes, size: int):
        """Record this node as a holder of `oid` (with its size) in the
        GCS object directory — best-effort: remote pulls retry their
        lookups until the directory catches up."""
        if self.gcs is None:
            return
        try:
            await self.gcs.call("add_object_location", {
                "object_id": oid, "node_id": self.node_id.binary(),
                "size": size})
        except Exception:
            pass

    async def _wake_object_waiters(self, oid: bytes):
        for fut in self.object_waiters.pop(oid, []):
            if not fut.done():
                fut.set_result(True)

    async def h_wait_object_local(self, conn, d):
        """Returns True once the object is local, False on `timeout`, or
        the string \"lost\" when the pull declared typed loss (the GCS
        directory stayed empty past pull_no_location_timeout_s) — the
        caller maps \"lost\" onto recovery/ObjectLostError instead of
        re-probing."""
        oid = d["object_id"]
        timeout = d.get("timeout") or None
        rec = self.local_objects.get(oid)
        if rec is not None:
            if rec["spilled"]:
                await self._restore_spilled(oid)
            return True
        fut = asyncio.get_running_loop().create_future()
        self.object_waiters.setdefault(oid, []).append(fut)
        asyncio.create_task(self._pull_object(oid))
        if timeout:
            try:
                return await asyncio.wait_for(asyncio.shield(fut), timeout)
            except asyncio.TimeoutError:
                return False
        return await fut

    async def h_hint_pull_purpose(self, conn, d):
        """Advisory label for an upcoming pull of `object_id` (e.g.
        \"kv_warm\" before a prefix-page import): consumed by the next
        streaming pull of that object so transfer introspection can
        attribute the bytes. Best-effort — no pull ever depends on it."""
        transfer.hint_pull(d["object_id"], d.get("purpose") or "")
        return True

    @property
    def _pull_sem(self) -> asyncio.Semaphore:
        # Admission control (reference: pull_manager.h:26): bound the
        # number of concurrent inbound transfers so a burst of pulls
        # can't monopolize bandwidth/memory; queued pulls wait here.
        if self._pull_sem_obj is None:
            self._pull_sem_obj = asyncio.Semaphore(
                self.config.max_concurrent_object_pulls)
        return self._pull_sem_obj

    async def _pull_object(self, oid: bytes, hint_addr: str | None = None):
        """Pull one object from remote nodes (reference: pull_manager.h:26
        admission + object_manager chunked transfer; streaming/striping in
        raylet/transfer.py). Retries while waiters exist, with exponential
        backoff between directory lookups; a directory that stays EMPTY
        past pull_no_location_timeout_s propagates typed loss to the
        h_wait_object_local waiters instead of spinning forever.
        `hint_addr`: a node known to hold the object (push path) — tried
        immediately with NO GCS location lookup; on failure falls back to
        the normal lookup/retry loop so a concurrent demand waiter
        (deduped into this pull) is never stranded."""
        if oid in self._pulls_inflight:
            return
        self._pulls_inflight.add(oid)
        try:
            if hint_addr is not None and oid not in self.local_objects:
                try:
                    async with self._pull_sem:
                        if oid not in self.local_objects:
                            await self._pull_any(oid, [hint_addr])
                    return
                except Exception as e:
                    logger.warning("hinted pull of %s from %s failed: %s",
                                   oid[:6].hex(), hint_addr, e)
            empty_since: float | None = None
            backoff = 0.05
            while oid not in self.local_objects and oid in self.object_waiters:
                try:
                    locations = await self.gcs.call(
                        "get_object_locations", {"object_id": oid})
                except Exception:
                    locations = None  # GCS hiccup: not evidence of loss
                addresses = []
                for node_id in locations or ():
                    if node_id == self.node_id.binary():
                        continue
                    info = self.cluster_nodes.get(node_id)
                    if info is not None:
                        addresses.append(info["address"])
                if addresses:
                    empty_since = None
                    try:
                        async with self._pull_sem:
                            if oid in self.local_objects:
                                break
                            await self._pull_any(oid, addresses)
                        break
                    except Exception as e:
                        logger.warning("pull of %s failed: %s",
                                       oid[:6].hex(), e)
                elif locations is not None and not locations:
                    # NOBODY claims a copy. Give the directory a bounded
                    # window (a seal's registration is async), then fail
                    # the waiters typed so _read_plasma stops burning
                    # probe cycles on an object that is simply gone.
                    now = time.monotonic()
                    if empty_since is None:
                        empty_since = now
                    elif (now - empty_since
                          > self.config.pull_no_location_timeout_s):
                        self._fail_object_waiters(oid)
                        return
                else:
                    # copies registered on nodes we can't see (yet), or
                    # the GCS lookup failed: keep retrying, but don't
                    # run the loss clock
                    empty_since = None
                await asyncio.sleep(backoff)
                backoff = min(backoff * 2, 2.0)
        finally:
            self._pulls_inflight.discard(oid)

    def _fail_object_waiters(self, oid: bytes):
        """Typed loss: wake every h_wait_object_local waiter with the
        \"lost\" sentinel (the owner-side _read_plasma maps it onto its
        recovery/ObjectLostError path instead of re-probing)."""
        waiters = self.object_waiters.pop(oid, [])
        for fut in waiters:
            if not fut.done():
                fut.set_result("lost")
        if waiters:
            logger.warning(
                "object %s has no registered location after %.1fs; "
                "declared lost to %d waiter(s)", oid[:6].hex(),
                self.config.pull_no_location_timeout_s, len(waiters))

    async def _raylet_conn(self, address: str) -> rpc.Connection:
        conn = self._raylet_conns.get(address)
        if conn is not None and not conn.closed:
            return conn
        # per-address dial lock: concurrent pulls must share ONE conn —
        # a replaced-but-live conn would strand its in-flight calls in a
        # GC-able island (same hang class as core_worker._peer)
        lock = self._raylet_dial_locks.setdefault(address, asyncio.Lock())
        async with lock:
            conn = self._raylet_conns.get(address)
            if conn is None or conn.closed:
                conn = await rpc.connect(
                    rpc.prefer_uds(address, os.path.join(self.session_dir,
                                                         "sock"),
                                   local_ips=("127.0.0.1",
                                              self.config.node_ip_address)),
                    name=f"raylet->{address}")
                old = self._raylet_conns.get(address)
                self._raylet_conns[address] = conn
                if old is not None and not old.closed:
                    asyncio.ensure_future(old.close())
        return conn

    def _bulk_addr(self, address: str) -> str | None:
        """Map a peer raylet's control address to its bulk channel
        (advertised via the GCS node table), preferring the same-node UDS
        twin. None when the peer predates/disabled the bulk plane."""
        for info in self.cluster_nodes.values():
            if info.get("address") == address:
                bulk = info.get("bulk_address")
                if not bulk:
                    return None
                return rpc.prefer_uds(
                    bulk, os.path.join(self.session_dir, "sock"),
                    local_ips=("127.0.0.1", self.config.node_ip_address))
        return None

    async def _pull_any(self, oid: bytes, addresses: list[str]):
        """Pull `oid` given candidate holder control addresses: the
        streaming bulk plane (striped across every source with a bulk
        channel) first; the one-source-at-a-time chunked pull over the
        control connection when no source serves a bulk channel or the
        streaming pull raises."""
        bulk = [b for b in (self._bulk_addr(a) for a in addresses) if b]
        if bulk:
            try:
                await self._pull_streaming(oid, bulk)
                return
            except Exception as e:
                # advertised-but-unreachable bulk channels (firewalled
                # ephemeral port, half-up peer) must degrade to the
                # control-path pull for THIS attempt, not hang the
                # retry loop on streaming forever
                logger.warning(
                    "streaming pull of %s failed (%s); falling back "
                    "to the control-path pull", oid[:6].hex(), e)
        last: Exception | None = None
        for address in addresses:
            try:
                await self._pull_control_path(oid, address)
                return
            except Exception as e:
                logger.warning("pull of %s from %s failed: %s",
                               oid[:6].hex(), address, e)
                last = e
        raise last if last is not None else KeyError(
            f"no source for {oid[:6].hex()}")

    async def _pull_streaming(self, oid: bytes, bulk_addresses: list[str]):
        """One streaming pull over the bulk data plane, striped across
        the sources (transfer.streaming_pull) on an executor thread so
        the raylet loop keeps serving heartbeats/leases."""
        cfg = self.config
        object_id = ObjectID(oid)
        loop = asyncio.get_running_loop()
        # bulk-pull trace entry point: the wire context rides the pull
        # request so the SOURCE raylet's serve span joins this tree
        ctx = tracing.maybe_trace()
        t0 = time.time()
        purpose = transfer.take_pull_hint(oid)
        size = await loop.run_in_executor(None, lambda: transfer.streaming_pull(
            oid, object_id, self.store, bulk_addresses,
            chunk=cfg.object_transfer_chunk_size,
            stripe=cfg.object_transfer_stripe_size,
            max_sources=cfg.max_pull_sources,
            io_timeout=cfg.bulk_transfer_io_timeout_s,
            trace=tracing.to_wire(ctx) if ctx is not None else None,
            purpose=purpose))
        transfer.M_PULL_S.observe(time.time() - t0,
                                  exemplar=tracing.exemplar_of(ctx))
        if ctx is not None:
            tracing.record_span("transfer.pull", t0, time.time(), ctx,
                                {"object_id": oid[:6].hex(),
                                 "bytes": size,
                                 "sources": len(bulk_addresses)})
        self._pulled_local(oid, size)
        await self._wake_object_waiters(oid)

    async def _pull_control_path(self, oid: bytes, address: str):
        """The pull _pull_any falls back to when the bulk plane cannot
        carry the object: one fetch_chunk request-response at a time
        over the shared raylet<->raylet CONTROL connection — a full RTT,
        a bytes() copy out of the arena and a pickle frame per chunk,
        and control RPCs queue behind the frames. Error handling, not an
        alternative: it needs nothing but the connection every raylet
        already has to every peer."""
        conn = await self._raylet_conn(address)
        info = await conn.call("object_info", {"object_id": oid})
        if info is None:
            raise KeyError("remote no longer has object")
        size = info["size"]
        object_id = ObjectID(oid)
        try:
            buf = self.store.create(object_id, size)
        except FileExistsError:
            # stale .build from an abandoned pull (files backend)
            self.store.abort(object_id)
            buf = self.store.create(object_id, size)
        try:
            offset = 0
            chunk = self.config.object_transfer_chunk_size
            while offset < size:
                data = await conn.call("fetch_chunk", {
                    "object_id": oid, "offset": offset,
                    "size": min(chunk, size - offset)})
                buf.view[offset : offset + len(data)] = data
                transfer.M_PULL_BYTES.inc(len(data))
                offset += len(data)
            buf.close()
            self.store.seal(object_id)
        except BaseException:
            buf.close()
            self.store.abort(object_id)
            raise
        finally:
            # release the sender-side transfer pin promptly (the shared
            # control conn never closes, so TTL would otherwise be the
            # only release)
            try:
                await conn.notify("transfer_done", {"object_id": oid})
            except Exception:
                pass  # TTL sweep is the backstop
        self._pulled_local(oid, size)
        await self._wake_object_waiters(oid)

    def _pulled_local(self, oid: bytes, size: int):
        """Bookkeeping for a completed pull: the copy is resident here,
        and the GCS directory learns about it (background — remote
        lookups retry anyway) so later pulls can stripe across us and
        locality-aware leasing can weigh this node."""
        self.transfer_pins.cancel_deferred_free(oid)  # fresh incarnation
        self.local_objects[oid] = {"size": size, "pinned": False,
                                   "spilled": None}
        self.store_used += size
        self.m_objects_pulled.inc()
        if self.gcs is not None:
            asyncio.create_task(self._register_location(oid, size))

    async def h_push_hint(self, conn, d):
        """Proactive transfer start (the PushManager analog, reference:
        push_manager.h:29): a node holding `object_id` tells us we'll
        need it (task args racing a spilled-back lease). Dedup comes for
        free from _pulls_inflight; admission from the pull semaphore."""
        oid = d["object_id"]
        if oid in self.local_objects or oid in self._pulls_inflight:
            return True
        asyncio.create_task(self._pull_object(oid, hint_addr=d["from"]))
        return True

    async def h_push_objects_to(self, conn, d):
        """Owner side: our worker is about to run a task on `target`
        whose plasma args live here — hint the target so arg transfer
        overlaps with lease/worker setup."""
        target = d["target"]
        me = self.address
        for oid in d["object_ids"]:
            if oid not in self.local_objects:
                continue
            try:
                tconn = await self._raylet_conn(target)
                await tconn.notify("push_hint", {"object_id": oid,
                                                 "from": me})
            except Exception as e:
                logger.debug("push hint to %s failed: %s", target, e)
        return True

    def _control_pin_token(self, conn):
        return ("rpc", id(conn))

    async def h_object_info(self, conn, d):
        """Control-path transfer registration: reports size AND takes a
        transfer pin (TTL-leased, refreshed by each fetch_chunk) so the
        object can't be freed/evicted between the puller's chunks — the
        old mid-pull KeyError race."""
        oid = d["object_id"]
        if _fp.ARMED:
            await _fp.fire_async_strict("transfer.register")
        rec = self.local_objects.get(oid)
        if rec is None:
            return None
        if rec["spilled"]:
            await self._restore_spilled(oid)
        self.transfer_pins.pin(oid, self._control_pin_token(conn),
                               self.config.transfer_pin_ttl_s)
        return {"size": rec["size"]}

    async def h_fetch_chunk(self, conn, d):
        from ray_tpu import exceptions as exc

        oid = d["object_id"]
        object_id = ObjectID(oid)
        rec = self.local_objects.get(oid)
        if rec is not None and rec["spilled"]:
            # spilled between the puller's object_info and this chunk
            await self._restore_spilled(oid)
        if rec is not None:
            # refresh the transfer-pin lease for this puller
            self.transfer_pins.pin(oid, self._control_pin_token(conn),
                                   self.config.transfer_pin_ttl_s)
        buf = self.store.get(object_id)
        if buf is None:
            # typed (a puller fails over to another source / retries the
            # directory) instead of the old raw KeyError
            raise exc.ObjectLostError(object_id.hex())
        try:
            return bytes(buf.view[d["offset"] : d["offset"] + d["size"]])
        finally:
            buf.close()

    async def h_transfer_done(self, conn, d):
        """A control-path puller announces its transfer finished: release
        the pin NOW instead of waiting out the TTL lease — the
        raylet<->raylet control connection the pin is keyed to is cached
        indefinitely, so disconnect-release never fires for this path,
        and a TTL-only release would block frees/spill of the object for
        transfer_pin_ttl_s after every pull."""
        freeable = self.transfer_pins.unpin(d["object_id"],
                                            self._control_pin_token(conn))
        if freeable:
            await self._complete_deferred_frees(freeable)
        return True

    async def h_peer_ping(self, conn, d):
        """Round-trip a ping to `address` over THIS raylet's shared
        raylet<->raylet CONTROL connection — the one the control-path
        pull fallback also rides. The cross_node_pull bench uses it to
        measure control-plane head-of-line blocking during a bulk
        transfer."""
        t0 = time.monotonic()
        peer = await self._raylet_conn(d["address"])
        await peer.call("ping", {})
        return time.monotonic() - t0

    async def h_get_logs(self, conn, d):
        """Node-local log access — the per-node dashboard-agent role
        (reference: dashboard/agent.py log routes): the dashboard fans
        out here instead of aggregating every node's logs centrally.
        Without 'file': list this node's log files; with 'file': tail
        the last `lines` lines (bounded read)."""
        log_dir = os.path.join(self.session_dir, "logs")
        fname = d.get("file")
        if not fname:
            try:
                entries = []
                for name in sorted(os.listdir(log_dir)):
                    path = os.path.join(log_dir, name)
                    if os.path.isfile(path):
                        entries.append({"name": name,
                                        "size": os.path.getsize(path)})
                return entries
            except FileNotFoundError:
                return []
        if os.path.basename(fname) != fname or fname in (".", ".."):
            raise ValueError(f"log file must be a bare name: {fname!r}")
        path = os.path.join(log_dir, fname)
        lines = max(1, min(int(d.get("lines", 200)), 10_000))
        try:
            if not os.path.isfile(path):
                raise FileNotFoundError(path)
            size = os.path.getsize(path)
            with open(path, "rb") as f:
                f.seek(max(0, size - 512 * lines))  # bounded tail read
                data = f.read()
        except (FileNotFoundError, IsADirectoryError):
            raise ValueError(f"no log file {fname!r} on this node")
        text = data.decode(errors="replace")
        return "\n".join(text.splitlines()[-lines:])

    async def h_spill_now(self, conn, d):
        """Synchronous spill on behalf of a worker whose store create
        failed: move residents to disk until `need_bytes` fits (plus the
        normal threshold), oldest first."""
        need = int(d.get("need_bytes", 0))
        limit = max(0, int(self.config.object_store_memory
                           * self.config.object_spilling_threshold) - need)
        for oid, rec in list(self.local_objects.items()):
            if self.store_used <= limit:
                break
            if not rec["spilled"] and not self.transfer_pins.pinned(oid):
                await self._spill_one(oid, rec)
        return True

    async def h_pin_object(self, conn, d):
        rec = self.local_objects.get(d["object_id"])
        if rec is not None:
            rec["pinned"] = bool(d.get("pinned", True))
        return True

    async def h_free_objects(self, conn, d):
        for oid in d["object_ids"]:
            # atomic check-and-defer: a registered transfer defers the
            # free until the last pin drops or its TTL lease lapses (the
            # _reap_loop sweep completes it); the one-step form cannot
            # race a concurrent last-unpin into a stranded deferral
            if self.transfer_pins.defer_free_if_pinned(oid):
                continue
            await self._free_one(oid)
        return True

    async def _free_one(self, oid: bytes):
        rec = self.local_objects.pop(oid, None)
        if rec is None:
            return
        freed = 0
        if rec["spilled"]:
            try:
                os.unlink(rec["spilled"])
            except FileNotFoundError:
                pass
        else:
            freed = self.store.delete(ObjectID(oid))
        self.store_used = max(0, self.store_used - freed)
        if self.gcs is not None:
            try:
                await self.gcs.call("remove_object_location", {
                    "object_id": oid, "node_id": self.node_id.binary()})
            except Exception:
                pass

    async def _complete_deferred_frees(self, oids):
        for oid in oids:
            await self._free_one(oid)

    def complete_deferred_frees_threadsafe(self, oids):
        """Entry point for bulk-channel threads whose connection teardown
        released the last pin on a free-deferred object."""
        if self._loop is None or not oids:
            return
        try:
            asyncio.run_coroutine_threadsafe(
                self._complete_deferred_frees(list(oids)), self._loop)
        except RuntimeError:
            pass

    async def _maybe_spill(self):
        """Spill cold unpinned objects to disk above the usage threshold
        (reference: local_object_manager.h SpillObjects). Safe on BOTH
        backends: the files store copies before unlink, and the native
        arena's delete zombifies under outstanding reader pins (store.cc
        rts_delete) — the block is only reused after the last zero-copy
        view releases, so spilling can never corrupt a live reader.
        Zombie blocks do keep arena bytes busy until released, which is
        why the threshold leaves headroom below physical capacity."""
        limit = int(self.config.object_store_memory
                    * self.config.object_spilling_threshold)
        if self.store_used <= limit:
            return
        os.makedirs(self.spill_dir, exist_ok=True)
        for oid, rec in list(self.local_objects.items()):
            if self.store_used <= limit:
                break
            # reference semantics: the pin blocks EVICTION (losing the
            # only copy), not spilling — the spill file preserves the
            # bytes, so even owner-pinned primaries may move to disk
            # under pressure (local_object_manager.h SpillObjects spills
            # pinned primaries exactly the same way)
            if rec["spilled"]:
                continue
            if self.transfer_pins.pinned(oid):
                # a registered transfer is streaming this object out of
                # the arena right now: deleting the store entry under it
                # would abort the stream (and on the files backend orphan
                # the mmap) — skip until the pin lease lapses
                continue
            await self._spill_one(oid, rec)

    async def _spill_one(self, oid: bytes, rec: dict):
        object_id = ObjectID(oid)
        buf = self.store.get(object_id)
        if buf is None:
            return
        os.makedirs(self.spill_dir, exist_ok=True)
        path = os.path.join(self.spill_dir, object_id.hex())
        with open(path, "wb") as f:
            f.write(buf.view)
        buf.close()
        self.store.delete(object_id)
        rec["spilled"] = path
        self.store_used -= rec["size"]
        logger.info("spilled %s (%d bytes)", object_id.hex()[:12],
                    rec["size"])

    async def _restore_spilled(self, oid: bytes):
        rec = self.local_objects.get(oid)
        if rec is None or not rec["spilled"]:
            return
        object_id = ObjectID(oid)
        with open(rec["spilled"], "rb") as f:
            data = f.read()
        try:
            self.store.put_bytes(object_id, data)
        except MemoryError:
            # the store is the reason this object was spilled — push
            # other residents out until this one fits, then retry once
            # (bounded: spilling everything would thrash alternating
            # restores into O(n²) disk churn)
            target = max(
                0, int(self.config.object_store_memory
                       * self.config.object_spilling_threshold)
                - rec["size"])
            for other, orec in list(self.local_objects.items()):
                if self.store_used <= target:
                    break
                if (other != oid and not orec["spilled"]
                        and not self.transfer_pins.pinned(other)):
                    await self._spill_one(other, orec)
            self.store.put_bytes(object_id, data)
        os.unlink(rec["spilled"])
        rec["spilled"] = None
        self.store_used += rec["size"]

    # ------------------------------------------------------------------
    # cluster info
    # ------------------------------------------------------------------

    async def h_set_resource(self, conn, d):
        """Dynamically resize one resource's capacity on this node
        (reference: ray.experimental.set_resource →
        node_manager.cc resource update path). Capacity 0 deletes it."""
        from ray_tpu._private.common import quantize

        name = d["resource_name"]
        new_total = quantize(float(d["capacity"]))
        old_total = self.total.raw().get(name, 0)
        delta = new_total - old_total
        t = self.total.raw()
        a = self.available.raw()
        if new_total <= 0:
            # delete from totals, but keep availability DELTA accounting:
            # leases still out will release back into `a`, and dropping
            # the entry here would let that release resurrect capacity
            # for a resource that no longer exists
            t.pop(name, None)
            a[name] = a.get(name, 0) - old_total
            if a[name] == 0:
                a.pop(name)
        else:
            t[name] = new_total
            a[name] = a.get(name, 0) + delta  # may go negative while busy
        self.total = ResourceSet.from_raw(t)
        self.available = ResourceSet.from_raw(a)
        # fresh capacity may unblock queued leases
        await self._dispatch_pending()
        return {"total": self.total.raw(), "available": self.available.raw()}

    def _gauge_snapshot(self, snap: dict) -> dict:
        """Fold this raylet's live gauges into a metrics snapshot — used
        by BOTH h_get_metrics and the heartbeat piggyback, so the GCS
        metrics-history rings (what the autoscaler's busy/idle predicate
        reads) carry the same series the direct RPC shows."""
        snap["raylet.num_workers"] = {"type": "gauge",
                                      "value": len(self.workers)}
        snap["raylet.store_used_bytes"] = {"type": "gauge",
                                           "value": self.store_used}
        snap["raylet.local_objects"] = {"type": "gauge",
                                        "value": len(self.local_objects)}
        snap["raylet.pending_leases"] = {"type": "gauge",
                                         "value": len(self.pending_leases)}
        snap["raylet.active_leases"] = {
            "type": "gauge",
            "value": sum(1 for w in self.workers.values()
                         if w.lease_id is not None
                         or w.actor_id is not None)}
        snap["raylet.transfer_pins"] = {"type": "gauge",
                                        "value": self.transfer_pins.count()}
        return snap

    async def h_get_metrics(self, conn, d):
        from ray_tpu._private import stats

        snap = self._gauge_snapshot(stats.snapshot())
        # fold in per-worker process metrics (user-defined metrics from
        # util/metrics.py live in worker processes)
        import asyncio

        async def _pull(conn):
            try:
                return await asyncio.wait_for(
                    conn.call("get_stats", {}), timeout=2.0)
            except Exception:
                return {}

        # workers AND connected drivers: the submit-side task histograms
        # (core.task_lease_wait_s etc.) live in the OWNER process, which
        # for driver-submitted work is the driver — without its fold the
        # doctor's K*p99 thresholds would never see those stages
        conns = [w.conn for w in list(self.workers.values())
                 if not w.conn.closed]
        conns += [c for c in list(self.server.connections)
                  if c.context.get("driver") and not c.closed]
        worker_snaps = await asyncio.gather(*[_pull(c) for c in conns])
        # raylet-owned names are never clobbered by a worker metric that
        # happens to share the name; incompatible merges log once
        reserved = set(snap)
        logged = self._metric_merge_logged
        for ws in worker_snaps:
            for name, m in ws.items():
                cur = snap.get(name)
                if cur is None:
                    snap[name] = dict(m)
                elif m.get("type") == "count" and cur.get("type") == "count":
                    cur["value"] = cur.get("value", 0) + m.get("value", 0)
                elif (m.get("type") == "histogram"
                      and cur.get("type") == "histogram"
                      and m.get("boundaries") == cur.get("boundaries")):
                    cur["counts"] = [a + b for a, b in
                                     zip(cur["counts"], m["counts"])]
                    cur["sum"] = cur.get("sum", 0) + m.get("sum", 0)
                    cur["count"] = cur.get("count", 0) + m.get("count", 0)
                elif (name in reserved or m.get("type") != cur.get("type")
                      or m.get("type") == "histogram"):
                    # reserved-name collision, cross-type collision, or
                    # histograms whose bucket boundaries disagree:
                    # dropping is the only merge that doesn't corrupt one
                    # side (only same-type worker gauges may overwrite)
                    if name not in logged:
                        logged.add(name)
                        logger.warning(
                            "worker metric %r (%s) conflicts with an "
                            "existing %s metric (reserved=%s); worker "
                            "values are dropped from the merged snapshot",
                            name, m.get("type"), cur.get("type"),
                            name in reserved)
                else:
                    snap[name] = dict(m)  # worker gauges: last writer wins
        return snap

    async def h_cluster_info(self, conn, d):
        return {
            "node_id": self.node_id.binary(),
            "nodes": list(self.cluster_nodes.values()),
            "total": self.total.raw(),
            "available": self.available.raw(),
            "num_workers": len(self.workers),
            "store_used": self.store_used,
            "num_local_objects": len(self.local_objects),
            # Same-host drivers attach to this store directly (zero-copy).
            "session_dir": self.session_dir,
            "store_root": self.store_root,
            "bulk_address": self.bulk_address,
            # object transfer plane counters (dashboard /api/objects)
            "transfer": {
                "pull_bytes_total": transfer.M_PULL_BYTES.snapshot()["value"],
                "pulls_striped_total":
                    transfer.M_PULLS_STRIPED.snapshot()["value"],
                "inflight_chunks":
                    transfer.M_INFLIGHT_CHUNKS.snapshot()["value"],
                "transfer_pins": self.transfer_pins.count(),
            },
        }

    async def h_debug_state(self, conn, d):
        """Live-state snapshot of this raylet: worker pool, lease queue
        with ages, spillback grants awaiting adoption, object/transfer
        plane, rpc depth. With include_workers=True, fans out to every
        registered worker's debug_state (bounded per-worker wait) so one
        call answers for the whole node."""
        t_start = time.monotonic()
        now = time.time()
        mono = time.monotonic()
        pool = []
        idle = set(id(w) for w in self.idle) | set(
            id(w) for w in self.idle_tpu)
        for w in list(self.workers.values()):
            pool.append({
                "worker_id": w.worker_id.hex()[:16],
                "pid": w.pid,
                "address": w.address,
                "flavor": w.flavor,
                "lease_id": w.lease_id.hex() if w.lease_id else "",
                "actor_id": (w.actor_id.hex()[:16]
                             if w.actor_id else ""),
                "idle": id(w) in idle,
            })
        pending = []
        for spec, fut in list(self.pending_leases):
            q = spec.get("_queued_at")
            ctx = tracing.from_wire(spec.get("trace"))
            pending.append({
                "name": spec.get("name", "?"),
                "age_s": round(now - q, 3) if q else None,
                "resources": dict(spec.get("resources") or {}),
                "trace_id": ctx.trace_id.hex() if ctx is not None else "",
            })
        spilled = sum(1 for r in self.local_objects.values()
                      if r.get("spilled"))
        snap = {
            "role": "raylet",
            "node_id": self.node_id.hex()[:8],
            "address": self.address,
            "is_head": self.is_head,
            "topology": (self.topology.to_dict()
                         if self.topology is not None else None),
            "resources": {"total": self.total.raw(),
                          "available": self.available.raw()},
            "worker_pool": pool,
            "idle_workers": len(self.idle) + len(self.idle_tpu),
            "starting_workers": self.starting + self.starting_tpu,
            "pending_leases": pending,
            "unadopted_spillback_grants": [
                {"lease_id": lid.hex(),
                 "expires_in_s": round(dl - mono, 3)}
                for lid, dl in list(self._unadopted.items())],
            "objects": {"local_objects": len(self.local_objects),
                        "store_used_bytes": self.store_used,
                        "spilled": spilled,
                        "pulls_inflight": len(self._pulls_inflight)},
            "transfers": transfer.debug_transfers(self.transfer_pins),
            "bundles": len(self.bundles),
            "rpc": {"server_conns": len(self.server.connections),
                    "gcs_depth": (_debug.conn_depth(self.gcs.director)
                                  if self.gcs is not None else 0)},
        }
        if d.get("include_workers"):
            async def one(w):
                try:
                    state = await asyncio.wait_for(
                        w.conn.call("debug_state", {}), timeout=2.0)
                except Exception as e:
                    state = {"error": f"{type(e).__name__}: {e}",
                             "pid": w.pid}
                return w.worker_id.hex()[:16], state

            got = await asyncio.gather(
                *(one(w) for w in list(self.workers.values())
                  if not w.conn.closed))
            snap["workers"] = dict(got)

            # connected DRIVERS too (duplex conns carry their handlers):
            # driver-owned task state — e.g. a task stuck in lease_wait,
            # which lives only in the owner's `submitted` table — is
            # otherwise invisible to the out-of-process surfaces
            async def one_driver(conn, info):
                pid = (info or {}).get("pid")
                try:
                    state = await asyncio.wait_for(
                        conn.call("debug_state", {}), timeout=2.0)
                except Exception as e:
                    state = {"error": f"{type(e).__name__}: {e}",
                             "pid": pid}
                return str(pid or id(conn)), state

            drivers = [(c, c.context.get("driver"))
                       for c in list(self.server.connections)
                       if c.context.get("driver") and not c.closed]
            if drivers:
                got = await asyncio.gather(
                    *(one_driver(c, info) for c, info in drivers))
                snap["drivers"] = dict(got)
        return _debug.finish_snapshot(snap, t_start)

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    async def _handle_gcs_push(self, channel, data):
        if channel == _fp.CHANNEL:
            _fp.apply_kv_value(data)
            return
        if channel == tracing.CHANNEL:
            tracing.apply_kv_value(data)
            return
        if channel == _sprof.CHANNEL:
            _sprof.apply_kv_value(data)
            return
        if channel == "nodes":
            node = data["node"]
            if data["event"] in ("added", "updated"):
                self.cluster_nodes[node["node_id"]] = node
            else:
                self.cluster_nodes.pop(node["node_id"], None)
                await self._dispatch_pending()

    async def _reap_loop(self):
        while True:
            await asyncio.sleep(1.0)
            try:
                self._reap_starting_workers()
            except Exception:
                logger.exception("starting-worker reap failed")
            try:
                await self._respill_pending()
            except Exception:
                logger.exception("pending-lease respill failed")
            try:
                # expire transfer-pin leases left by dead pullers and run
                # the frees they were deferring
                freeable = self.transfer_pins.sweep()
                if freeable:
                    await self._complete_deferred_frees(freeable)
            except Exception:
                logger.exception("transfer-pin sweep failed")
            try:
                if self._reap_unadopted():
                    await self._dispatch_pending()
            except Exception:
                logger.exception("unadopted-lease reap failed")

    async def _respill_pending(self):
        """Queued leases get re-offered to nodes that NOW have capacity
        (a node joined or freed up since the lease queued) — without
        this, work queued before an autoscaled node arrives would wait
        on the saturated node forever (reference: the periodic
        ScheduleAndDispatchTasks in cluster_task_manager.cc)."""
        if not self.pending_leases or len(self.cluster_nodes) <= 1:
            return
        if self.gcs is None:
            return
        # ONE await up front; the scan below is synchronous, so it cannot
        # interleave with _dispatch_pending / h_request_worker_lease (both
        # mutate pending_leases on this loop) and drop their entries.
        try:
            raw = await self.gcs.call("get_available_resources", {})
        except Exception:
            return
        avail = {nid: ResourceSet.from_raw(r) for nid, r in raw.items()}
        still = []
        for spec, fut in self.pending_leases:
            if fut.done():
                continue
            if (self._bundle_key(spec) is not None
                    or not self._feasible_ever(spec)):
                still.append((spec, fut))
                continue
            addr = self._pick_from_availability(spec, avail)
            if addr is not None:
                self.m_spillbacks.inc()
                fut.set_result({"spillback": addr, "hops": 1})
            else:
                still.append((spec, fut))
        self.pending_leases = still

    # ------------------------------------------------------------------
    # elastic membership: graceful drain (planned departure)
    # ------------------------------------------------------------------

    async def h_drain(self, conn, d):
        """GCS asks this raylet to leave gracefully (autoscaler scale-down,
        `ray-tpu drain`, or our own preemption notice echoed back).
        Returns immediately; the drain itself runs in the background so
        the GCS RPC doesn't ride out the whole deadline. Idempotent: a
        second drain (e.g. a preemption notice landing mid-drain) just
        reports the in-progress state."""
        if self._draining:
            return {"state": "DRAINING"}
        self._draining = True
        self.m_drains.inc()
        deadline_s = float(d.get("deadline_s")
                           or self.config.drain_deadline_s)
        preempt = bool(d.get("preempt"))
        logger.info("drain requested (%s, deadline %.1fs): %d local "
                    "objects, %d workers",
                    "preempt" if preempt else "planned", deadline_s,
                    len(self.local_objects), len(self.workers))
        self._drain_task = asyncio.create_task(
            self._drain(deadline_s, preempt))
        return {"state": "DRAINING"}

    async def _drain(self, deadline_s: float, preempt: bool):
        """Planned departure: make the node's disappearance free.
        Normal order: bounce the lease queue, migrate plasma to
        survivors, let in-flight leases finish, checkpoint actors.
        Preemption compresses the window (TPU spot gives seconds), so
        the order flips: checkpoints first — they're small and
        irreplaceable — objects best-effort with whatever remains.
        Whatever misses the deadline takes exactly the crash path
        (typed reclaim/loss), scoped to the leftovers."""
        deadline = time.monotonic() + deadline_s
        self._drain_migrated: set[bytes] = set()
        skip_migrate = False
        if _fp.ARMED:
            # drain seam: `delay` stretches the window so chaos can kill
            # the node mid-drain; `raise` skips the migration pass
            # entirely (every object becomes a leftover)
            try:
                await _fp.fire_async_strict("raylet.drain")
            except _fp.FailpointError:
                skip_migrate = True
        try:
            self._drain_bounce_pending()
            migrated = 0
            if preempt:
                await self._drain_checkpoint_actors(deadline)
                if not skip_migrate:
                    migrated = await self._drain_migrate_objects(deadline)
            else:
                if not skip_migrate:
                    migrated = await self._drain_migrate_objects(deadline)
                await self._drain_wait_leases(deadline)
                if not skip_migrate:
                    # in-flight tasks wrote their returns to plasma AFTER
                    # the first pass — a second sweep migrates those too,
                    # so finishing-during-drain never means losing the
                    # result bytes
                    migrated = await self._drain_migrate_objects(deadline)
                await self._drain_checkpoint_actors(deadline)
            leftovers = sum(1 for oid in self.local_objects
                            if oid not in self._drain_migrated)
            logger.info("drain complete: %d objects migrated, %d left",
                        migrated, leftovers)
            try:
                await self.gcs.call("node_drained", {
                    "node_id": self.node_id.binary(),
                    "migrated": migrated,
                    "leftovers": leftovers,
                }, timeout=10.0)
            except Exception:
                # GCS unreachable: exiting anyway is correct — the
                # heartbeat checker reaps us through the crash path
                logger.warning("node_drained report failed; exiting anyway")
        except Exception:
            logger.exception("drain failed; exiting through the crash path")
            self._fail_stop("drain error")
        self._drain_exit()

    def _drain_bounce_pending(self):
        """Queued-but-ungranted leases spill to survivors via the normal
        owner-visible bounce; requests with no feasible survivor stay
        queued — the exit-time connection close routes them through the
        owner's retry machinery like any node loss."""
        still = []
        for spec, fut in self.pending_leases:
            if fut.done():
                continue
            addr = self._pick_spillback(spec)
            if addr is not None:
                self.m_spillbacks.inc()
                fut.set_result({"spillback": addr, "hops": 1})
            else:
                still.append((spec, fut))
        self.pending_leases = still

    async def _drain_migrate_objects(self, deadline: float) -> int:
        """Actively push every resident plasma object to a survivor:
        notify the target with a push_hint (it runs a normal striped
        pull over the bulk channel with us as the seed source), then
        poll the GCS directory until a survivor is listed as a holder —
        only a directory-confirmed copy counts as migrated, so the
        object stays resolvable after our locations drop. Bounded by
        drain_migrate_concurrency and the deadline."""
        me = self.node_id.binary()
        survivors = [
            info for nid, info in self.cluster_nodes.items()
            if nid != me and info.get("state", "ALIVE") == "ALIVE"
            and info.get("address")
        ]
        if not survivors or self.gcs is None:
            return 0
        sem = asyncio.Semaphore(
            max(1, self.config.drain_migrate_concurrency))

        async def _one(idx: int, oid: bytes, rec: dict):
            async with sem:
                if time.monotonic() >= deadline:
                    return
                if _fp.ARMED:
                    # migrate seam: `raise` turns THIS object into a
                    # leftover (typed loss downstream); `delay` holds an
                    # object mid-flight across the chaos kill window
                    try:
                        await _fp.fire_async_strict("transfer.migrate")
                    except _fp.FailpointError:
                        return
                target = survivors[idx % len(survivors)]
                try:
                    tconn = await self._raylet_conn(target["address"])
                    await tconn.notify("push_hint", {
                        "object_id": oid, "from": self.address})
                except Exception as e:
                    logger.warning("drain push to %s failed: %s",
                                   target["address"], e)
                    return
                while time.monotonic() < deadline:
                    try:
                        nodes = await self.gcs.call(
                            "get_object_locations", {"object_id": oid})
                    except Exception:
                        return
                    if any(n != me for n in nodes or ()):
                        self._drain_migrated.add(oid)
                        self.m_drain_migrated_bytes.inc(
                            int(rec.get("size") or 0))
                        return
                    await asyncio.sleep(0.05)

        todo = [(oid, rec) for oid, rec in self.local_objects.items()
                if oid not in self._drain_migrated]
        await asyncio.gather(
            *(_one(i, oid, rec) for i, (oid, rec) in enumerate(todo)),
            return_exceptions=True)
        return len(self._drain_migrated)

    async def _drain_wait_leases(self, deadline: float):
        """Let in-flight tasks run to completion (actors are handled by
        the checkpoint step — they never finish on their own). Leases
        still live at the deadline are reclaimed through the normal
        typed machinery when the node exits."""
        while time.monotonic() < deadline:
            if not any(w.lease_id is not None for w in self.workers.values()):
                return
            await asyncio.sleep(0.1)

    async def _drain_checkpoint_actors(self, deadline: float):
        """Snapshot restartable actor state to the control plane: each
        actor worker runs the actor's __ray_checkpoint__() hook (if
        defined) and we land the pickled state in the GCS KV — a
        survivor by construction — keyed by actor id. The GCS then
        relocates the actor (planned, no restart burned) and the new
        incarnation restores via __ray_restore__. Actors without the
        hook relocate stateless, exactly like today."""
        for w in list(self.workers.values()):
            if w.actor_id is None or w.conn.closed:
                continue
            budget = deadline - time.monotonic()
            if budget <= 0:
                return
            try:
                reply = await asyncio.wait_for(
                    w.conn.call("checkpoint_actor", {}),
                    timeout=max(0.2, budget))
                state = (reply or {}).get("state")
                if state is not None:
                    await self.gcs.call("kv_put", {
                        "key": f"actor_ckpt:{w.actor_id.hex()}",
                        "value": state})
            except Exception as e:
                logger.warning("checkpoint of actor %s failed: %s",
                               w.actor_id.hex()[:8], e)

    def _drain_exit(self):
        """Graceful twin of _fail_stop: the GCS already finalized us as
        DRAINED (or will reap us), so stop accepting work and leave with
        status 0. Workers get the intended-exit notice first so their
        owners see a clean actor exit, not a crash."""
        logger.info("raylet exiting after drain")
        self._shutting_down = True
        for w in list(self.workers.values()):
            try:
                w.conn.context["intended_exit"] = True
                os.kill(w.pid, signal.SIGTERM)
            except (ProcessLookupError, PermissionError):
                pass
        for proc, _flavor in self._starting_procs:
            try:
                proc.kill()
            except OSError:
                pass
        os._exit(0)

    def _fail_stop(self, reason: str):
        """Fail-stop this node: kill every worker and exit. A raylet the
        GCS has given up on must NOT linger as a split-brain zombie that
        still grants leases and runs tasks nobody can reach — the rest of
        the cluster already declared this node dead and rescheduled its
        actors (reference: raylets exit when disconnected from the GCS)."""
        logger.error("raylet fail-stop: %s — killing %d worker(s) and "
                     "exiting", reason, len(self.workers))
        self._shutting_down = True
        for w in list(self.workers.values()):
            try:
                os.kill(w.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
        for proc, _flavor in self._starting_procs:
            try:
                proc.kill()
            except OSError:
                pass
        os._exit(1)

    def _heartbeat_metrics(self) -> dict | None:
        """Every 4th beat (~2s) the heartbeat piggybacks this raylet's
        metric snapshot for the GCS time-series ring. A fired
        metrics.push failpoint skips the sample — never the beat."""
        self._beat_n += 1
        if self._beat_n % 4:
            return None
        try:
            if _fp.ARMED:
                _fp.fire_strict("metrics.push")
        except _fp.FailpointError:
            return None
        from ray_tpu._private import stats

        return self._gauge_snapshot(stats.snapshot())

    async def _flush_profile(self):
        """Flush recorded trace spans / profile events to the GCS (~2s
        cadence off the heartbeat loop); a failed flush requeues into
        the bounded buffer like the core-worker path."""
        now = time.monotonic()
        if now - self._last_profile_flush < 2.0:
            return
        self._last_profile_flush = now
        if self.gcs is None:
            return
        await self._flush_profile_samples()
        events = self._profile.drain()
        if not events:
            return
        try:
            if _fp.ARMED:
                _fp.fire_strict("trace.flush")
            await self.gcs.notify("add_profile_events", {
                "component_type": "raylet",
                "component_id": os.getpid(),
                "node_id": self.node_id.binary(),
                "events": events,
            })
        except Exception:
            self._profile.requeue(events)

    async def _flush_profile_samples(self):
        """Flush the continuous-profiler window into the GCS profile
        ring (sampling_profiler.flush_to: the shared drain +
        `profile.flush` seam + bounded merge-back contract)."""
        await _sprof.flush_to(self.gcs, "raylet",
                              node_id=self.node_id.binary())

    async def heartbeat_loop(self):
        interval = self.config.heartbeat_interval_s
        window = max(self.config.gcs_reconnect_timeout_s, 2 * interval)
        last_ok = time.monotonic()
        while True:
            await asyncio.sleep(interval)
            if _fp.ARMED and not self._draining:
                # preemption-notice seam: stands in for the cloud
                # metadata "you have N seconds" signal (TPU spot). The
                # notice starts a COMPRESSED drain through the GCS so
                # the departure is cluster-visible — checkpoints first,
                # objects best-effort (idempotent if already draining).
                try:
                    await _fp.fire_async_strict("node.preempt_notice")
                except _fp.FailpointError:
                    logger.warning("preemption notice received: "
                                   "requesting compressed drain")
                    try:
                        await self.gcs.call("drain_node", {
                            "node_id": self.node_id.binary(),
                            "preempt": True,
                        }, timeout=5.0)
                    except Exception:
                        logger.warning("preempt drain request failed; "
                                       "retrying next beat")
            try:
                if _fp.ARMED:
                    await _fp.fire_async_strict("raylet.heartbeat")
                beat = {
                    "node_id": self.node_id.binary(),
                    "available": self.available.raw(),
                }
                metrics = self._heartbeat_metrics()
                if metrics is not None:
                    beat["metrics"] = metrics
                    beat["metrics_source"] = (
                        f"{self.node_id.hex()[:8]}/raylet")
                # Bounded per-beat: a HUNG (not dead) GCS must not park
                # this call forever — that would stop the failure clock
                # and leave exactly the zombie this loop exists to kill.
                await self.gcs.call("heartbeat", beat,
                                    timeout=max(2.0, 4 * interval))
                last_ok = time.monotonic()
                try:
                    await self._flush_profile()
                except Exception:
                    logger.exception("profile flush failed")
            except Exception:
                logger.warning("heartbeat to GCS failed")
                if time.monotonic() - last_ok > window:
                    # Continuous failure past the reconnect window: the
                    # GCS has long since declared us dead (heartbeat
                    # timeout is far shorter) — fail-stop, don't zombie.
                    self._fail_stop(
                        f"heartbeats failing for >{window:.0f}s "
                        f"(GCS reconnect window)")

    async def run(self, port: int = 0, ready_file: str | None = None):
        self._loop = asyncio.get_running_loop()
        _debug.start_loop_lag_monitor()
        _sprof.start("raylet")
        actual = await self.server.start_tcp(
            host=self.config.bind_host, port=port,
            uds_dir=os.path.join(self.session_dir, "sock"))
        self.address = f"{self.config.node_ip_address}:{actual}"
        try:
            # bulk object data plane: sibling listener, own threads —
            # object bytes never touch the control connection again
            self.bulk_address = self.bulk.start(
                self.config.bind_host, self.config.node_ip_address,
                os.path.join(self.session_dir, "sock"))
        except OSError as e:  # pragma: no cover - bind quirks
            logger.warning("bulk transfer channel disabled: %s", e)
            self.bulk_address = ""

        async def _gcs_session(conn):
            """(Re-)establish GCS session state: subscribe, refresh the
            cluster view, re-register this node. Runs on first connect and
            again after every GCS restart (reference: raylet re-registers
            via service_based_gcs_client reconnection)."""
            await conn.call("subscribe", {"channel": "nodes"})
            await conn.call("subscribe", {"channel": _fp.CHANNEL})
            armed = await conn.call("kv_get", {"key": _fp.KV_KEY})
            if armed:
                _fp.apply_kv_value(armed)
            await conn.call("subscribe", {"channel": tracing.CHANNEL})
            rate = await conn.call("kv_get", {"key": tracing.KV_KEY})
            if rate:
                tracing.apply_kv_value(rate)
            await conn.call("subscribe", {"channel": _sprof.CHANNEL})
            hz = await conn.call("kv_get", {"key": _sprof.KV_KEY})
            if hz:
                _sprof.apply_kv_value(hz)
            nodes = await conn.call("get_all_nodes", {})
            self.cluster_nodes = {n["node_id"]: n for n in nodes}
            await conn.call("register_node", {
                "node_id": self.node_id.binary(),
                "address": self.address,
                "bulk_address": self.bulk_address,
                "resources": self.total.raw(),
                "available": self.available.raw(),
                "hostname": os.uname().nodename,
                "is_head": self.is_head,
                "labels": self.labels,
                "tpu_slice": self.tpu_slice,
                "topology": (self.topology.to_dict()
                             if self.topology is not None else None),
            })

        def _gcs_gone():
            self._fail_stop("GCS unreachable past reconnect timeout")

        # Duplex: the GCS drives actor creation and bundle 2PC back over
        # this connection; it survives GCS restarts.
        uds_dir = os.path.join(self.session_dir, "sock")
        director = rpc.ReconnectingConnection(
            rpc.prefer_uds(self.gcs_address, uds_dir,
                           local_ips=("127.0.0.1",
                                      self.config.node_ip_address)),
            handlers=self._handlers(), name="raylet->gcs",
            on_reconnect=_gcs_session,
            retry_timeout=self.config.gcs_reconnect_timeout_s,
            on_give_up=_gcs_gone)
        # Sharded control plane: the object-directory ops this raylet
        # issues per seal/free/pull (the hottest steady-state stream)
        # key-route straight to the owning store shard; membership,
        # heartbeats, scheduling and pubsub stay on the director. With
        # gcs_shards=1 (default) this is a pure passthrough.
        from ray_tpu.gcs.client import GcsClient

        self.gcs = GcsClient(director, self.config, uds_dir=uds_dir)
        self.gcs.set_push_handler(self._handle_gcs_push)
        await _gcs_session(await director.ensure_connected())
        asyncio.create_task(self.heartbeat_loop())
        asyncio.create_task(self._reap_loop())
        prestart = self.config.num_initial_workers
        if prestart < 0:
            prestart = min(int(self.num_cpus), 8)
        for _ in range(prestart):
            self._start_worker_process()
        logger.info("raylet up at %s (node %s)", self.address,
                    self.node_id.hex()[:8])
        if ready_file:
            tmp = ready_file + ".tmp"
            with open(tmp, "w") as f:
                f.write(self.address)
            os.rename(tmp, ready_file)
        while True:
            await asyncio.sleep(3600)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--gcs-address", required=True)
    parser.add_argument("--session-dir", required=True)
    parser.add_argument("--store-root", required=True)
    parser.add_argument("--node-id", default=None)
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--num-cpus", type=float, default=None)
    parser.add_argument("--num-tpus", type=float, default=0)
    parser.add_argument("--resources", default="{}")
    parser.add_argument("--labels", default="{}")
    parser.add_argument("--tpu-slice", default="")
    parser.add_argument("--topology", default="",
                        help="explicit TopologyCoord JSON "
                             '({"slice_id","coords","dims"}); empty = '
                             "derive from RAY_TPU_TOPOLOGY / tpu-slice")
    parser.add_argument("--tpu-worker-platforms", default="tpu",
                        help="JAX_PLATFORMS of a TPU-flavour worker")
    parser.add_argument("--is-head", action="store_true")
    parser.add_argument("--ready-file", default=None)
    parser.add_argument("--log-file", default=None)
    args = parser.parse_args()

    import json

    from ray_tpu._private.log_utils import setup_process_logging

    setup_process_logging("raylet", args.log_file)
    _fp.set_role("raylet")
    from ray_tpu._private.events import init_events

    init_events("RAYLET", args.node_id or "",
                os.path.dirname(args.log_file) if args.log_file else None)
    set_config(Config.load())
    resources = dict(json.loads(args.resources))
    resources.setdefault("CPU", args.num_cpus
                         if args.num_cpus is not None else (os.cpu_count() or 1))
    if args.num_tpus:
        resources.setdefault("TPU", args.num_tpus)
    node_id = (NodeID.from_hex(args.node_id) if args.node_id
               else NodeID.from_random())
    raylet = Raylet(
        node_id=node_id,
        session_dir=args.session_dir,
        gcs_address=args.gcs_address,
        resources=resources,
        store_root=args.store_root,
        is_head=args.is_head,
        labels=json.loads(args.labels),
        config=get_config(),
        tpu_slice=json.loads(args.tpu_slice) if args.tpu_slice else None,
        topology=json.loads(args.topology) if args.topology else None,
        tpu_worker_platforms=args.tpu_worker_platforms,
    )
    asyncio.run(raylet.run(args.port, args.ready_file))


if __name__ == "__main__":
    main()
