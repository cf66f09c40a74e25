"""GCS — global control store server (head-node control plane).

Capability parity with the reference's gcs_server process (reference:
src/ray/gcs/gcs_server/gcs_server.h:57): cluster membership + heartbeat
failure detection (GcsHeartbeatManager, gcs_heartbeat_manager.h:32), actor
lifecycle + restart (GcsActorManager, gcs_actor_manager.h:157), actor
scheduling (GcsActorScheduler, gcs_actor_scheduler.h:83), job registry,
KV store + pubsub (GcsPubSub over Redis in the reference — here an
in-process table + push channels over our RPC layer; no Redis process),
object location directory (GcsObjectManager), and placement groups
(GcsPlacementGroupManager, gcs_placement_group_manager.h:130).

State is write-through persisted via GcsStorage (WAL + snapshot under the
session dir — see storage.py; reference: gcs_table_storage.h:294 persists
to Redis): a restarted GCS reloads jobs/actors/named-actors/placement
groups/KV/node table, raylets and drivers redial and re-register
(rpc.ReconnectingConnection), and the cluster continues — the analog of
the reference's GCS fault-tolerance behavior
(python/ray/tests/test_gcs_fault_tolerance.py).
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import os
import random
import time

from ray_tpu._private import debug_state as _debug
from ray_tpu._private import failpoints as _fp
from ray_tpu._private import rpc
from ray_tpu._private import sampling_profiler as _sprof
from ray_tpu._private import stats as _stats
from ray_tpu._private import topology as _topo
from ray_tpu._private import tracing as _tracing
from ray_tpu._private.common import InsufficientResources, ResourceSet
from ray_tpu._private.config import Config, get_config, set_config

logger = logging.getLogger("ray_tpu.gcs")

M_TRACE_APPLY_FAILURES = _stats.Count(
    "gcs.trace_apply_failures_total",
    "profile/trace batches dropped by a failed trace-table apply")
M_TOPO_FALLBACKS = _stats.Count(
    "gcs.placement_topology_fallbacks_total",
    "ICI_RING placements that fell back to PACK (no candidate node had "
    "registered topology coords, or the scoring seam failed)")
M_PLACEMENT_SCORE_S = _stats.Histogram(
    "gcs.placement_score_s", _stats.LATENCY_BOUNDARIES_S,
    "one placement decision: strategy dispatch + candidate scoring in "
    "_place_bundles (every strategy — the PACK-vs-ICI_RING latency A/B "
    "reads this histogram per arm)")
M_PREEMPT_NOTICES = _stats.Count(
    "gcs.preemption_notices_total",
    "preemption notices received (node.preempt_notice failpoint or "
    "drain --preempt) — each starts a compressed drain; a notice on an "
    "already-draining node is counted but idempotent")
M_RING_REPLACEMENTS = _stats.Count(
    "gcs.ring_replacements_total",
    "ICI_RING placements scored around a torus hole (>=1 masked "
    "DRAINING or recently-departed coord) — gang re-placements after "
    "a drain/preemption")

# How long a departed node's torus coords stay visible as masked_coords
# in new ICI_RING plans (re-placements around the hole are recorded and
# counted within this window; a re-registration clears the hole early).
_DEPARTED_COORD_TTL_S = 300.0

# Actor states (reference: src/ray/protobuf/gcs.proto ActorTableData.ActorState)
DEPENDENCIES_UNREADY = "DEPENDENCIES_UNREADY"
PENDING_CREATION = "PENDING_CREATION"
ALIVE = "ALIVE"
RESTARTING = "RESTARTING"
DEAD = "DEAD"


class GcsServer:
    def __init__(self, config: Config, storage=None,
                 shard_addresses: list[str] | None = None):
        self.config = config
        self.storage = storage
        # Store-shard tier (gcs/shard.py): the director advertises the
        # addresses (get_shard_map) so clients key-route table ops
        # directly, and keeps its own connection per shard to push
        # actor/pg directory mirrors, node-death prunes, and live
        # failpoint arming. Empty = single-process layout (shards=1).
        self.shard_addresses = list(shard_addresses or [])
        self._shard_conns: list = [None] * len(self.shard_addresses)
        # sibling-UDS dir (run() fills it): local shard dials skip TCP
        self._uds_dir: str | None = None
        self.kv: dict[str, bytes] = {}
        self.subscriptions: dict[str, set[rpc.Connection]] = {}
        # node_id(bytes) -> node info dict
        self.nodes: dict[bytes, dict] = {}
        self.node_conns: dict[bytes, rpc.Connection] = {}
        self.last_heartbeat: dict[bytes, float] = {}
        self.available: dict[bytes, ResourceSet] = {}
        # actor_id -> record
        self.actors: dict[bytes, dict] = {}
        self.named_actors: dict[tuple[str, str], bytes] = {}
        self.jobs: dict[bytes, dict] = {}
        self.next_job = 1
        # object_id -> {"nodes": set of node_ids, "size": bytes} — the
        # object directory (reference: object_directory.h). Sizes feed
        # the raylets' locality-aware lease targeting; multiple nodes
        # feed multi-source striped pulls.
        self.object_locations: dict[bytes, dict] = {}
        self.placement_groups: dict[bytes, dict] = {}
        # ICI_RING scoring leaves the winning candidate's plan here for
        # _do_create_pg to stamp onto the CREATED record (single-threaded
        # asyncio: set synchronously in _place_bundles, read immediately
        # after it returns)
        self._last_topology_plan: dict | None = None
        # (coords, snake order) of coord-bearing nodes — rebuilt only
        # when membership changes, so per-decision scoring cost stays in
        # the PACK arm's latency bucket (the <=5% A/B gate)
        self._topo_cache: tuple[dict, list] | None = None
        # node8 -> (departed_ts, topology dict) for coord-bearing nodes
        # that drained or died: ICI_RING plans stamp these as
        # masked_coords so re-placement around the torus hole stays
        # visible in the placement record after the node is gone
        self._departed_coords: dict[str, tuple[float, dict]] = {}
        self.server = rpc.Server(self._handlers(), on_disconnect=self._on_disconnect,
                                 name="gcs")
        self._pending_actor_queue: list[bytes] = []
        self._pending_logged: set[bytes] = set()
        # Structured cluster events ring (reference: src/ray/util/event.h
        # EventManager; fed by every process via "report_event").
        import collections as _collections

        self.events: _collections.deque = _collections.deque(maxlen=1000)
        # Profile-event table (reference: the GCS profile table fed by
        # core_worker profiling.h batches), bounded ring.

        self.profile_events: _collections.deque = _collections.deque(
            maxlen=200_000)
        # Trace table: flat span rows (tracing.py spans carry a `tid`
        # trace id in extra_data) indexed out of the profile batches so
        # one request's cross-process tree is queryable by trace id.
        self.trace_spans: _collections.deque = _collections.deque(
            maxlen=50_000)
        # Continuous-profiling ring (sampling_profiler.py): collapsed-
        # stack sample batches from every process class, bounded —
        # director-memory-only like the other observability rings.
        self.profile_samples: _collections.deque = _collections.deque(
            maxlen=4000)
        # per-shard t_end of the last ingested profiler window (the
        # at-least-once ack _drain_shard_profiles carries)
        self._shard_profile_acks: dict[int, float] = {}
        # Metrics time series: source -> metric -> ring of [ts, value]
        # samples, fed by raylet heartbeat piggybacks and worker/driver
        # push_metrics notifies (~2s cadence; ~10 min of history).
        self.metrics_history: dict[str, dict] = {}
        self.metrics_history_samples = 300
        self.metrics_last_push: dict[str, float] = {}
        # histogram p99 exemplars (trace-id strings can't ride the
        # scalar rings): source -> hist name -> {"trace_id","value","ts"}
        self.metrics_exemplars: dict[str, dict] = {}
        # History epoch: metrics-history and trace rings are DIRECTOR
        # MEMORY ONLY by contract (ARCHITECTURE.md "State introspection"
        # — the lossy-restart contract): a restart resets them, and
        # consumers (`ray-tpu top`) detect the reset by this changing.
        self.started_at = time.time()
        if storage is not None:
            self._restore()

    # ---- persistence (reference: gcs_table_storage.h:294) ----
    def _restore(self):
        """Reload control state after a GCS restart. Raylets redial and
        re-register (restoring conns/heartbeats); actors that were mid-
        scheduling are re-queued; ALIVE actors keep running untouched."""
        st = self.storage
        self.kv = dict(st.table("kv"))
        if _fp.KV_KEY in self.kv:
            # armed failpoints survive a GCS restart with the KV
            _fp.apply_kv_value(self.kv[_fp.KV_KEY])
        if _tracing.KV_KEY in self.kv:
            # so does a live trace-sampling override
            _tracing.apply_kv_value(self.kv[_tracing.KV_KEY])
        if _sprof.KV_KEY in self.kv:
            # and a live profiling-rate override
            _sprof.apply_kv_value(self.kv[_sprof.KV_KEY])
        self.jobs = dict(st.table("jobs"))
        self.next_job = st.get("meta", "next_job", 1)
        now = time.monotonic()
        for node_id, info in st.table("nodes").items():
            self.nodes[node_id] = dict(info)
            # Full resources until the raylet's next heartbeat corrects it.
            self.available[node_id] = ResourceSet.from_raw(info["resources"])
            # Grace window: a raylet that outlived the GCS reconnects well
            # within the normal heartbeat timeout.
            self.last_heartbeat[node_id] = now
        for actor_id, rec in st.table("actors").items():
            rec = dict(rec)
            self.actors[actor_id] = rec
            if rec["state"] in (PENDING_CREATION, RESTARTING):
                self._pending_actor_queue.append(actor_id)
        for key, actor_id in st.table("named_actors").items():
            ns, _, name = key.partition("\x00")
            self.named_actors[(ns, name)] = actor_id
        for pg_id, rec in st.table("placement_groups").items():
            rec = dict(rec)
            rec.pop("creating", None)
            self.placement_groups[pg_id] = rec
        if self.nodes or self.actors:
            logger.info(
                "restored GCS state: %d nodes, %d actors, %d pgs, %d kv",
                len(self.nodes), len(self.actors),
                len(self.placement_groups), len(self.kv))

    def _persist(self, table: str, key, value, sync: bool = False):
        if _fp.ARMED:
            # table-apply seam: `raise` fails the mutating handler (the
            # caller sees RemoteError and retries idempotently), `delay`
            # widens the apply->publish window a GCS crash can land in
            _fp.fire_strict("gcs.table.apply")
        if self.storage is not None:
            self.storage.put(table, key, value, sync=sync)

    def _persist_del(self, table: str, key):
        if self.storage is not None:
            self.storage.delete(table, key)

    def _persist_actor(self, rec):
        # Everything in rec travelled over msgpack RPC, so it persists
        # as-is. Actor transitions fsync: losing one strands live handles.
        self._persist("actors", rec["actor_id"], rec, sync=True)

    def _persist_pg(self, rec):
        clean = {k: v for k, v in rec.items() if k != "creating"}
        self._persist("placement_groups", rec["pg_id"], clean, sync=True)

    def _handlers(self):
        return {
            "kv_put": self.h_kv_put,
            "kv_get": self.h_kv_get,
            "kv_del": self.h_kv_del,
            "kv_exists": self.h_kv_exists,
            "kv_keys": self.h_kv_keys,
            "subscribe": self.h_subscribe,
            "unsubscribe": self.h_unsubscribe,
            "publish": self.h_publish,
            "register_node": self.h_register_node,
            "heartbeat": self.h_heartbeat,
            "set_resource": self.h_set_resource,
            "get_all_nodes": self.h_get_all_nodes,
            "get_available_resources": self.h_get_available_resources,
            "drain_node": self.h_drain_node,
            "node_drained": self.h_node_drained,
            "register_job": self.h_register_job,
            "register_actor": self.h_register_actor,
            "get_actor": self.h_get_actor,
            "get_named_actor": self.h_get_named_actor,
            "list_actors": self.h_list_actors,
            "kill_actor": self.h_kill_actor,
            "actor_alive": self.h_actor_alive,
            "report_worker_failure": self.h_report_worker_failure,
            "add_object_location": self.h_add_object_location,
            "remove_object_location": self.h_remove_object_location,
            "get_object_locations": self.h_get_object_locations,
            "get_object_locations_batch": self.h_get_object_locations_batch,
            "create_placement_group": self.h_create_placement_group,
            "remove_placement_group": self.h_remove_placement_group,
            "get_placement_group": self.h_get_placement_group,
            "get_named_placement_group": self.h_get_named_placement_group,
            "list_placement_groups": self.h_list_placement_groups,
            "add_profile_events": self.h_add_profile_events,
            "get_profile_events": self.h_get_profile_events,
            "get_trace_spans": self.h_get_trace_spans,
            "add_profile_samples": self.h_add_profile_samples,
            "get_profile_samples": self.h_get_profile_samples,
            "push_metrics": self.h_push_metrics,
            "get_metrics_history": self.h_get_metrics_history,
            "report_event": self.h_report_event,
            "get_events": self.h_get_events,
            "get_metrics": self.h_get_metrics,
            "get_shard_map": self.h_get_shard_map,
            "debug_state": self.h_debug_state,
            "debug_stacks": lambda conn, data: _debug.collect_stacks(),
            "ping": lambda conn, data: "pong",
        }

    # ---- store-shard tier ----
    async def h_get_shard_map(self, conn, d):
        """Addresses of the store shards, in index order — the client-
        side routing table (gcs/client.py shard_for)."""
        return {"addresses": self.shard_addresses}

    async def _shard_conn(self, idx: int):
        conn = self._shard_conns[idx]
        if conn is None:
            async def _resync(c, idx=idx):
                await self._resync_shard(idx, c)

            conn = rpc.ReconnectingConnection(
                rpc.prefer_uds(self.shard_addresses[idx], self._uds_dir),
                name=f"gcs->shard{idx}", on_reconnect=_resync,
                retry_timeout=self.config.gcs_reconnect_timeout_s)
            self._shard_conns[idx] = conn
        return conn

    def _shard_index_for(self, key) -> int:
        from ray_tpu.gcs.client import shard_for

        return shard_for(key, len(self.shard_addresses))

    async def _resync_shard(self, idx: int, conn):
        """Re-push everything the director owns that this shard mirrors:
        actor/pg public records in its partition, plus live failpoint /
        trace-sampling specs. Runs at startup and after every shard
        reconnect, so a shard restarted WHILE a mirror push was lost
        still converges (its journal already replayed the rest)."""
        records = []
        for actor_id, rec in self.actors.items():
            if self._shard_index_for(actor_id) == idx:
                records.append(["actors", actor_id, self._actor_public(rec)])
        for pg_id, rec in self.placement_groups.items():
            if self._shard_index_for(pg_id) == idx:
                records.append(["pgs", pg_id, _pg_public(rec)])
        if records:
            await conn.call("mirror_apply", {"records": records})
        spec = self.kv.get(_fp.KV_KEY)
        if spec:
            await conn.notify("configure_failpoints", {"spec": spec})
        hz = self.kv.get(_sprof.KV_KEY)
        if hz:
            await conn.notify("configure_profiling", {"spec": hz})

    async def _mirror(self, table: str, key, value):
        """Push one actor/pg public record (value=None deletes) to the
        owning shard. Best-effort with a short bound: a shard mid-restart
        must not stall scheduling — the reconnect resync repairs it."""
        if not self.shard_addresses:
            return
        conn = await self._shard_conn(self._shard_index_for(key))
        try:
            await asyncio.wait_for(
                conn.call("mirror_apply",
                          {"records": [[table, key, value]]}),
                timeout=2.0)
        except Exception:
            logger.warning("mirror push to shard lost (%s); reconnect "
                           "resync will repair", table)

    async def _broadcast_shards(self, method: str, data):
        async def one(idx):
            try:
                conn = await self._shard_conn(idx)
                await asyncio.wait_for(conn.call(method, data), timeout=2.0)
            except Exception:
                logger.warning("shard %d broadcast %r failed", idx, method)

        # concurrent: callers like _remove_node gate failover on this —
        # serial 2s timeouts would stack per unreachable shard
        await asyncio.gather(*(one(i)
                               for i in range(len(self.shard_addresses))))

    # ---- kv ----
    async def h_kv_put(self, conn, d):
        key = d["key"]
        if not d.get("overwrite", True) and key in self.kv:
            return False
        self.kv[key] = d["value"]
        self._persist("kv", key, d["value"])
        if key == _fp.KV_KEY:
            # live fault-injection arming: apply here, broadcast to every
            # subscribed raylet/worker/driver (failpoints.arm_cluster),
            # and forward to the store shards (they don't subscribe)
            _fp.apply_kv_value(d["value"])
            await self.publish(_fp.CHANNEL, d["value"])
            if self.shard_addresses:
                await self._broadcast_shards(
                    "configure_failpoints", {"spec": d["value"]})
        elif key == _tracing.KV_KEY:
            # live trace-sampling override (ray_tpu.set_trace_sampling):
            # same apply-here + broadcast plane as the failpoints
            _tracing.apply_kv_value(d["value"])
            await self.publish(_tracing.CHANNEL, d["value"])
        elif key == _sprof.KV_KEY:
            # live profiler arming (ray_tpu.set_profiling): apply here,
            # broadcast to subscribers, forward to the store shards
            # (they don't subscribe to pubsub)
            _sprof.apply_kv_value(d["value"])
            await self.publish(_sprof.CHANNEL, d["value"])
            if self.shard_addresses:
                await self._broadcast_shards(
                    "configure_profiling", {"spec": d["value"]})
        return True

    async def h_kv_get(self, conn, d):
        return self.kv.get(d["key"])

    async def h_kv_del(self, conn, d):
        self._persist_del("kv", d["key"])
        return self.kv.pop(d["key"], None) is not None

    async def h_kv_exists(self, conn, d):
        return d["key"] in self.kv

    async def h_kv_keys(self, conn, d):
        prefix = d.get("prefix", "")
        return [k for k in self.kv if k.startswith(prefix)]

    # ---- pubsub ----
    async def h_subscribe(self, conn, d):
        self.subscriptions.setdefault(d["channel"], set()).add(conn)
        return True

    async def h_unsubscribe(self, conn, d):
        self.subscriptions.get(d["channel"], set()).discard(conn)
        return True

    async def h_publish(self, conn, d):
        await self.publish(d["channel"], d["data"])
        return True

    async def publish(self, channel: str, data):
        if _fp.ARMED and channel != _fp.CHANNEL:
            # publish seam: drop_conn DROPS this publish (subscribers
            # must survive a lost state push — e.g. the owner-side actor
            # poll backstop); never injected on the failpoints channel
            # itself, which must stay reliable to disarm a sweep
            if await _fp.fire_async("gcs.publish") == "drop_conn":
                logger.warning("gcs.publish failpoint dropped a publish "
                               "on %r", channel)
                return
        for conn in list(self.subscriptions.get(channel, ())):
            if conn.closed:
                self.subscriptions[channel].discard(conn)
                continue
            try:
                await conn.push(channel, data)
            except Exception:
                self.subscriptions[channel].discard(conn)

    # ---- nodes ----
    async def h_register_node(self, conn, d):
        node_id = d["node_id"]
        info = {
            "node_id": node_id,
            "address": d["address"],  # raylet rpc address
            "object_manager_address": d.get("object_manager_address", d["address"]),
            # bulk object data-plane listener (raylet/transfer.py); ""
            # when the node runs without one (peers fall back to the
            # legacy chunked rpc pull)
            "bulk_address": d.get("bulk_address", ""),
            "resources": d["resources"],  # raw quantized dict
            "hostname": d.get("hostname", ""),
            "is_head": d.get("is_head", False),
            "labels": d.get("labels", {}),
            # util/accelerators.TpuSliceDescriptor dict or None: this
            # host's ICI domain, consumed by _place_bundles
            "tpu_slice": d.get("tpu_slice"),
            # _private/topology.TopologyCoord dict or None: the node's
            # position in the torus (ICI_RING scoring, spillback
            # ordering, locality tie-breaks all read it)
            "topology": d.get("topology"),
            "state": "ALIVE",
            "start_time": time.time(),
        }
        rejoining = node_id in self.nodes  # redial after a GCS restart
        self.nodes[node_id] = info
        self._topo_cache = None
        # a re-registering node fills its own torus hole
        self._departed_coords.pop(node_id.hex()[:8], None)
        self.available[node_id] = ResourceSet.from_raw(
            d.get("available", d["resources"]))
        self.last_heartbeat[node_id] = time.monotonic()
        conn.context["node_id"] = node_id
        self.node_conns[node_id] = conn
        self._persist("nodes", node_id, info)
        if not rejoining:
            await self.publish("nodes",
                               {"event": "added", "node": _node_public(info)})
        logger.info("node %s: %s @ %s",
                    "re-registered" if rejoining else "registered",
                    node_id.hex()[:8], d["address"])
        if not rejoining:
            from ray_tpu._private.events import INFO

            self._event(INFO, "NODE_ADDED",
                        f"node {node_id.hex()[:8]} joined @ {d['address']}",
                        node_id=node_id.hex())
        await self._try_schedule_pending_actors()
        await self._retry_pending_pgs()
        return True

    async def h_set_resource(self, conn, d):
        """ray.experimental.set_resource: forward to the target raylet,
        then refresh this table's view (reference: gcs_resource_manager
        UpdateResources)."""
        node_id = d.get("node_id") or next(
            (nid for nid, info in self.nodes.items()
             if info["state"] == "ALIVE"), None)
        node_conn = self.node_conns.get(node_id)
        if node_conn is None or node_conn.closed:
            raise ValueError(f"no live raylet for node "
                             f"{node_id.hex()[:8] if node_id else None}")
        reply = await node_conn.call("set_resource", {
            "resource_name": d["resource_name"],
            "capacity": d["capacity"],
        })
        info = self.nodes.get(node_id)
        if info is not None:
            info["resources"] = reply["total"]
            self._persist("nodes", node_id, info)
            # let every raylet refresh its cluster view (spillback
            # scoring and api.nodes() read it)
            await self.publish("nodes", {"event": "updated",
                                         "node": _node_public(info)})
        self.available[node_id] = ResourceSet.from_raw(reply["available"])
        return True

    async def h_heartbeat(self, conn, d):
        if _fp.ARMED:
            # heartbeat seam: `raise` makes beats fail while the conn
            # stays up — the raylet's fail-stop window must catch it
            await _fp.fire_async_strict("gcs.heartbeat")
        node_id = d["node_id"]
        self.last_heartbeat[node_id] = time.monotonic()
        if "metrics" in d:
            # heartbeat-piggybacked raylet metric sample (the raylet
            # sends one every ~4th beat) — feed the time-series ring
            self._ingest_metrics(
                d.get("metrics_source")
                or f"{node_id.hex()[:8]}/raylet", d["metrics"])
        if "available" in d and node_id in self.nodes:
            self.available[node_id] = ResourceSet.from_raw(d["available"])
            if any(r["state"] == "PENDING"
                   for r in self.placement_groups.values()):
                await self._retry_pending_pgs()
            # resources freed elsewhere may unblock queued actors —
            # without this, a pending actor waits for a node REGISTRATION
            # that may never come (the deadlock: all slots busy at
            # creation time, freed later)
            if self._pending_actor_queue:
                await self._try_schedule_pending_actors()
        return True

    async def h_get_all_nodes(self, conn, d):
        return [_node_public(info) for info in self.nodes.values()]

    async def h_get_available_resources(self, conn, d):
        """Heartbeat-fresh per-node availability, used by raylets for
        load-aware spillback (reference: the scheduler's cluster resource
        view fed by resource usage broadcast, cluster_resource_scheduler.cc:217)."""
        return {node_id: avail.raw()
                for node_id, avail in self.available.items()
                # DRAINING nodes are leaving — spillback must not target
                # them, so they simply vanish from this view
                if self.nodes.get(node_id, {}).get("state") == "ALIVE"}

    async def h_drain_node(self, conn, d):
        """Start (or report) a graceful drain: ALIVE -> DRAINING here;
        the raylet then migrates its plasma objects to survivors,
        finishes in-flight leases (bounded by the deadline), checkpoints
        restartable actor state, calls node_drained and exits — so the
        node finalizes DRAINED, never tripping the crash path. `preempt`
        compresses the deadline (checkpoints first, objects best-effort)
        and counts a preemption notice. Idempotent: a second drain call
        or a notice on an already-draining node reports the in-progress
        state without restarting anything."""
        node_id = d["node_id"]
        info = self.nodes.get(node_id)
        preempt = bool(d.get("preempt"))
        if preempt:
            M_PREEMPT_NOTICES.inc()
        if info is None:
            return {"state": "UNKNOWN"}
        if info["state"] == "DRAINING":
            return {"state": "DRAINING",
                    "deadline_s": info.get("drain_deadline_s")}
        deadline_s = d.get("deadline_s")
        if deadline_s is None:
            deadline_s = (self.config.preempt_drain_deadline_s if preempt
                          else self.config.drain_deadline_s)
        info["state"] = "DRAINING"
        info["drain_deadline_s"] = float(deadline_s)
        info["drain_preempt"] = preempt
        info["drain_started"] = time.time()
        self._persist("nodes", node_id, info)
        from ray_tpu._private.events import WARNING

        self._event(WARNING, "NODE_DRAINING",
                    f"node {node_id.hex()[:8]} draining "
                    f"({'preempt' if preempt else 'planned'}, "
                    f"deadline {float(deadline_s):.1f}s)",
                    node_id=node_id.hex(), preempt=preempt)
        # "updated" (not "removed"): every raylet keeps the node in its
        # cluster view but reads state=DRAINING and stops targeting it
        # for spillback/locality; new placements mask its coords
        await self.publish("nodes", {"event": "updated",
                                     "node": _node_public(info)})
        node_conn = self.node_conns.get(node_id)
        if node_conn is not None and not node_conn.closed:
            try:
                await asyncio.wait_for(
                    node_conn.call("drain", {"deadline_s": deadline_s,
                                             "preempt": preempt}),
                    timeout=5.0)
            except Exception:
                logger.warning("drain RPC to %s failed; the heartbeat "
                               "checker will reap it past the deadline",
                               node_id.hex()[:8])
        return {"state": "DRAINING", "deadline_s": deadline_s}

    async def h_node_drained(self, conn, d):
        """The raylet finished draining and is about to exit."""
        await self._finish_drain(d["node_id"],
                                 migrated=int(d.get("migrated", 0)),
                                 leftovers=int(d.get("leftovers", 0)))
        return True

    def _remember_departed(self, node_id: bytes, topo: dict | None):
        if not topo:
            return
        now = time.time()
        self._departed_coords[node_id.hex()[:8]] = (now, dict(topo))
        for key in [k for k, (ts, _) in self._departed_coords.items()
                    if now - ts > _DEPARTED_COORD_TTL_S]:
            self._departed_coords.pop(key, None)

    async def _finish_drain(self, node_id: bytes, migrated: int = 0,
                            leftovers: int = 0):
        """Planned twin of _remove_node: the node leaves as DRAINED, so
        nothing trips the crash path — restartable actors relocate
        without burning a restart, and only this node's own directory
        entries drop (migrated copies on survivors keep every object
        resolvable)."""
        info = self.nodes.pop(node_id, None)
        self.available.pop(node_id, None)
        self._topo_cache = None
        self.last_heartbeat.pop(node_id, None)
        self.node_conns.pop(node_id, None)
        if info is None:
            return
        self._remember_departed(node_id, info.get("topology"))
        from ray_tpu._private.events import INFO

        self._event(INFO, "NODE_DRAINED",
                    f"node {node_id.hex()[:8]} drained "
                    f"({migrated} objects migrated, {leftovers} left)",
                    node_id=node_id.hex(), migrated=migrated)
        info["state"] = "DRAINED"
        self._persist_del("nodes", node_id)
        await self.publish("nodes", {"event": "removed",
                                     "node": _node_public(info),
                                     "reason": "drained"})
        if self.shard_addresses:
            await self._broadcast_shards("prune_node", {"node_id": node_id})
        # Planned relocation: restartable actors move to a survivor
        # without consuming a restart; pinned (max_restarts=0) ones die.
        for actor_id, rec in list(self.actors.items()):
            if rec.get("node_id") == node_id and rec["state"] in (ALIVE, PENDING_CREATION):
                await self._on_actor_interrupted(actor_id, "node drained",
                                                 planned=True)
        for oid, rec in list(self.object_locations.items()):
            rec["nodes"].discard(node_id)
            if not rec["nodes"]:
                # a leftover the drain could not migrate in time: same
                # typed-loss path as a crash, scoped to the leftovers
                del self.object_locations[oid]

    async def _remove_node(self, node_id: bytes, reason: str):
        info = self.nodes.pop(node_id, None)
        self.available.pop(node_id, None)
        self._topo_cache = None
        self.last_heartbeat.pop(node_id, None)
        self.node_conns.pop(node_id, None)
        if info is None:
            return
        self._remember_departed(node_id, info.get("topology"))
        from ray_tpu._private.events import ERROR

        self._event(ERROR, "NODE_REMOVED",
                    f"node {node_id.hex()[:8]} removed: {reason}",
                    node_id=node_id.hex(), reason=reason)
        info["state"] = "DEAD"
        self._persist_del("nodes", node_id)
        await self.publish("nodes", {"event": "removed",
                                     "node": _node_public(info),
                                     "reason": reason})
        if self.shard_addresses:
            # the object-directory partitions live on the shards: drop
            # every location entry naming the dead node
            await self._broadcast_shards("prune_node", {"node_id": node_id})
        # Fail or restart actors that lived on this node.
        for actor_id, rec in list(self.actors.items()):
            if rec.get("node_id") == node_id and rec["state"] in (ALIVE, PENDING_CREATION):
                await self._on_actor_interrupted(actor_id, f"node died ({reason})")
        for oid, rec in list(self.object_locations.items()):
            rec["nodes"].discard(node_id)
            if not rec["nodes"]:
                # no copy left anywhere: pulls waiting on this object
                # hit the empty-directory deadline and fail typed
                del self.object_locations[oid]

    async def heartbeat_checker(self):
        cfg = self.config
        timeout = cfg.heartbeat_interval_s * cfg.num_heartbeats_timeout
        woke = time.monotonic()
        while True:
            await asyncio.sleep(cfg.heartbeat_interval_s)
            now = time.monotonic()
            # This process overslept: while it did not run it received
            # no beat either, so the silence it would count is its own.
            # (A TPU host stops as a whole for seconds while libtpu
            # initialises its chips — 5-9 s with one chip, longer with
            # four — and a checker that wakes first would declare the
            # raylet dead before its queued beats are read.) Every node
            # is credited the time this loop was late.
            late = now - woke - cfg.heartbeat_interval_s
            woke = now
            if late > cfg.heartbeat_interval_s:
                for node_id in self.last_heartbeat:
                    self.last_heartbeat[node_id] += late
            for node_id, last in list(self.last_heartbeat.items()):
                limit = timeout
                info = self.nodes.get(node_id)
                if info is not None and info.get("state") == "DRAINING":
                    # a draining raylet is busy migrating: give it its
                    # full drain budget + grace before the crash path
                    # takes over (it normally exits via node_drained
                    # well before this)
                    limit = max(timeout,
                                float(info.get("drain_deadline_s") or 0.0)
                                + cfg.drain_grace_s)
                if now - last > limit:
                    logger.warning("node %s missed heartbeats; declaring dead",
                                   node_id.hex()[:8])
                    await self._remove_node(node_id, reason="heartbeat timeout")

    # ---- jobs ----
    async def h_register_job(self, conn, d):
        # Idempotent by driver-supplied token: a replayed call (reply lost
        # across a GCS restart) returns the already-allocated job instead
        # of minting a ghost.
        token = d.get("token") or ""
        if token:
            for rec in self.jobs.values():
                if rec.get("token") == token:
                    return {"job_id": rec["job_id"]}
        job_id = self.next_job.to_bytes(4, "big")
        self.next_job += 1
        self.jobs[job_id] = {"job_id": job_id, "driver_addr": d.get("driver_addr", ""),
                             "start_time": time.time(), "state": "RUNNING",
                             "token": token}
        self._persist("meta", "next_job", self.next_job)
        self._persist("jobs", job_id, self.jobs[job_id])
        return {"job_id": job_id}

    # ---- actors ----
    async def h_register_actor(self, conn, d):
        """Register + schedule an actor creation.

        Protocol parity (reference: gcs_actor_manager.h:125-127): caller
        registers the actor; GCS owns scheduling + lifetime from then on.
        Returns once the actor is scheduled (ALIVE) or queued.
        """
        spec = d["spec"]
        actor_id = spec["actor_id"]
        # Idempotent: a client retrying across a GCS restart (or a lost
        # reply) must not double-register.
        existing_rec = self.actors.get(actor_id)
        if existing_rec is not None:
            return self._actor_public(existing_rec)
        name = spec["actor_creation"].get("name") or ""
        namespace = spec["actor_creation"].get("namespace") or "default"
        if name:
            key = (namespace, name)
            if key in self.named_actors:
                existing = self.named_actors[key]
                if self.actors.get(existing, {}).get("state") != DEAD:
                    raise ValueError(f"actor name {name!r} already taken")
            self.named_actors[key] = actor_id
            self._persist("named_actors", f"{namespace}\x00{name}", actor_id)
        rec = {
            "actor_id": actor_id,
            "spec": spec,
            "state": PENDING_CREATION,
            "address": "",
            "task_channel": "",
            "node_id": None,
            "worker_id": None,
            "name": name,
            "namespace": namespace,
            "num_restarts": 0,
            "max_restarts": spec["actor_creation"].get("max_restarts", 0),
            "death_cause": "",
        }
        self.actors[actor_id] = rec
        self._persist_actor(rec)
        await self._mirror("actors", actor_id, self._actor_public(rec))
        await self._schedule_actor(actor_id)
        return self._actor_public(rec)

    async def _schedule_actor(self, actor_id: bytes):
        rec = self.actors[actor_id]
        spec = rec["spec"]
        need = ResourceSet.from_raw(spec["resources"])
        # Random-among-feasible policy (reference:
        # gcs_actor_schedule_strategy.h:42 GcsRandomActorScheduleStrategy),
        # honoring placement-group bundle location when present.
        candidates = []
        if spec.get("pg_id") is not None:
            pg = self.placement_groups.get(spec["pg_id"])
            if pg and pg["state"] == "CREATED":
                idx = spec.get("bundle_index", -1)
                bundle_nodes = {b["node_id"] for i, b in enumerate(pg["bundles"])
                                if idx in (-1, i)}
                candidates = [n for n in bundle_nodes if n in self.nodes]
        if not candidates:
            candidates = [
                node_id for node_id, avail in self.available.items()
                if need.is_subset_of(avail)
            ]
        # Only ALIVE nodes with a live raylet connection are placeable.
        # A restored-from-storage node whose raylet hasn't redialed yet
        # is NOT dead (its actors are alive) — skip it and let the
        # heartbeat checker decide its fate, never _remove_node from
        # here. DRAINING nodes are leaving: never place new actors there.
        candidates = [
            n for n in candidates
            if (c := self.node_conns.get(n)) is not None and not c.closed
            and self.nodes.get(n, {}).get("state") == "ALIVE"
        ]
        if not candidates:
            if actor_id not in self._pending_actor_queue:
                self._pending_actor_queue.append(actor_id)
            # one-shot logging: the heartbeat-driven retry re-enters here
            # every interval for a stuck actor
            if actor_id not in self._pending_logged:
                self._pending_logged.add(actor_id)
                logger.info("actor %s pending: no feasible node",
                            actor_id.hex()[:8])
                # infeasible-anywhere warning (reference:
                # cluster_task_manager.cc logs infeasible tasks)
                totals = [ResourceSet.from_raw(n["resources"])
                          for n in self.nodes.values()]
                if not any(need.is_subset_of(t) for t in totals):
                    logger.warning(
                        "actor %s requires %s, which exceeds every "
                        "node's TOTAL capacity — it will never schedule "
                        "on the current cluster", actor_id.hex()[:8],
                        need.to_dict())
            return
        self._pending_logged.discard(actor_id)
        node_id = random.choice(candidates)
        conn = self.node_conns[node_id]
        rec["node_id"] = node_id
        try:
            reply = await conn.call("create_actor", {"spec": spec})
        except Exception as e:
            if isinstance(getattr(e, "exc", None), InsufficientResources):
                # The GCS's availability view was stale (lease grants race
                # the heartbeat): that is a scheduling miss, not an actor
                # failure — requeue, and correct the view so the next
                # pass picks another node (the true value arrives with
                # the node's next heartbeat).
                self.available[node_id] = ResourceSet()
                if actor_id not in self._pending_actor_queue:
                    self._pending_actor_queue.append(actor_id)
                logger.info("actor %s bounced off %s (stale availability);"
                            " requeued", actor_id.hex()[:8],
                            node_id.hex()[:8])
                return
            logger.warning("actor creation on %s failed: %s", node_id.hex()[:8], e)
            await self._on_actor_interrupted(actor_id, f"creation failed: {e}")
            return
        rec["state"] = ALIVE
        rec["address"] = reply["worker_address"]
        # same-node direct task channel of the hosting worker ("" when
        # unavailable; owners on other nodes can't reach it and fall
        # back to the rpc address)
        rec["task_channel"] = reply.get("task_channel") or ""
        rec["worker_id"] = reply["worker_id"]
        await self._publish_actor(rec)

    async def _on_actor_interrupted(self, actor_id: bytes, reason: str,
                                    planned: bool = False):
        rec = self.actors.get(actor_id)
        if rec is None or rec["state"] == DEAD:
            return
        restarts_left = (rec["max_restarts"] == -1
                         or rec["num_restarts"] < rec["max_restarts"])
        if planned:
            # drain relocation: moving a restartable actor is free (no
            # restart burned) — only actors pinned at max_restarts=0
            # cannot be relocated and die with the node
            restarts_left = rec["max_restarts"] != 0
        if restarts_left:
            if not planned:
                rec["num_restarts"] += 1
            # the new incarnation checks the KV for drained-away state
            # (actor_ckpt:<id>, written by the departing raylet) and
            # restores via __ray_restore__ before taking traffic
            rec["spec"]["restore"] = True
            rec["state"] = RESTARTING
            rec["address"] = ""
            await self._publish_actor(rec)
            await self._schedule_actor(actor_id)
        else:
            rec["state"] = DEAD
            rec["death_cause"] = reason
            rec["address"] = ""
            if self.kv.pop(f"actor_ckpt:{actor_id.hex()}", None) is not None:
                self._persist_del("kv", f"actor_ckpt:{actor_id.hex()}")
            await self._publish_actor(rec)

    async def _publish_actor(self, rec):
        # Every externally-visible actor transition goes through here, so
        # it is also the persistence + event point.
        if rec["state"] in (DEAD, RESTARTING):
            from ray_tpu._private.events import ERROR, WARNING

            self._event(
                ERROR if rec["state"] == DEAD else WARNING,
                "ACTOR_DEAD" if rec["state"] == DEAD else "ACTOR_RESTART",
                f"actor {rec['actor_id'].hex()[:8]} "
                f"({rec['spec']['name']}) -> {rec['state']}: "
                f"{rec.get('death_cause') or 'restarting'}",
                actor_id=rec["actor_id"].hex(),
                class_name=rec["spec"]["name"])
        self._persist_actor(rec)
        # mirror BEFORE the publish: a subscriber poked awake by the push
        # must read back at-least-as-fresh state from the owning shard
        await self._mirror("actors", rec["actor_id"], self._actor_public(rec))
        await self.publish(f"actor:{rec['actor_id'].hex()}", self._actor_public(rec))

    def _actor_public(self, rec):
        return {
            "actor_id": rec["actor_id"],
            "state": rec["state"],
            "address": rec["address"],
            "node_id": rec["node_id"],
            "name": rec["name"],
            "namespace": rec["namespace"],
            "num_restarts": rec["num_restarts"],
            "max_restarts": rec["max_restarts"],
            "death_cause": rec["death_cause"],
            "task_channel": (rec.get("task_channel", "")
                             if rec["state"] == ALIVE else ""),
            "class_name": rec["spec"]["name"],
        }

    async def h_get_actor(self, conn, d):
        rec = self.actors.get(d["actor_id"])
        return self._actor_public(rec) if rec else None

    async def h_get_named_actor(self, conn, d):
        key = (d.get("namespace") or "default", d["name"])
        actor_id = self.named_actors.get(key)
        if actor_id is None:
            return None
        return self._actor_public(self.actors[actor_id])

    async def h_list_actors(self, conn, d):
        return [self._actor_public(r) for r in self.actors.values()]

    async def h_actor_alive(self, conn, d):
        """Raylet reports a restarted/relocated actor is up (unused in the
        normal path — creation reply carries the address)."""
        rec = self.actors.get(d["actor_id"])
        if rec:
            rec["state"] = ALIVE
            rec["address"] = d["address"]
            await self._publish_actor(rec)
        return True

    async def h_kill_actor(self, conn, d):
        actor_id = d["actor_id"]
        rec = self.actors.get(actor_id)
        if rec is None:
            return False
        no_restart = d.get("no_restart", True)
        if no_restart:
            rec["max_restarts"] = rec["num_restarts"]
        node_conn = self.node_conns.get(rec.get("node_id"))
        if node_conn is not None and rec["state"] == ALIVE:
            try:
                await node_conn.call("kill_actor_worker",
                                     {"worker_id": rec["worker_id"],
                                      "actor_id": actor_id})
            except Exception:
                pass
        if no_restart:
            rec["state"] = DEAD
            rec["death_cause"] = "killed via kill()"
            rec["address"] = ""
            await self._publish_actor(rec)
        return True

    async def h_report_worker_failure(self, conn, d):
        """Raylet reports a dead worker, listing actors it hosted."""
        for actor_id in d.get("actor_ids", []):
            rec = self.actors.get(actor_id)
            if rec is not None and rec["state"] in (ALIVE, RESTARTING):
                if d.get("intended", False):
                    rec["state"] = DEAD
                    rec["death_cause"] = "actor exited"
                    rec["address"] = ""
                    await self._publish_actor(rec)
                else:
                    await self._on_actor_interrupted(actor_id, "worker died")
        return True

    async def _try_schedule_pending_actors(self):
        queue, self._pending_actor_queue = self._pending_actor_queue, []
        for actor_id in queue:
            if self.actors.get(actor_id, {}).get("state") != DEAD:
                await self._schedule_actor(actor_id)

    # ---- profiling / metrics ----
    def _event(self, severity: str, label: str, message: str, **fields):
        """GCS-originated structured event: file + own ring."""
        from ray_tpu._private import events

        self.events.append(
            events.report_event(severity, label, message, **fields))

    async def h_report_event(self, conn, d):
        self.events.append(d)
        return True

    async def h_get_events(self, conn, d):
        out = list(self.events)
        sev = d.get("severity")
        if sev:
            out = [e for e in out if e.get("severity") == sev]
        limit = d.get("limit")
        limit = 1000 if limit is None else int(limit)
        if limit <= 0:
            return []
        return out[-limit:]

    async def h_add_profile_events(self, conn, d):
        if _fp.ARMED:
            # trace-table apply seam: `raise` models a failed table
            # write — the batch is dropped HERE (counted, typed log)
            # while the sender's requeue path stays untouched
            try:
                await _fp.fire_async_strict("gcs.trace_table.apply")
            except _fp.FailpointError:
                M_TRACE_APPLY_FAILURES.inc()
                logger.warning("trace table apply failed (failpoint); "
                               "dropping batch of %d events",
                               len(d.get("events", ())))
                return False
        self.profile_events.append({
            "component_type": d["component_type"],
            "component_id": d["component_id"],
            "node_id": d.get("node_id"),
            "events": d["events"],
        })
        # index trace spans (events carrying a trace id) into the flat
        # trace table so get_trace_spans can filter by trace
        for ev in d["events"]:
            extra = ev.get("extra_data") or {}
            if "tid" in extra:
                self.trace_spans.append({
                    "component_type": d["component_type"],
                    "component_id": d["component_id"],
                    "node_id": d.get("node_id"),
                    "event_type": ev["event_type"],
                    "start_time": ev["start_time"],
                    "end_time": ev["end_time"],
                    "extra_data": extra,
                })
        return True

    async def h_get_profile_events(self, conn, d):
        return list(self.profile_events)

    async def h_get_trace_spans(self, conn, d):
        """Flat span rows from the trace table, optionally filtered to
        one trace (hex trace id)."""
        tid = d.get("trace_id")
        if isinstance(tid, bytes):
            tid = tid.decode()
        out = list(self.trace_spans)
        if tid:
            out = [s for s in out if s["extra_data"].get("tid") == tid]
        return out

    async def h_add_profile_samples(self, conn, d):
        """One collapsed-stack sample batch from any process's sampler
        (sampling_profiler.py) into the bounded profile ring."""
        if _fp.ARMED:
            # same seam class as the trace table: `raise` models a
            # failed ring apply — batch dropped here, typed; the
            # sender's bounded merge-back path stays untouched
            try:
                await _fp.fire_async_strict("gcs.profile_ring.apply")
            except _fp.FailpointError:
                M_TRACE_APPLY_FAILURES.inc()
                logger.warning("profile ring apply failed (failpoint); "
                               "dropping batch of %d stacks",
                               len(d.get("stacks", ())))
                return False
        if d.get("stacks"):
            self.profile_samples.append({
                k: d.get(k) for k in (
                    "component_type", "component_id", "node_id",
                    "t_start", "t_end", "hz", "samples", "stacks")})
        return True

    async def h_get_profile_samples(self, conn, d):
        """Profile-ring read: optionally filtered to one component class
        and/or to batches whose window ended at/after `since`."""
        component = d.get("component")
        since = d.get("since")
        out = []
        for b in self.profile_samples:
            if component and b.get("component_type") != component:
                continue
            if since is not None and (b.get("t_end") or 0) < float(since):
                continue
            out.append(b)
        return out

    def _ingest_own_profile(self):
        """The director IS the ring: its own sampler batches ingest
        directly (no RPC), on the heartbeat-checker cadence."""
        batch = _sprof.drain_batch("gcs")
        if batch is not None:
            self.profile_samples.append(batch)

    async def _drain_shard_profiles(self):
        """Pull the store shards' sampler windows into the ring (shards
        don't dial the director; the director polls them on the same
        cadence that mirrors flow). Each call acks the previously
        ingested window's t_end — a timed-out reply makes the shard
        merge that window back instead of losing it."""
        for idx in range(len(self.shard_addresses)):
            try:
                conn = await self._shard_conn(idx)
                batch = await asyncio.wait_for(
                    conn.call("drain_profile_samples",
                              {"ack": self._shard_profile_acks.get(idx)}),
                    timeout=2.0)
                if batch and batch.get("stacks"):
                    self.profile_samples.append(batch)
                    self._shard_profile_acks[idx] = batch.get("t_end")
            except Exception:
                pass  # delayed, not lost: the shard re-merges unacked

    async def _profile_ingest_loop(self):
        """~2s profile cadence for the control plane itself: fold the
        director's own sampler window (and the shards') into the ring."""
        while True:
            await asyncio.sleep(2.0)
            try:
                self._ingest_own_profile()
                if self.shard_addresses:
                    await self._drain_shard_profiles()
            except Exception:  # pragma: no cover - must never die
                logger.exception("profile ingest tick failed")

    def _ingest_metrics(self, source: str, snap: dict):
        """One timestamped sample per metric into the per-source ring.
        Histograms flatten to scalar series (.count/.sum/.p99) so the
        serving tier's autoscaler can read router p99 over time without
        re-deriving bucket math."""
        import collections as _collections

        ts = time.time()
        rings = self.metrics_history.setdefault(source, {})

        def put(name, value):
            ring = rings.get(name)
            if ring is None:
                ring = rings[name] = _collections.deque(
                    maxlen=self.metrics_history_samples)
            ring.append([ts, float(value)])

        for name, m in snap.items():
            try:
                kind = m.get("type")
                if kind == "histogram":
                    put(name + ".count", m.get("count", 0))
                    put(name + ".sum", m.get("sum", 0.0))
                    p99, saturated = _stats.percentile(
                        m, 0.99, with_saturation=True)
                    put(name + ".p99", p99)
                    # saturation is explicit, not inferred: a p99 AT the
                    # top boundary means "at least this" only when the
                    # quantile actually landed in the overflow bucket
                    put(name + ".p99_saturated", 1.0 if saturated else 0.0)
                    overflow = _stats.overflow_count(m)
                    if overflow:
                        put(name + ".overflow", overflow)
                    ex = _stats.quantile_exemplar(m, 0.99)
                    if ex is not None:
                        # exemplars are strings; they ride a side table
                        # beside the scalar rings, newest wins
                        self.metrics_exemplars.setdefault(
                            source, {})[name] = ex
                else:
                    put(name, m.get("value", 0.0))
            except (TypeError, ValueError, AttributeError):
                continue  # one malformed metric must not drop the batch
        self.metrics_last_push[source] = ts
        # Worker/driver sources are keyed per pid and churn with jobs;
        # nothing else removes a dead process's rings. Evict sources
        # idle past a full retention window (~2s cadence * ring length)
        # so the history stays bounded by live pushers, not by every
        # process that ever pushed.
        cutoff = ts - 2.0 * self.metrics_history_samples
        for stale in [s for s, t in self.metrics_last_push.items()
                      if t < cutoff]:
            self.metrics_history.pop(stale, None)
            self.metrics_last_push.pop(stale, None)
            self.metrics_exemplars.pop(stale, None)

    async def h_push_metrics(self, conn, d):
        """Metric sample push from a worker/driver process (raylets ride
        the heartbeat piggyback instead)."""
        source = d.get("source") or "?"
        self._ingest_metrics(source, d.get("metrics") or {})
        return True

    async def h_get_metrics_history(self, conn, d):
        samples = int(d.get("samples") or 0)
        out = {}
        for source, rings in self.metrics_history.items():
            out[source] = {
                name: list(ring)[-samples:] if samples > 0 else list(ring)
                for name, ring in rings.items()}
        if d.get("meta"):
            # history-epoch envelope (opt-in, shape-preserving for old
            # callers): started_at changing between two reads means the
            # director restarted and the rings reset — the documented
            # lossy-restart contract `ray-tpu top` renders as a marker
            return {"meta": {"started_at": self.started_at,
                             "retention_samples":
                                 self.metrics_history_samples},
                    # p99 exemplars: the trace id behind each histogram's
                    # current tail (`ray-tpu top` prints it; `ray-tpu
                    # trace --trace-id` resolves it to the span tree)
                    "exemplars": {s: dict(ex) for s, ex in
                                  self.metrics_exemplars.items()},
                    "series": out}
        return out

    async def h_debug_state(self, conn, d):
        """Director live state: membership + heartbeat ages, actor/pg/
        job table sizes, pubsub fan-out, observability-ring occupancy,
        shard tier state (each live shard's own debug_state embedded,
        bounded wait)."""
        t_start = time.monotonic()
        mono = time.monotonic()
        nodes = []
        for node_id, info in list(self.nodes.items()):
            last = self.last_heartbeat.get(node_id)
            conn_n = self.node_conns.get(node_id)
            nodes.append({
                "node_id": node_id.hex()[:8],
                "address": info.get("address", ""),
                "state": info.get("state", ""),
                "is_head": bool(info.get("is_head")),
                "heartbeat_age_s": (round(mono - last, 3)
                                    if last is not None else None),
                "conn_live": bool(conn_n is not None
                                  and not conn_n.closed),
            })
        actor_states: dict[str, int] = {}
        for rec in self.actors.values():
            actor_states[rec["state"]] = (
                actor_states.get(rec["state"], 0) + 1)
        snap = {
            "role": "gcs",
            "started_at": self.started_at,
            "nodes_table": nodes,
            "actors_by_state": actor_states,
            "pending_actor_queue": len(self._pending_actor_queue),
            "placement_groups": {
                "total": len(self.placement_groups),
                "pending": sum(1 for r in self.placement_groups.values()
                               if r["state"] in ("PENDING", "INFEASIBLE"))},
            # per-pg bundle->node rows with topology coords (`ray-tpu
            # state placement`; the doctor's topology_mismatch check),
            # bounded like the other introspection surfaces
            "placement_table": self._placement_table(limit=200),
            "jobs": len(self.jobs),
            "kv_keys": len(self.kv),
            "object_locations": len(self.object_locations),
            "pubsub": {ch: len(subs)
                       for ch, subs in list(self.subscriptions.items())
                       if subs},
            "rings": {"events": len(self.events),
                      "profile_events": len(self.profile_events),
                      "trace_spans": len(self.trace_spans),
                      "profile_samples": len(self.profile_samples),
                      "metrics_sources": len(self.metrics_history)},
            "rpc": {"server_conns": len(self.server.connections)},
        }
        if self.shard_addresses:
            async def one(idx):
                try:
                    c = await self._shard_conn(idx)
                    return await asyncio.wait_for(
                        c.call("debug_state", {}), timeout=2.0)
                except Exception as e:
                    return {"error": f"{type(e).__name__}: {e}",
                            "address": self.shard_addresses[idx]}

            snap["shards"] = list(await asyncio.gather(
                *(one(i) for i in range(len(self.shard_addresses)))))
        return _debug.finish_snapshot(snap, t_start)

    def _placement_table(self, limit: int = 200) -> list[dict]:
        """Flat bundle->node rows for every placement group: strategy,
        cost-model name, per-bundle node + topology coord + slice —
        what `ray-tpu state placement` prints and the doctor's
        topology_mismatch finding scans."""
        rows = []
        for rec in list(self.placement_groups.values())[:limit]:
            plan = rec.get("topology_plan") or {}
            base = {
                "pg": rec["pg_id"].hex()[:12],
                "name": rec.get("name", ""),
                "strategy": rec["strategy"],
                "cost_model": (plan.get("cost_model")
                               or rec.get("cost_model") or ""),
                "state": rec["state"],
            }
            if plan:
                base["ring_circumference"] = plan.get("ring_circumference")
            if rec.get("detail"):
                base["detail"] = rec["detail"]
            if rec["state"] != "CREATED":
                rows.append(base)
                continue
            for b in rec["bundles"]:
                topo = b.get("topology") or {}
                nid = b.get("node_id")
                rows.append({
                    **base,
                    "bundle": b.get("bundle_index"),
                    "node": nid.hex()[:8] if isinstance(nid, bytes)
                    else str(nid),
                    "slice": topo.get("slice_id") or "",
                    "coords": ",".join(str(c) for c in
                                       topo.get("coords") or ()) or "",
                })
        return rows

    async def h_get_metrics(self, conn, d):
        """This process's metric registry + computed cluster gauges."""
        from ray_tpu._private import stats

        snap = stats.snapshot()
        snap["gcs.nodes_alive"] = {
            "type": "gauge",
            "value": sum(1 for n in self.nodes.values()
                         if n.get("state") == "ALIVE")}
        snap["gcs.nodes_draining"] = {
            "type": "gauge",
            "value": sum(1 for n in self.nodes.values()
                         if n.get("state") == "DRAINING")}
        snap["gcs.actors_alive"] = {
            "type": "gauge",
            "value": sum(1 for r in self.actors.values()
                         if r["state"] == ALIVE)}
        snap["gcs.placement_groups"] = {
            "type": "gauge", "value": len(self.placement_groups)}
        return snap

    # ---- object directory ----
    async def h_add_object_location(self, conn, d):
        rec = self.object_locations.setdefault(
            d["object_id"], {"nodes": set(), "size": 0})
        rec["nodes"].add(d["node_id"])
        if d.get("size"):
            rec["size"] = int(d["size"])
        return True

    async def h_remove_object_location(self, conn, d):
        rec = self.object_locations.get(d["object_id"])
        if rec:
            rec["nodes"].discard(d["node_id"])
            if not rec["nodes"]:
                del self.object_locations[d["object_id"]]
        return True

    async def h_get_object_locations(self, conn, d):
        rec = self.object_locations.get(d["object_id"])
        return list(rec["nodes"]) if rec else []

    async def h_get_object_locations_batch(self, conn, d):
        """Locations + sizes for a set of objects in one round trip —
        feeds the raylets' locality-aware lease targeting (arg-byte
        weighting) and multi-source pull planning."""
        out = {}
        for oid in d["object_ids"]:
            rec = self.object_locations.get(oid)
            if rec:
                out[oid] = {"nodes": list(rec["nodes"]),
                            "size": rec["size"]}
        return out

    # ---- placement groups ----
    async def h_create_placement_group(self, conn, d):
        """2-phase bundle reservation across raylets (reference:
        gcs_placement_group_scheduler.h:49; strategies :133-160). Infeasible
        groups stay PENDING and are retried as nodes join / resources free
        (STRICT_SPREAD wanting more nodes than the fleet HAS goes
        INFEASIBLE instead — typed at the client — until nodes join)."""
        pg_id = d["pg_id"]
        # unknown cost-model specs fail HERE, typed at creation — never
        # as a silently-heuristic placement
        _topo.resolve_cost_model(d.get("cost_model"))
        # Idempotent: a call replayed across a GCS restart (lost reply)
        # must not reset a CREATED group to PENDING and double-reserve
        # its bundles.
        if pg_id not in self.placement_groups:
            self.placement_groups[pg_id] = {
                "pg_id": pg_id, "bundles": [dict(b) for b in d["bundles"]],
                "strategy": d.get("strategy", "PACK"), "state": "PENDING",
                "name": d.get("name", ""),
                "cost_model": d.get("cost_model") or "",
            }
            self._persist_pg(self.placement_groups[pg_id])
            await self._mirror("pgs", pg_id,
                               _pg_public(self.placement_groups[pg_id]))
        return {"state": await self._try_create_pg(pg_id)}

    async def _retry_pending_pgs(self):
        for pg_id, rec in list(self.placement_groups.items()):
            # INFEASIBLE retries too: a joining node can make a
            # too-wide STRICT_SPREAD placeable again
            if rec["state"] in ("PENDING", "INFEASIBLE"):
                await self._try_create_pg(pg_id)

    async def _try_create_pg(self, pg_id) -> str:
        rec = self.placement_groups.get(pg_id)
        if rec is None:
            return "REMOVED"
        if rec["state"] == "CREATED":
            return "CREATED"
        # INFEASIBLE records re-evaluate in place (the state only moves
        # once the outcome actually changes — _do_create_pg flips it
        # back to PENDING or on to CREATED; flipping it here would
        # re-persist + republish an unchanged record every retry sweep)
        # In-flight guard: while one 2PC attempt awaits raylet RPCs, a
        # concurrent retry (heartbeat/node-join) must not start a second
        # one — double prepare_bundle would double-reserve node resources.
        if rec.get("creating"):
            return "PENDING"
        rec["creating"] = True
        try:
            return await self._do_create_pg(pg_id, rec)
        finally:
            rec["creating"] = False

    async def _do_create_pg(self, pg_id, rec) -> str:
        bundles = rec["bundles"]
        strategy = rec["strategy"]
        t_score = time.perf_counter()
        try:
            placement = self._place_bundles(bundles, strategy,
                                            cost_model=rec.get("cost_model"))
        finally:
            M_PLACEMENT_SCORE_S.observe(time.perf_counter() - t_score)
        plan = self._last_topology_plan
        if placement is None:
            alive = sum(1 for n in self.node_conns.values()
                        if n is not None and not n.closed)
            if strategy == "STRICT_SPREAD" and len(bundles) > alive:
                # the fleet CANNOT hold this group today: surface typed
                # (PlacementGroupInfeasibleError at ready()) instead of
                # an indistinguishable forever-PENDING; node joins flip
                # it back to PENDING and retry
                detail = (f"{len(bundles)} STRICT_SPREAD bundles "
                          f"need distinct nodes; fleet has {alive}")
                if (rec["state"] == "INFEASIBLE"
                        and rec.get("detail") == detail):
                    # unchanged verdict: no persist/mirror/publish churn
                    # on every heartbeat-driven retry sweep
                    return "INFEASIBLE"
                rec["state"] = "INFEASIBLE"
                rec["detail"] = detail
                self._persist_pg(rec)
                await self._mirror("pgs", pg_id, _pg_public(rec))
                await self.publish(f"pg:{pg_id.hex()}", _pg_public(rec))
                return "INFEASIBLE"
            if rec["state"] == "INFEASIBLE":
                # structurally placeable again (a node joined) but not
                # yet reserved: back to PENDING so ready() stops raising
                rec["state"] = "PENDING"
                rec.pop("detail", None)
                self._persist_pg(rec)
                await self._mirror("pgs", pg_id, _pg_public(rec))
                await self.publish(f"pg:{pg_id.hex()}", _pg_public(rec))
            return "PENDING"
        if _fp.ARMED:
            # reserve seam, BETWEEN scoring and the 2PC prepare: `delay`
            # widens the window a scored node can die in (the chaos
            # case); `raise` aborts this attempt — the group stays
            # PENDING and the heartbeat-driven retry re-scores
            try:
                await _fp.fire_async_strict("placement.reserve")
            except _fp.FailpointError:
                logger.warning("placement.reserve failpoint aborted the "
                               "2PC for pg %s; will retry",
                               pg_id.hex()[:8])
                return "PENDING"
        # prepare
        prepared = []
        ok = True
        for idx, node_id in placement.items():
            conn_n = self.node_conns.get(node_id)
            try:
                res = await conn_n.call("prepare_bundle", {
                    "pg_id": pg_id, "bundle_index": idx,
                    "resources": bundles[idx]["resources"],
                })
                if not res:
                    ok = False
                    break
                prepared.append((idx, node_id))
            except Exception:
                ok = False
                break
        if not ok:
            for idx, node_id in prepared:
                conn_n = self.node_conns.get(node_id)
                if conn_n:
                    try:
                        await conn_n.call("cancel_bundle",
                                          {"pg_id": pg_id, "bundle_index": idx})
                    except Exception:
                        pass
            return "PENDING"
        # commit
        committed = []
        for idx, node_id in placement.items():
            conn_n = self.node_conns.get(node_id)
            try:
                if conn_n is None or conn_n.closed:
                    raise ConnectionError("node connection lost")
                await conn_n.call("commit_bundle",
                                  {"pg_id": pg_id, "bundle_index": idx})
                committed.append(idx)
            except Exception:
                # A node died between prepare and commit: unwind everything
                # (committed bundles returned, prepared ones cancelled) and
                # stay PENDING for the next retry.
                for jdx, jnode in placement.items():
                    conn_j = self.node_conns.get(jnode)
                    if conn_j is None or conn_j.closed:
                        continue
                    method = ("return_bundle" if jdx in committed
                              else "cancel_bundle")
                    try:
                        await conn_j.call(method, {"pg_id": pg_id,
                                                   "bundle_index": jdx})
                    except Exception:
                        pass
                return "PENDING"
        if self.placement_groups.get(pg_id) is not rec:
            # Removed while the 2PC was in flight: give the bundles back.
            for idx, node_id in placement.items():
                conn_n = self.node_conns.get(node_id)
                if conn_n is not None and not conn_n.closed:
                    try:
                        await conn_n.call("return_bundle", {
                            "pg_id": pg_id, "bundle_index": idx})
                    except Exception:
                        pass
            return "REMOVED"
        rec["state"] = "CREATED"
        rec.pop("detail", None)
        rec["bundles"] = [
            {"bundle_index": i, "resources": bundles[i]["resources"],
             "node_id": placement[i],
             # the assigned node's torus coord rides each bundle row —
             # `ray-tpu state placement`, the doctor's topology_mismatch
             # check, and transport derivation all read it
             "topology": self.nodes.get(placement[i], {}).get("topology")}
            for i in range(len(bundles))
        ]
        if plan is not None:
            # ICI_RING placed by topology: the plan gates client-side
            # transport derivation (topology.transport_plan) — a PACK
            # fallback carries none, so ad-hoc gangs keep probing
            rec["topology_plan"] = plan
        self._persist_pg(rec)
        # mirror-then-publish (same ordering rule as actors), then wake
        # PlacementGroup.ready() waiters parked on the pg channel — the
        # payload carries the full record so waiters don't even need the
        # read-back
        await self._mirror("pgs", pg_id, _pg_public(rec))
        await self.publish(f"pg:{pg_id.hex()}", _pg_public(rec))
        return "CREATED"

    def _nodes_by_slice(self, node_ids):
        """Group nodes by TPU slice_id (ICI domain). Nodes without a
        slice descriptor are excluded."""
        slices: dict[str, list] = {}
        for nid in node_ids:
            desc = self.nodes.get(nid, {}).get("tpu_slice")
            if desc and desc.get("slice_id"):
                slices.setdefault(desc["slice_id"], []).append(nid)
        return slices

    def _place_ici_ring(self, bundles, needs, avail, cost_model: str):
        """ICI_RING core: enumerate candidate bundle->node assignments
        over the snake order of coord-bearing nodes, score each with the
        request's cost model, take the cheapest that fits.

        Candidates per snake offset: a greedy FILL (consecutive ranks
        pack onto each node while it fits, then advance — one free node
        big enough yields the all-on-one-host/shm assignment) and a
        STRIDED spread (ranks spaced across the torus). The fill family
        contains the minimal rings the default model wants; the strided
        family gives an inverted/learned model genuinely different
        geometry to prefer. Returns placement dict or None (no located
        candidates / nothing fits / scoring seam failed)."""
        if self._topo_cache is None:
            cached: dict[bytes, _topo.TopologyCoord] = {}
            for nid, info in self.nodes.items():
                c = _topo.TopologyCoord.from_dict(info.get("topology"))
                if c is not None:
                    cached[nid] = c
            self._topo_cache = (cached, sorted(
                cached, key=lambda n: (cached[n].slice_id,
                                       _topo.snake_key(cached[n]))))
        coords, snake = self._topo_cache
        # liveness/availability filter is per-decision (conn state moves
        # without a membership event); the snake sort is not
        live = [nid for nid in snake
                if nid in avail
                and (cn := self.node_conns.get(nid)) is not None
                and not cn.closed]
        if not live:
            return None
        if _fp.ARMED:
            # scoring seam: `raise` models a failed topology read —
            # placement degrades to the counted PACK fallback; `delay`
            # stretches the scoring window the latency gate watches
            try:
                _fp.fire_strict("placement.topology_score")
            except _fp.FailpointError:
                logger.warning("placement.topology_score failpoint: "
                               "falling back to PACK")
                return None
        try:
            model = _topo.resolve_cost_model(cost_model)
        except ValueError:
            # model vanished since creation (process restart without the
            # registering import): heuristic fallback is counted, not
            # silent
            logger.warning("cost model %r unresolvable at scoring time; "
                           "falling back to PACK", cost_model)
            return None
        bind = getattr(model, "bind_context", None)
        if bind is not None:
            bind({"metrics_history": self.metrics_history,
                  # node-id prefix -> registered coord host_id, so a
                  # model keying on metric sources (<node8>/raylet) can
                  # reach coords whose host_id isn't the node-id hex
                  "node_hosts": {nid.hex()[:8]: c.host_id
                                 for nid, c in coords.items()}})
        order = live
        k = len(needs)
        n = len(order)
        # Fast path for the overwhelmingly common gang shape — every
        # bundle identical: one integer pass over the raw fixed-point
        # dicts computes how many bundle-slots each node fits, and
        # candidate generation becomes index walking (no ResourceSet
        # churn inside the offset loop). This is what keeps the scoring
        # A/B within the PACK arm's latency bucket.
        need_raw = needs[0].raw()
        uniform = all(nd.raw() == need_raw for nd in needs[1:])
        caps: dict[bytes, int] = {}
        if uniform:
            for nid in order:
                araw = avail[nid].raw()
                c = k
                for res, q in need_raw.items():
                    if q > 0:
                        c = min(c, araw.get(res, 0) // q)
                caps[nid] = c

        def fits(assignment) -> bool:
            if uniform:
                used: dict[bytes, int] = {}
                for nid in assignment:
                    used[nid] = used.get(nid, 0) + 1
                    if used[nid] > caps[nid]:
                        return False
                return True
            trial: dict[bytes, ResourceSet] = {}
            for i, nid in enumerate(assignment):
                rs = trial.get(nid)
                if rs is None:
                    rs = trial[nid] = avail[nid].copy()
                if not needs[i].is_subset_of(rs):
                    return False
                rs.subtract(needs[i])
            return True

        def fill_from(offset: int) -> list[bytes] | None:
            """Greedy walk from snake position `offset`: consecutive
            ranks pack onto each node while it fits, then advance."""
            out: list[bytes] = []
            if uniform:
                pos = offset
                while len(out) < k and pos < offset + n:
                    nid = order[pos % n]
                    take = min(caps[nid], k - len(out))
                    out.extend([nid] * take)
                    pos += 1
                return out if len(out) == k else None
            rs = None
            pos = offset
            for i in range(k):
                while pos < offset + n:
                    nid = order[pos % n]
                    if rs is None:
                        rs = avail[nid].copy()
                    if needs[i].is_subset_of(rs):
                        rs.subtract(needs[i])
                        out.append(nid)
                        break
                    pos += 1
                    rs = None
                else:
                    return None
            return out

        # Generate-and-score incrementally, fill candidates first: the
        # default model's minimum for a distinct-node ring is k (every
        # wire hop >= 1), so once a perfect ring scores <= k — and no
        # node could host two ranks (caps <= 1 => no 0-hop same-host
        # shortcuts exist) — stop scanning. Pluggable models see every
        # candidate.
        ring_default = isinstance(model, _topo.RingDistanceCostModel)
        can_pack = (not uniform) or any(c > 1 for c in caps.values())
        seen: set[tuple] = set()
        best, best_cost = None, None
        stride = max(1, n // k)

        def consider(cand) -> bool:
            """Score one candidate; True = stop scanning (provably
            optimal for the default model)."""
            nonlocal best, best_cost
            key = tuple(cand)
            if key in seen:
                return False
            seen.add(key)
            cost = model.score(bundles, [coords[nid] for nid in cand])
            if best_cost is None or cost < best_cost:
                best, best_cost = cand, cost
            return ring_default and not can_pack and best_cost <= k

        done = False
        for offset in range(n):
            if uniform and caps[order[offset]] == 0:
                continue  # identical fill to the next live offset
            filled = fill_from(offset)
            if filled is not None and consider(filled):
                done = True
                break
        if not done and stride > 1:
            for offset in range(n):
                strided = [order[(offset + j * stride) % n]
                           for j in range(k)]
                if fits(strided) and consider(strided):
                    break
        if best is None:
            return None
        for i, nid in enumerate(best):
            avail[nid].subtract(needs[i])
        ring = [coords[nid] for nid in best]
        # Torus holes this plan routed around: coord-bearing nodes that
        # are DRAINING (still registered, masked out of avail) plus
        # recently-departed coords — the placement record shows exactly
        # which coords the snake re-sort skipped.
        now = time.time()
        masked = [dict(self.nodes[nid].get("topology") or {})
                  for nid in snake
                  if self.nodes.get(nid, {}).get("state")
                  not in (None, "ALIVE")]
        masked.extend(dict(t) for ts, t in self._departed_coords.values()
                      if now - ts <= _DEPARTED_COORD_TTL_S)
        self._last_topology_plan = {
            "cost_model": getattr(model, "name", "") or cost_model or "ring",
            "cost": float(best_cost),
            "ring_circumference": _topo.ring_circumference(ring),
            "candidates_scored": len(seen),
            # the (data, fsdp) factorization FSDP-mode meshes derive
            # from this gang (SNIPPETS [2] table; parallel/mesh.py)
            "mesh_shape": list(_topo.mesh_shape_for(k)),
        }
        if masked:
            self._last_topology_plan["masked_coords"] = masked
            M_RING_REPLACEMENTS.inc()
        return {i: nid for i, nid in enumerate(best)}

    def _place_bundles(self, bundles, strategy, cost_model: str = ""):
        """Map bundle_index -> node_id, or None if infeasible now.

        TPU topology (SURVEY §7 step 1; reference strategy analog:
        gcs_placement_group_scheduler.h:133-160): STRICT_PACK means "one
        ICI domain" — a single node, or, for TPU bundles, the hosts of
        ONE slice (equal slice_id ⇔ ICI-connected; never spans slices).
        STRICT_SPREAD prefers distinct hosts of one slice before falling
        back to arbitrary distinct nodes, so a dp group's gradient
        allreduce rides ICI when a big-enough slice exists.

        ICI_RING orders candidate nodes so CONSECUTIVE bundle ranks are
        ICI neighbors (minimal ring circumference over the torus),
        scored by the request's pluggable cost model; with no
        coord-bearing candidates it falls back to PACK, counted by
        `gcs.placement_topology_fallbacks_total`. Sets
        `self._last_topology_plan` (ICI_RING success only) so
        _do_create_pg can stamp the record without re-deriving."""
        self._last_topology_plan = None
        # DRAINING nodes are masked out of every strategy's candidate
        # set: a group placed now must survive the node's departure
        avail = {nid: r.copy() for nid, r in self.available.items()
                 if self.nodes.get(nid, {}).get("state") == "ALIVE"}
        placement: dict[int, bytes] = {}
        node_ids = list(avail.keys())
        if not node_ids:
            return None

        def fits(node_id, res: ResourceSet):
            return res.is_subset_of(avail[node_id])

        def take(node_id, res: ResourceSet):
            avail[node_id].subtract(res)

        needs = [ResourceSet.from_raw(b["resources"]) for b in bundles]
        wants_tpu = any(n.get("TPU") > 0 for n in needs)

        if strategy == "ICI_RING":
            local = self._place_ici_ring(bundles, needs, avail, cost_model)
            if local is not None:
                return local
            # no topology to score (or the scoring seam failed): behave
            # exactly like PACK, but count the downgrade only when the
            # gang actually PLACES topology-blind — a merely
            # capacity-starved fleet stays PENDING and re-enters
            # ICI_RING scoring on the next availability change, which
            # must not ring the fallback alarm once per retry heartbeat
            placed = self._place_bundles(bundles, "PACK", cost_model)
            if placed is not None:
                M_TOPO_FALLBACKS.inc()
            return placed

        def pack_within(cand_ids):
            """Fit all bundles onto `cand_ids`, placing the LARGEST need
            first onto the emptiest node (first-fit-decreasing — a
            smaller bundle grabbing the big node can't strand a larger
            one); returns placement dict or None. Mutates avail."""
            local: dict[int, bytes] = {}
            order = sorted(range(len(needs)),
                           key=lambda i: -needs[i].get("TPU"))
            for i in order:
                need = needs[i]
                cs = [n for n in cand_ids if fits(n, need)]
                if not cs:
                    return None
                node = max(cs, key=lambda n: avail[n].get("TPU"))
                take(node, need)
                local[i] = node
            return local

        if strategy in ("PACK", "STRICT_PACK"):
            # try to fit all on one node first
            for node_id in sorted(node_ids,
                                  key=lambda n: -avail[n].get("CPU")):
                trial = avail[node_id].copy()
                ok = True
                for n in needs:
                    if not n.is_subset_of(trial):
                        ok = False
                        break
                    trial.subtract(n)
                if ok:
                    for i in range(len(bundles)):
                        placement[i] = node_id
                    return placement
            if strategy == "STRICT_PACK":
                if not wants_tpu:
                    return None
                # one ICI domain: all bundles within a single slice
                for slice_id, members in sorted(
                        self._nodes_by_slice(node_ids).items(),
                        key=lambda kv: -sum(avail[n].get("TPU")
                                            for n in kv[1])):
                    saved = {n: avail[n].copy() for n in members}
                    local = pack_within(members)
                    if local is not None:
                        return local
                    avail.update(saved)
                return None
            # PACK falls back to spread-fit
        if strategy == "STRICT_SPREAD":
            if len(bundles) > len(node_ids):
                return None
            if wants_tpu:
                # prefer distinct hosts of ONE slice (ICI for the group)
                for slice_id, members in sorted(
                        self._nodes_by_slice(node_ids).items(),
                        key=lambda kv: -len(kv[1])):
                    if len(members) < len(bundles):
                        continue
                    saved = {n: avail[n].copy() for n in members}
                    used: set[bytes] = set()
                    local: dict[int, bytes] = {}
                    for i, need in enumerate(needs):
                        cs = [n for n in members
                              if n not in used and fits(n, need)]
                        if not cs:
                            local = None
                            break
                        node = random.choice(cs)
                        used.add(node)
                        take(node, need)
                        local[i] = node
                    if local is not None:
                        return local
                    avail.update(saved)
            used = set()
            for i, need in enumerate(needs):
                cands = [n for n in node_ids if n not in used and fits(n, need)]
                if not cands:
                    return None
                node = random.choice(cands)
                used.add(node)
                take(node, need)
                placement[i] = node
            return placement
        # PACK fallback / SPREAD: round-robin best-fit
        order = node_ids if strategy != "SPREAD" else random.sample(
            node_ids, len(node_ids))
        for i, need in enumerate(needs):
            cands = [n for n in order if fits(n, need)]
            if not cands:
                return None
            if strategy == "SPREAD":
                node = min(cands, key=lambda n: sum(
                    1 for j, p in placement.items() if p == n))
            else:
                node = cands[0]
            take(node, need)
            placement[i] = node
        return placement

    async def h_remove_placement_group(self, conn, d):
        self._persist_del("placement_groups", d["pg_id"])
        rec = self.placement_groups.pop(d["pg_id"], None)
        if rec is not None:
            await self._mirror("pgs", d["pg_id"], None)
            await self.publish(f"pg:{d['pg_id'].hex()}",
                               {"pg_id": d["pg_id"], "state": "REMOVED"})
        if rec and rec["state"] == "CREATED":
            for b in rec["bundles"]:
                conn_n = self.node_conns.get(b["node_id"])
                if conn_n is not None and not conn_n.closed:
                    try:
                        await conn_n.call("return_bundle", {
                            "pg_id": d["pg_id"],
                            "bundle_index": b["bundle_index"]})
                    except Exception:
                        pass
        return rec is not None

    async def h_get_placement_group(self, conn, d):
        return self.placement_groups.get(d["pg_id"])

    async def h_get_named_placement_group(self, conn, d):
        for rec in self.placement_groups.values():
            if rec.get("name") and rec["name"] == d["name"]:
                return rec
        return None

    async def h_list_placement_groups(self, conn, d):
        return list(self.placement_groups.values())

    # ---- lifecycle ----
    async def _on_disconnect(self, conn):
        for subs in self.subscriptions.values():
            subs.discard(conn)
        node_id = conn.context.get("node_id")
        if node_id is not None and node_id in self.nodes:
            # Keep the node until heartbeats actually time out? No: a closed
            # raylet connection means the process died — remove immediately.
            await self._remove_node(node_id, reason="raylet disconnected")

    async def _connect_shards(self):
        """Dial every store shard at startup and push an initial mirror
        resync (a director restarted against its persisted tables
        refreshes mirrors that may have gone stale while it was down;
        reconnects after a shard restart resync via on_reconnect)."""
        for idx in range(len(self.shard_addresses)):
            try:
                conn = await self._shard_conn(idx)
                await conn.ensure_connected()
                await self._resync_shard(idx, conn)
            except Exception:
                logger.warning("initial connect to shard %d failed "
                               "(will keep redialing)", idx)

    async def run(self, port: int, ready_file: str | None = None,
                  uds_dir: str | None = None):
        cfg = get_config()
        self._uds_dir = uds_dir
        _debug.start_loop_lag_monitor()
        actual = await self.server.start_tcp(host=cfg.bind_host, port=port,
                                             uds_dir=uds_dir)
        asyncio.create_task(self.heartbeat_checker())
        # continuous profiling: the director samples itself (a KV-armed
        # rate applied in _restore outranks the env default) and folds
        # its own + the shards' windows into the profile ring
        _sprof.start("gcs")
        asyncio.create_task(self._profile_ingest_loop())
        if self.shard_addresses:
            asyncio.create_task(self._connect_shards())
        logger.info("GCS listening on %s:%d (advertised %s)",
                    cfg.bind_host, actual, cfg.node_ip_address)
        if ready_file:
            tmp = ready_file + ".tmp"
            with open(tmp, "w") as f:
                f.write(str(actual))
            os.rename(tmp, ready_file)
        while True:
            await asyncio.sleep(3600)


def _node_public(info):
    return {k: info.get(k) for k in (
        "node_id", "address", "object_manager_address", "bulk_address",
        "resources", "hostname", "is_head", "state", "labels",
        "tpu_slice", "topology")}


def _pg_public(rec):
    return {k: v for k, v in rec.items() if k != "creating"}


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--ready-file", default=None)
    parser.add_argument("--log-file", default=None)
    parser.add_argument("--store-dir", default=None,
                        help="WAL+snapshot dir; enables persistence/restart")
    parser.add_argument("--shard-addresses", default="",
                        help="comma-separated store-shard addresses "
                             "(index order; empty = unsharded)")
    parser.add_argument("--uds-dir", default=None,
                        help="serve a sibling UDS listener here (same-node "
                             "clients skip the loopback-TCP tax)")
    args = parser.parse_args()
    from ray_tpu._private.log_utils import setup_process_logging

    setup_process_logging("gcs_server", args.log_file)
    _fp.set_role("gcs")
    from ray_tpu._private.events import init_events

    init_events("GCS", "gcs",
                os.path.dirname(args.log_file) if args.log_file else None)
    set_config(Config.load())
    storage = None
    if args.store_dir:
        from ray_tpu.gcs.storage import GcsStorage

        storage = GcsStorage(args.store_dir)
    shard_addresses = [a for a in args.shard_addresses.split(",") if a]
    server = GcsServer(get_config(), storage=storage,
                       shard_addresses=shard_addresses)
    asyncio.run(server.run(args.port, args.ready_file,
                           uds_dir=args.uds_dir))


if __name__ == "__main__":
    main()
