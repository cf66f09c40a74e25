"""Global config registry — the RAY_CONFIG-equivalent.

The reference defines ~90 `RAY_CONFIG(type, name, default)` flags in a single
header (reference: src/ray/common/ray_config_def.h) initialized from a JSON
`_system_config` and propagated to every spawned process. We keep the same
single-source-of-truth + env/JSON override design: every knob is declared
here, overridable via the RAY_TPU_SYSTEM_CONFIG env var (JSON) or the
`_system_config` argument to `ray_tpu.init`, and child processes inherit the
merged dict through that env var.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any

_ENV_VAR = "RAY_TPU_SYSTEM_CONFIG"


@dataclasses.dataclass
class Config:
    # --- object plane ---
    # Objects at or below this size are passed inline through the owner's
    # in-process memory store instead of the shared-memory store
    # (reference: ray_config_def.h max_direct_call_object_size=100KB).
    max_direct_call_object_size: int = 100 * 1024
    # Default shared-memory store capacity per node (bytes).
    object_store_memory: int = 2 * 1024**3
    # "files" = file-per-object mmap store; "native" = the C++ shared-arena
    # slab allocator (native/store/store.cc), built on demand with g++.
    object_store_backend: str = "native"
    # Chunk size for node-to-node object transfer.
    object_transfer_chunk_size: int = 5 * 1024**2
    # Admission control: concurrent inbound object transfers per raylet
    # (reference: pull_manager.h bounded active pulls).
    max_concurrent_object_pulls: int = 4
    # Work-stealing unit for multi-source striped pulls: each source
    # streams ranges of this size off a shared queue, so a slow source
    # naturally ends up transferring fewer bytes and a dead one's
    # remaining ranges are resumed by survivors (Hoplite-style
    # multi-source fetch).
    object_transfer_stripe_size: int = 8 * 1024**2
    # Max sources striped across in one pull (extra directory entries are
    # kept as failover spares).
    max_pull_sources: int = 4
    # Sender-side transfer pin lease: an object being served to a puller
    # is protected from free/eviction for this long past the last
    # activity, so a dead puller cannot pin the arena forever
    # (reference: the pinned_objects set in object_manager.h, bounded
    # here by time instead of by connection liveness alone).
    transfer_pin_ttl_s: float = 20.0
    # A pull whose GCS directory lookup stays EMPTY for this long (no
    # node claims a copy) propagates typed object loss to its waiters
    # instead of spinning the lookup forever.
    pull_no_location_timeout_s: float = 10.0
    # Per-socket IO timeout on the bulk transfer channel (recv/send of
    # one chunk): a stalled peer mid-stream surfaces as a socket timeout
    # and the remaining ranges fail over to other sources.
    bulk_transfer_io_timeout_s: float = 30.0

    # --- locality-aware scheduling ---
    # Weigh lease targets by resident plasma-arg bytes (GCS object
    # directory): a task whose args live on another node is leased there
    # instead of pulling the args here (reference: lease_policy.h
    # locality-aware lease targeting). Spillback/queueing still apply on
    # the target.
    locality_aware_leasing: bool = True
    # Only redirect when the best remote node holds at least this many
    # MORE resident arg bytes than the local node (small args are cheaper
    # to move than the task round trip).
    locality_min_arg_bytes: int = 1024 * 1024
    # Spill directory ("" = session dir /spill).
    object_spilling_path: str = ""
    # Spill when store usage exceeds this fraction.
    object_spilling_threshold: float = 0.8

    # --- control plane ---
    # Heartbeat cadence + miss tolerance (reference: raylet 100ms beats,
    # declared dead after 300 misses; we beat less often, die faster).
    heartbeat_interval_s: float = 0.5
    num_heartbeats_timeout: int = 20
    gcs_port: int = 0  # 0 = pick free port
    # GCS fault tolerance: clients (raylets, workers, drivers) redial a
    # restarted GCS for this long before giving up (reference:
    # gcs_rpc_server_reconnect_timeout_s in ray_config_def.h); the node
    # monitor respawns a crashed GCS when enabled.
    gcs_reconnect_timeout_s: float = 30.0
    gcs_persistence: bool = True
    gcs_auto_restart: bool = True

    # --- sharded control plane ---
    # Number of GCS store-shard processes the high-rate tables (KV,
    # object directory, actor/pg read mirrors) are key-partitioned over
    # (gcs/shard.py; client-side crc32 routing in gcs/client.py). 1 (the
    # default, also settable via RAY_TPU_GCS_SHARDS) spawns no shard
    # processes and preserves the single-GCS layout exactly.
    gcs_shards: int = 1

    # --- scheduling ---
    # Max in-flight lease-reused tasks pushed to one worker
    # (reference: direct_task_transport.h max_tasks_in_flight_per_worker).
    max_tasks_in_flight_per_worker: int = 10
    # Raylet→raylet lease spillback: a raylet that can't grant FORWARDS
    # the lease request to its chosen peer (cycle-guarded) and relays
    # the grant, instead of bouncing the owner back out for another
    # round trip per hop. Max raylet hops a forwarded request may chain
    # through before the last raylet queues it locally (stops ping-pong
    # on a saturated cluster).
    lease_spillback_max_hops: int = 3
    # Lease pre-warm: max leases asked for in one batched
    # request_worker_lease RPC (soft target is ceil(queue / in-flight
    # cap), clamped here; reference: pipelined lease requests in
    # direct_task_transport.h).
    max_lease_batch: int = 4
    # While ≥1 lease is working a key, extra lease requests are SOFT
    # (granted from idle workers only, never spawning); they escalate to
    # hard — may spawn a worker — once the queue has waited this long.
    lease_escalation_s: float = 1.0
    # Idle leases are returned to the raylet after this grace (single
    # shared reaper; also bounds how long a drained-queue prewarm lease
    # can strand a worker).
    lease_idle_grace_s: float = 0.25
    # Initial worker-pool size per node; workers are also started on demand.
    # -1 = auto (min(num_cpus, 8)). Prestarting matters on TPU hosts: every
    # Python start pays the jax import cost, so cold workers are slow.
    num_initial_workers: int = -1
    # Hard cap on worker processes per node (0 = num_cpus).
    max_workers_per_node: int = 0
    worker_register_timeout_s: float = 30.0

    # --- fault tolerance ---
    task_max_retries: int = 3
    actor_max_restarts: int = 0
    lineage_pinning_enabled: bool = True

    # --- TPU topology ---
    # Logical ICI slice size used by the slice-aware scheduler when packing
    # STRICT_PACK placement groups onto TPU hosts.
    tpu_slice_hosts: int = 1
    tpu_chips_per_host: int = 4

    # --- elastic membership ---
    # Graceful drain budget: a DRAINING raylet keeps serving its
    # in-flight leases and migrating plasma objects to survivors for at
    # most this long; whatever is still running at the deadline is
    # reclaimed through the normal typed lease machinery (exactly the
    # crash path, but scoped to the leftovers).
    drain_deadline_s: float = 30.0
    # Compressed-drain budget on a preemption notice (TPU spot gives
    # seconds, not minutes): actor/gang checkpoints run first, object
    # migration is best-effort inside whatever remains of this window.
    preempt_drain_deadline_s: float = 5.0
    # Cap on concurrent object migrations pushed off a draining node
    # (each is a striped pull on the survivor; bounding it keeps the
    # bulk channel from thundering-herding the survivors).
    drain_migrate_concurrency: int = 4
    # Grace past the drain deadline before the GCS heartbeat checker may
    # declare a DRAINING node DEAD (covers the final migrate/ack RTT).
    drain_grace_s: float = 5.0

    # --- training ---
    # Batches each train worker keeps in flight against its DatasetShard
    # ingest actor (train/ingest.py). 2 = double buffering: the next
    # batch transfers over the bulk channel while the current step
    # computes, so a healthy pipeline shows train.ingest_wait_s p50 ~ 0.
    train_ingest_prefetch_depth: int = 2

    # --- rpc ---
    rpc_connect_timeout_s: float = 10.0
    rpc_call_timeout_s: float = 0.0  # 0 = no timeout
    # Address this node advertises to peers (GCS/raylet/worker servers).
    # The default keeps everything loopback-only (single machine); the
    # cluster launcher sets each host's reachable IP, which also flips
    # the listeners to 0.0.0.0 (reference: ray start --node-ip-address).
    node_ip_address: str = "127.0.0.1"

    @property
    def bind_host(self) -> str:
        return ("127.0.0.1" if self.node_ip_address == "127.0.0.1"
                else "0.0.0.0")

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @classmethod
    def load(cls, overrides: dict[str, Any] | None = None) -> "Config":
        cfg = cls()
        env = os.environ.get(_ENV_VAR)
        merged: dict[str, Any] = {}
        if env:
            merged.update(json.loads(env))
        if overrides:
            merged.update(overrides)
        # Dedicated env toggles (checked only when the JSON/overrides did
        # not already pin the knob, so _system_config stays authoritative).
        if "gcs_shards" not in merged and os.environ.get("RAY_TPU_GCS_SHARDS"):
            merged["gcs_shards"] = int(os.environ["RAY_TPU_GCS_SHARDS"])
        known = {f.name for f in dataclasses.fields(cls)}
        for key, value in merged.items():
            if key not in known:
                raise ValueError(f"Unknown system config key: {key}")
            setattr(cfg, key, value)
        return cfg

    def child_env(self) -> dict[str, str]:
        """Env vars to propagate this config to spawned processes."""
        return {_ENV_VAR: self.to_json()}


_global_config: Config | None = None


def get_config() -> Config:
    global _global_config
    if _global_config is None:
        _global_config = Config.load()
    return _global_config


def set_config(cfg: Config) -> None:
    global _global_config
    _global_config = cfg
