"""Public API (reference: python/ray/worker.py — init :490, get :1369,
put :1446, wait :1475, remote :1741, kill :1597, cancel :1625,
get_actor :1576)."""

from __future__ import annotations

import inspect
from typing import Any, Sequence

from ray_tpu import exceptions as exc
from ray_tpu._private import global_state
from ray_tpu._private.config import Config, set_config
from ray_tpu._private.core_worker import DRIVER, CoreWorker
from ray_tpu._private.ids import ActorID
from ray_tpu._private.node import Node
from ray_tpu.actor import ActorClass, ActorHandle
from ray_tpu.object_ref import ObjectRef
from ray_tpu.remote_function import RemoteFunction

_global_node: Node | None = None


def init(address: str | None = None, *, num_cpus: float | None = None,
         num_tpus: float | None = None, resources: dict | None = None,
         labels: dict | None = None, object_store_memory: int | None = None,
         _system_config: dict | None = None, ignore_reinit_error=False,
         **kwargs) -> dict:
    """Start (or connect to) a cluster and connect this process as driver.

    address=None starts a new local head node; address="<gcs host:port>"
    connects to an existing cluster (e.g. one made by cluster_utils.Cluster
    or `ray-tpu start`); address="auto" finds one via RAY_TPU_ADDRESS.
    """
    global _global_node
    if global_state.get_core_worker() is not None:
        if ignore_reinit_error:
            return connection_info()
        raise RuntimeError("ray_tpu.init() called twice")

    overrides = dict(_system_config or {})
    if object_store_memory is not None:
        overrides["object_store_memory"] = object_store_memory
    config = Config.load(overrides)
    set_config(config)

    if address == "auto":
        import os

        address = os.environ.get("RAY_TPU_ADDRESS")
        if not address:
            raise ConnectionError(
                "address='auto' but RAY_TPU_ADDRESS is not set")

    if address is None:
        if num_tpus is None:
            num_tpus = _detect_tpu_chips()
        _global_node = Node(config=config, num_cpus=num_cpus,
                            num_tpus=num_tpus, resources=resources,
                            labels=labels)
        raylet_address = _global_node.raylet_address
        gcs_address = _global_node.gcs_address
        session_dir = _global_node.session_dir
        store_root = _global_node.store_root
    else:
        # Connect as a driver to an existing cluster: ask the GCS for a node
        # on this host (round-1: pick the first).
        gcs_address = address
        import asyncio

        from ray_tpu._private import rpc as _rpc

        async def _find():
            conn = await _rpc.connect(gcs_address, name="probe")
            nodes = await conn.call("get_all_nodes", {})
            await conn.close()
            return nodes

        nodes = asyncio.run(_find())
        if not nodes:
            raise ConnectionError(f"no alive nodes in cluster at {address}")
        head = next((n for n in nodes if n.get("is_head")), nodes[0])
        raylet_address = head["address"]
        import os

        # Attach to the raylet's own session/store when it's on this host
        # (the `ray-tpu start` two-shell flow): shared-memory objects are
        # then zero-copy between driver and workers.
        async def _info():
            conn = await _rpc.connect(raylet_address, name="probe")
            info = await conn.call("cluster_info", {})
            await conn.close()
            return info

        try:
            info = asyncio.run(_info())
        except Exception:
            info = {}
        session_dir = kwargs.get("session_dir") or info.get("session_dir")
        store_root = kwargs.get("store_root") or info.get("store_root")
        if not (session_dir and os.path.isdir(session_dir)):
            session_dir = "/tmp/ray_tpu/attached"
        os.makedirs(session_dir, exist_ok=True)
        if not (store_root and os.path.isdir(store_root)):
            store_root = os.path.join(session_dir, "driver_store")

    CoreWorker(
        mode=DRIVER,
        raylet_address=raylet_address,
        gcs_address=gcs_address,
        session_dir=session_dir,
        store_root=store_root,
        config=config,
    )
    return connection_info()


def _detect_tpu_chips() -> float:
    """TPU chips this node offers as the ``TPU`` resource. Counted from
    what the machine exposes, WITHOUT initialising JAX (a backend claim
    here would take the chip for the driver, and the chip belongs to the
    TPU-flavour worker). ``RAY_TPU_NUM_CHIPS`` overrides the count (a
    declared resource: the test tree, CPU rehearsals); an explicit
    ``num_tpus=`` overrides both."""
    import os

    from ray_tpu._private.accelerator import count_tpu_chips

    if os.environ.get("RAY_TPU_NUM_CHIPS"):
        return float(os.environ["RAY_TPU_NUM_CHIPS"])
    return float(count_tpu_chips())


def connection_info() -> dict:
    cw = global_state.require_core_worker()
    return {
        "gcs_address": _global_node.gcs_address if _global_node else "",
        "raylet_address": cw.raylet.name if cw.raylet else "",
        "session_dir": cw.session_dir,
        "node_id": cw.node_id.hex() if cw.node_id else "",
    }


def is_initialized() -> bool:
    return global_state.get_core_worker() is not None


def shutdown():
    global _global_node, _doctor_metrics_cache
    # disarm the doctor loop FIRST: a surviving tick would spin against
    # the dead runtime and silently re-attach to any later init() with
    # this session's stale cache/dedup state
    stop_doctor()
    _doctor_metrics_cache = None
    from ray_tpu._private import debug_state as _ds

    _ds.reset_stall_dedup()
    from ray_tpu._private import sampling_profiler as _sprof

    _sprof.stop()
    cw = global_state.get_core_worker()
    if cw is not None:
        cw.shutdown()
    if _global_node is not None:
        _global_node.kill_all_processes()
        _global_node = None


def timeline(filename: str | None = None) -> list[dict]:
    """Chrome-trace timeline of recorded cluster profile events
    (reference: python/ray/state.py:946 timeline(); load the output in
    chrome://tracing or Perfetto). Spans are flushed from workers within
    ~2s of recording (sooner after task completion) — a timeline taken
    immediately after a very short run may lag a moment behind."""
    import json

    from ray_tpu._private.profiling import to_chrome_trace

    cw = global_state.require_core_worker()
    trace = to_chrome_trace(cw.get_profile_events())
    if filename:
        with open(filename, "w") as f:
            json.dump(trace, f)
    return trace


def cluster_events(severity: str | None = None) -> list[dict]:
    """Structured cluster events (node joins/removals, actor deaths,
    worker crashes — the RAY_EVENT analog; reference: src/ray/util/
    event.h + the dashboard event view)."""
    return global_state.require_core_worker().get_cluster_events(severity)


def cluster_metrics(history: int | None = None) -> dict:
    """Metric snapshots from the GCS and every raylet (reference:
    src/ray/stats/metric.h export surface).

    With `history=N`, returns the GCS metrics time-series instead:
    `{source: {metric: [[ts, value], ...]}}` with up to the last N
    timestamped samples per metric (N<=0 for the full retained ring).
    Sources are `<node>/raylet` (heartbeat-piggybacked) and
    `<node>/<mode>-<pid>` per worker/driver (pushed on the ~2s profile
    flush cadence); histograms appear as `.count`/`.sum`/`.p99` scalar
    series — the serve autoscaler's feed."""
    cw = global_state.require_core_worker()
    if history is not None:
        return cw.get_metrics_history(samples=history)
    return cw.get_cluster_metrics()


def cluster_state(component: str | None = None,
                  filters: dict | None = None, *,
                  include_workers: bool = True,
                  timeout: float = 5.0):
    """Live cluster-wide introspection snapshot (the flight recorder;
    debug_state.py): every process class — driver, GCS director +
    shards, each raylet and its workers (serve actors and collective
    groups included) — answers a cheap `debug_state()` of its in-flight
    work: per-task stage with age, lease tables, transfer streams/pins,
    collective op phases, rpc conn depth, event-loop lag.

    With `component` (one of serve|tasks|actors|objects|leases|
    transfers|collectives) returns flat rows across every process,
    sorted oldest first (`serve`: per-router queue depth vs admission
    bound, shed/admitted totals, replica-group/controller state);
    `filters={"field": substring}` narrows them. Unreachable
    components degrade to an {"error": ...} entry — asking a sick
    cluster what is wrong must never hang on the sick part."""
    from ray_tpu._private import debug_state

    cw = global_state.require_core_worker()
    snap = cw.get_cluster_state(include_workers=include_workers,
                                timeout=timeout)
    if component is None:
        return snap
    rows = debug_state.flatten(snap, component)
    for key, want in (filters or {}).items():
        rows = [r for r in rows if str(want) in str(r.get(key, ""))]
    return rows


_doctor_metrics_cache: tuple | None = None  # (monotonic_ts, metrics)


def doctor(*, floor_s: float | None = None,
           p99_factor: float | None = None,
           include_stacks: bool = True, emit_events: bool = True,
           timeout: float = 5.0, metrics_max_age_s: float = 10.0) -> dict:
    """The stall doctor: cross-references `cluster_state()` against the
    per-hop latency histograms the cluster already records — any
    in-flight item whose age exceeds max(floor, K×p99-of-its-stage) is
    flagged with its stage, age, trace id and owning process, and (with
    include_stacks) the all-thread stacks of that process. Findings are
    also emitted as deduped STALL_DETECTED warning events into the GCS
    events ring (`/api/events`, `ray-tpu events`) so dashboards surface
    stalls without polling. Knobs: floor_s (default 1s,
    RAY_TPU_DOCTOR_FLOOR_S) and p99_factor (default 3, RAY_TPU_DOCTOR_P99_K)."""
    from ray_tpu._private import debug_state

    global _doctor_metrics_cache
    import time as _time

    cw = global_state.require_core_worker()
    snap = cw.get_cluster_state(timeout=timeout)
    # The p99 thresholds drift on the histogram timescale, not per tick:
    # cache the metrics fan-out so the armed 1s doctor cadence pays ONE
    # cluster sweep per tick (state), not two (the ≤5% microbench gate).
    cache = _doctor_metrics_cache
    if (cache is not None
            and _time.monotonic() - cache[0] < metrics_max_age_s):
        metrics = cache[1]
    else:
        try:
            metrics = cw.get_cluster_metrics()
        except Exception:
            metrics = {}
        # this driver's OWN registry: the submit-side task histograms
        # (lease_wait/queue_wait/e2e) live here, not in any raylet fold
        from ray_tpu._private import stats as _stats

        metrics = dict(metrics)
        metrics["driver"] = _stats.snapshot()
        _doctor_metrics_cache = (_time.monotonic(), metrics)
    findings = debug_state.diagnose(snap, metrics, floor_s=floor_s,
                                    p99_factor=p99_factor)
    if include_stacks and findings:
        addr_of = _process_addresses(snap)
        stacks: dict[str, dict] = {}
        for f in findings:
            label = f["process"]
            if label in stacks or len(stacks) >= 4:
                continue
            try:
                if label == "driver":
                    stacks[label] = cw.get_debug_stacks()
                elif label == "gcs":
                    stacks[label] = cw._io.run(
                        cw.gcs.call("debug_stacks", {}), timeout=timeout)
                elif addr_of.get(label):
                    stacks[label] = cw.get_debug_stacks(addr_of[label])
            except Exception as e:
                stacks[label] = {"error": f"{type(e).__name__}: {e}"}
        for f in findings:
            if f["process"] in stacks:
                f["stacks"] = stacks[f["process"]]
    if emit_events:
        for f in debug_state.novel_findings(findings):
            event = debug_state.make_stall_event(
                {k: v for k, v in f.items() if k != "stacks"})
            try:
                cw._io.run(cw.gcs.notify("report_event", event),
                           timeout=2.0)
            except Exception:
                pass
    return {"findings": findings,
            "collected_at": snap.get("collected_at"),
            "processes": sum(
                1 for _ in debug_state_iter_processes(snap))}


def debug_state_iter_processes(snap):
    from ray_tpu._private import debug_state

    return debug_state.iter_processes(snap)


def _process_addresses(snap: dict) -> dict[str, str]:
    """process label (as in doctor findings) -> rpc address."""
    from ray_tpu._private import debug_state

    out = {}
    for label, proc in debug_state.iter_processes(snap):
        addr = proc.get("address")
        if addr:
            out[label] = addr
    return out


_doctor_loop = None


def start_doctor(interval: float = 1.0, **knobs) -> None:
    """Arm a background doctor tick in this driver: every `interval`
    seconds, collect cluster_state + diagnose + emit stall events (the
    cadence the microbench regression gate runs at). Idempotent;
    stop_doctor() disarms."""
    import threading

    global _doctor_loop
    if _doctor_loop is not None:
        return
    stop = threading.Event()

    def _loop():
        while not stop.wait(interval):
            try:
                doctor(include_stacks=False, **knobs)
            except Exception:
                pass

    t = threading.Thread(target=_loop, name="stall-doctor", daemon=True)
    t.start()
    _doctor_loop = (t, stop)


def stop_doctor() -> None:
    global _doctor_loop
    if _doctor_loop is not None:
        _doctor_loop[1].set()
        _doctor_loop = None


def debug_stacks(address: str | None = None) -> dict:
    """All-thread Python stacks of this driver, or of any live runtime
    process by rpc address (`sys._current_frames` over rpc — the
    `ray-tpu stack` surface)."""
    return global_state.require_core_worker().get_debug_stacks(address)


def trace_spans(trace_id: str | None = None) -> list[dict]:
    """Flat span rows from the GCS trace table (tracing.py), optionally
    filtered to one trace (hex trace id). Each row carries the emitting
    process (`component_type`/`component_id`/`node_id`) and the span's
    `tid`/`sid`/`psid` linkage in `extra_data`."""
    return global_state.require_core_worker().get_trace_spans(trace_id)


def profile(seconds: float | None = 2.0, component: str | None = None,
            out: str | None = None) -> dict:
    """Cluster-wide CPU flamegraph off the continuous profiling plane
    (sampling_profiler.py): every process class (driver, workers,
    raylets, GCS director + shards) runs an always-on ~67 Hz wall-clock
    sampler whose collapsed stacks flush to the GCS profile ring on the
    ~2 s profile cadence.

    With `seconds=N` collects a fresh window: waits N seconds (plus up
    to one flush cadence for the tail) and returns the sampler windows
    OVERLAPPING it — a ~2s flush window already open when collection
    starts is included whole, so a short collection may carry up to one
    cadence of immediately-preceding stacks. `seconds=None` returns
    everything the ring holds.
    `component` filters to one process class (driver|worker|raylet|
    gcs|gcs-shard); `out` also writes the collapsed text to a file.

    Returns {"collapsed": str, "components": [...], "samples": int,
    "batches": [...]} — `collapsed` is Brendan-Gregg collapsed-stack
    text (one `component;thread;frame;... count` line per stack; feed
    it to flamegraph.pl / speedscope), `batches` the raw ring rows
    (sampling_profiler.samples_to_chrome_trace renders them as merged
    Perfetto tracks)."""
    import time as _time

    from ray_tpu._private import sampling_profiler as _sprof

    cw = global_state.require_core_worker()
    if seconds is not None:
        since = _time.time()
        _time.sleep(max(0.0, float(seconds)))
        batches = _sprof.wait_for_coverage(
            lambda: cw.get_profile_samples(since=since,
                                           component=component),
            component)
    else:
        batches = cw.get_profile_samples(component=component)
    collapsed = _sprof.collapse_text(batches)
    if out:
        with open(out, "w") as f:
            f.write(collapsed + ("\n" if collapsed else ""))
    return {
        "collapsed": collapsed,
        "components": _sprof.components_of(batches),
        "samples": sum(b.get("samples", 0) for b in batches),
        "batches": batches,
    }


def set_profiling(hz: float) -> None:
    """Arm/re-rate the continuous profiler cluster-wide, live: every
    process's sampler thread flips to `hz` samples/s (0 stops it; the
    default is RAY_TPU_PROFILE_HZ, ~67). Rides the internal KV + pubsub
    plane exactly like failpoint arming and trace-sampling overrides,
    so running processes and any spawned later both honor it."""
    from ray_tpu._private import sampling_profiler as _sprof

    hz = min(_sprof.MAX_HZ, max(0.0, float(hz)))
    cw = global_state.require_core_worker()
    cw.kv_put(_sprof.KV_KEY, repr(hz).encode())
    _sprof.apply_kv_value(repr(hz))  # local apply; push also lands


def set_trace_sampling(rate: float) -> None:
    """Set the head-sampling rate for distributed tracing cluster-wide,
    live (0.0 disables new roots, 1.0 traces everything; default is
    `RAY_TPU_TRACE_SAMPLE`, ~1%). Rides the internal KV + pubsub plane,
    so every connected process — and any spawned later — picks it up."""
    from ray_tpu._private import tracing

    rate = min(1.0, max(0.0, float(rate)))
    cw = global_state.require_core_worker()
    cw.kv_put(tracing.KV_KEY, repr(rate).encode())
    tracing.set_sample_rate(rate)  # local apply; push also lands


def remote(*args, **kwargs):
    """@remote decorator for functions and classes, with or without options:

        @ray_tpu.remote
        def f(): ...

        @ray_tpu.remote(num_tpus=1, max_restarts=3)
        class A: ...
    """
    if len(args) == 1 and not kwargs and callable(args[0]):
        return _make_remote(args[0], {})
    if args:
        raise TypeError("@remote takes keyword options only")

    def decorator(obj):
        return _make_remote(obj, kwargs)

    return decorator


def _make_remote(obj, opts):
    if inspect.isclass(obj):
        allowed = {"num_cpus", "num_tpus", "resources", "max_restarts",
                   "max_concurrency", "accelerator_type"}
        bad = set(opts) - allowed
        if bad:
            raise ValueError(f"unsupported actor options: {bad}")
        return ActorClass(obj, **opts)
    allowed = {"num_cpus", "num_tpus", "resources", "num_returns",
               "max_retries", "accelerator_type"}
    bad = set(opts) - allowed
    if bad:
        raise ValueError(f"unsupported task options: {bad}")
    return RemoteFunction(obj, **opts)


def put(value: Any) -> ObjectRef:
    return global_state.require_core_worker().put(value)


def get(refs, timeout: float | None = None):
    cw = global_state.require_core_worker()
    if isinstance(refs, ObjectRef):
        return cw.get([refs], timeout)[0]
    if not isinstance(refs, (list, tuple)):
        raise TypeError("get() expects an ObjectRef or a list of ObjectRefs")
    for r in refs:
        if not isinstance(r, ObjectRef):
            raise TypeError(f"get() got a non-ObjectRef element: {type(r)}")
    return cw.get(list(refs), timeout)


def wait(refs: Sequence[ObjectRef], *, num_returns: int = 1,
         timeout: float | None = None, fetch_local: bool = True):
    if isinstance(refs, ObjectRef):
        raise TypeError("wait() expects a list of ObjectRefs")
    refs = list(refs)
    if len(set(refs)) != len(refs):
        raise ValueError("wait() got duplicate ObjectRefs")
    if num_returns > len(refs):
        raise ValueError("num_returns exceeds the number of refs")
    cw = global_state.require_core_worker()
    return cw.wait(refs, num_returns=num_returns, timeout=timeout,
                   fetch_local=fetch_local)


def kill(actor: ActorHandle, *, no_restart: bool = True):
    if not isinstance(actor, ActorHandle):
        raise TypeError("kill() expects an ActorHandle; use cancel() for tasks")
    cw = global_state.require_core_worker()
    cw.kill_actor(actor._actor_id.binary(), no_restart=no_restart)


def cancel(ref: ObjectRef, *, force: bool = False, recursive: bool = True):
    cw = global_state.require_core_worker()
    cw.cancel_task(ref, force=force, recursive=recursive)


def get_actor(name: str, namespace: str = "") -> ActorHandle:
    cw = global_state.require_core_worker()
    info = cw.get_named_actor(name, namespace)
    if info is None or info["state"] == "DEAD":
        raise ValueError(f"Failed to look up actor {name!r}")
    from ray_tpu._private.ids import ActorID as _ActorID

    # class fn_id is unknown to late-bound getters; methods resolve by name
    # at call time, so a nil cls id is fine.
    return ActorHandle(_ActorID(info["actor_id"]), b"\x00" * 16,
                       info.get("class_name", "Actor"))


def nodes() -> list[dict]:
    cw = global_state.require_core_worker()
    info = cw.cluster_info()
    return [
        {
            "NodeID": n["node_id"].hex(),
            "Alive": True,
            "Address": n["address"],
            "Resources": {k: v / 10000 for k, v in n["resources"].items()},
            "IsHead": n.get("is_head", False),
            "Labels": n.get("labels", {}),
            "TpuSlice": n.get("tpu_slice"),
        }
        for n in info["nodes"]
    ]


def cluster_resources() -> dict:
    out: dict[str, float] = {}
    for node in nodes():
        for k, v in node["Resources"].items():
            out[k] = out.get(k, 0) + v
    return out


def available_resources() -> dict:
    cw = global_state.require_core_worker()
    info = cw.cluster_info()
    return {k: v / 10000 for k, v in info["available"].items()}
