"""Live cluster state introspection + stall doctor (flight recorder).

Every runtime process class (driver/worker core worker, raylet, GCS
director + store shards, serve controller/proxy/replica actors,
collective groups) exposes a cheap `debug_state()` snapshot of its
in-flight work — per-task stage with age, lease tables, transfer
streams/pins, collective ops with phase, rpc conn depth, event-loop lag
— plus a `debug_stacks()` all-thread Python stack dump (via
`sys._current_frames`, the `py-spy dump` analog with no ptrace).
Snapshots aggregate over the existing rpc/GCS plane into
`api.cluster_state()`, the dashboard `/api/state` endpoint, and the
`ray-tpu state|stack|doctor` CLI (reference analog: the reference
raylet's DebugString() dumps + the Ray state API,
python/ray/util/state).

The **stall doctor** (`diagnose`) cross-references live state against
the per-hop latency histograms the cluster already records (PR 6):
anything whose age exceeds max(floor, K×p99) for its stage is flagged
with its trace id and owning process, so a wedged cluster answers
"which in-flight thing is stuck, where, and on what stack" without a
reproduction run. Findings also flow as deduped WARNING events through
_private/events.py so `/api/events` surfaces stalls without polling.

Wire discipline: snapshots travel over the msgpack rpc layer — only
str/int/float/bool/bytes/list/dict, ids hex-encoded, never sets.
"""

from __future__ import annotations

import os
import sys
import threading
import time
import traceback

from ray_tpu._private import stats as _stats

# Satellite gauges (ARCHITECTURE.md metrics-registry table; tier-1 drift
# gate): sampled event-loop responsiveness per process, and the cost of
# the last debug_state collection — the doctor's own overhead must be
# observable through the same plane it reads.
M_LOOP_LAG = _stats.Gauge(
    "proc.event_loop_lag_s",
    "sampled event-loop lag: scheduled-wakeup overshoot of the process's "
    "main asyncio loop (a wedged/overloaded loop reads as a rising lag)")
M_STATE_COLLECT = _stats.Gauge(
    "debug.state_collect_s",
    "wall time of this process's last debug_state() collection")

# Default doctor knobs (api.doctor accepts overrides; env for the CLI).
DOCTOR_FLOOR_S = float(os.environ.get("RAY_TPU_DOCTOR_FLOOR_S", "1.0"))
DOCTOR_P99_FACTOR = float(os.environ.get("RAY_TPU_DOCTOR_P99_K", "3.0"))
# compile-storm finding: >= this many jit compiles within the last 60s
# (with >= floor_s of wall time behind them) flags the process
COMPILE_STORM_MIN = int(os.environ.get("RAY_TPU_DOCTOR_COMPILE_STORM_MIN",
                                       "4"))
# prefix_cold finding: an engine whose prefix tree has nodes and at
# least this many lookups but ZERO hits flags mis-aligned page hashing
PREFIX_COLD_MIN_LOOKUPS = int(os.environ.get(
    "RAY_TPU_DOCTOR_PREFIX_COLD_MIN", "32"))

# stage -> latency histogram whose p99 scales the stall threshold (the
# PR 6 per-hop histograms; stages with no histogram gate on the floor)
STAGE_HISTOGRAMS = {
    "lease_wait": "core.task_lease_wait_s",
    "queued": "core.task_queue_wait_s",
    "executing": "core.task_e2e_s",
    "exec": "core.task_exec_s",
    "raylet_queue": "raylet.lease_grant_s",
    "router_queue": "serve.router_queue_s",
    "decode_step": "serve.decode_step_s",
}


# ---------------------------------------------------------------------------
# per-process primitives
# ---------------------------------------------------------------------------


def start_loop_lag_monitor(interval: float = 0.5):
    """Start the sampled event-loop lag gauge on the CURRENT running
    loop (idempotent per loop). Schedules a callback `interval` ahead
    and records how late it actually ran — a busy or wedged loop shows
    up as lag without any per-callback instrumentation."""
    import asyncio

    loop = asyncio.get_running_loop()
    if getattr(loop, "_ray_tpu_lag_monitor", False):
        return
    loop._ray_tpu_lag_monitor = True

    def _tick(expected: float):
        M_LOOP_LAG.set(max(0.0, loop.time() - expected))
        if not loop.is_closed():
            loop.call_later(interval, _tick, loop.time() + interval)

    loop.call_later(interval, _tick, loop.time() + interval)


def collect_stacks() -> dict:
    """All-thread Python stacks of THIS process (sys._current_frames).
    Cheap and lock-free; the returned dict is msgpack-safe."""
    frames = sys._current_frames()
    names = {t.ident: t for t in threading.enumerate()}
    threads = []
    for tid, frame in frames.items():
        t = names.get(tid)
        threads.append({
            "thread_id": tid,
            "name": t.name if t is not None else f"tid-{tid}",
            "daemon": bool(t.daemon) if t is not None else False,
            "stack": "".join(traceback.format_stack(frame)),
        })
    threads.sort(key=lambda r: r["name"])
    return {"pid": os.getpid(), "threads": threads,
            "collected_at": time.time()}


def finish_snapshot(snap: dict, t_start: float) -> dict:
    """Stamp shared trailer fields + the collection-latency gauge."""
    dt = time.monotonic() - t_start
    M_STATE_COLLECT.set(dt)
    snap["pid"] = os.getpid()
    snap["collected_at"] = time.time()
    snap["collect_s"] = dt
    snap["event_loop_lag_s"] = M_LOOP_LAG.snapshot()["value"]
    return snap


def conn_depth(conn) -> int:
    """In-flight request count on one rpc.Connection (0 for anything
    else — ReconnectingConnection exposes its live conn)."""
    inner = getattr(conn, "_conn", conn)
    pending = getattr(inner, "_pending", None)
    return len(pending) if pending is not None else 0


def bounded(obj, max_items: int = 40, max_str: int = 4000, depth: int = 6):
    """Truncate a snapshot for attachment to a raised error: hangs must
    become self-describing without shipping megabytes inside exceptions."""
    if depth <= 0:
        return "..."
    if isinstance(obj, dict):
        out = {}
        for i, (k, v) in enumerate(obj.items()):
            if i >= max_items:
                out["..."] = f"(+{len(obj) - max_items} more)"
                break
            out[k] = bounded(v, max_items, max_str, depth - 1)
        return out
    if isinstance(obj, (list, tuple)):
        out = [bounded(v, max_items, max_str, depth - 1)
               for v in obj[:max_items]]
        if len(obj) > max_items:
            out.append(f"(+{len(obj) - max_items} more)")
        return out
    if isinstance(obj, str) and len(obj) > max_str:
        return obj[:max_str] + "...(truncated)"
    if isinstance(obj, bytes):
        return obj[:32].hex() + ("..." if len(obj) > 32 else "")
    return obj


# ---------------------------------------------------------------------------
# cluster-wide collection (shared by the driver API and the CLI)
# ---------------------------------------------------------------------------


async def collect_cluster_state_async(gcs_call, peer_dial, *,
                                      include_workers: bool = True,
                                      timeout: float = 5.0) -> dict:
    """Aggregate debug_state across the cluster over the existing rpc
    plane. `gcs_call(method, data)` awaits a GCS director call;
    `peer_dial(address)` awaits a connected rpc.Connection to a raylet.
    Unreachable components degrade to an {"error": ...} entry — a
    snapshot of a sick cluster must never hang on the sick part."""
    import asyncio

    out = {"collected_at": time.time(), "nodes": {}}
    try:
        out["gcs"] = await asyncio.wait_for(
            gcs_call("debug_state", {}), timeout)
    except Exception as e:
        out["gcs"] = {"error": f"{type(e).__name__}: {e}"}
    try:
        nodes = await asyncio.wait_for(gcs_call("get_all_nodes", {}),
                                       timeout)
    except Exception as e:
        out["nodes_error"] = f"{type(e).__name__}: {e}"
        return out

    async def one(n):
        nid = n["node_id"].hex()[:8]
        try:
            conn = await asyncio.wait_for(peer_dial(n["address"]), timeout)
            state = await asyncio.wait_for(
                conn.call("debug_state",
                          {"include_workers": include_workers}), timeout)
            return nid, state
        except Exception as e:
            return nid, {"error": f"{type(e).__name__}: {e}",
                         "address": n["address"]}

    got = await asyncio.gather(*(one(n) for n in nodes))
    out["nodes"] = dict(got)
    return out


def collect_via_rpc(gcs_address: str, *, include_workers: bool = True,
                    timeout: float = 5.0) -> dict:
    """Blocking cluster_state collection for out-of-process callers (the
    CLI): dials the GCS directly, no driver runtime required."""
    import asyncio

    from ray_tpu._private import rpc

    async def _go():
        gcs = await rpc.connect(gcs_address, name="state-cli", timeout=5)
        peers = {}
        try:
            async def gcs_call(method, data):
                return await gcs.call(method, data, timeout=timeout)

            async def peer_dial(address):
                conn = peers.get(address)
                if conn is None or conn.closed:
                    conn = peers[address] = await rpc.connect(
                        address, name="state-cli")
                return conn

            return await collect_cluster_state_async(
                gcs_call, peer_dial, include_workers=include_workers,
                timeout=timeout)
        finally:
            for conn in peers.values():
                await conn.close()
            await gcs.close()

    return asyncio.run(_go())


# ---------------------------------------------------------------------------
# flattening (the `ray-tpu state <component>` tables)
# ---------------------------------------------------------------------------

COMPONENTS = ("serve", "placement", "tasks", "actors", "objects",
              "leases", "transfers",
              "collectives")


def iter_processes(snapshot: dict):
    """Yield (component_label, process_state) for every process-level
    snapshot inside a cluster_state() result."""
    if isinstance(snapshot.get("driver"), dict):
        yield "driver", snapshot["driver"]
    gcs = snapshot.get("gcs")
    if isinstance(gcs, dict):
        yield "gcs", gcs
        for idx, shard in enumerate(gcs.get("shards") or []):
            if isinstance(shard, dict):
                yield f"gcs-shard{idx}", shard
    for nid, node in (snapshot.get("nodes") or {}).items():
        if not isinstance(node, dict):
            continue
        yield f"{nid}/raylet", node
        for wid, w in (node.get("workers") or {}).items():
            if isinstance(w, dict):
                yield f"{nid}/worker-{w.get('pid', wid)}", w
        for did, d in (node.get("drivers") or {}).items():
            if isinstance(d, dict):
                yield f"{nid}/driver-{d.get('pid', did)}", d


def flatten(snapshot: dict, component: str) -> list[dict]:
    """Flat per-item rows for one component class across every process
    in a cluster_state() snapshot."""
    if component not in COMPONENTS:
        raise ValueError(f"unknown component {component!r} "
                         f"(expected one of {COMPONENTS})")
    rows: list[dict] = []
    for label, proc in iter_processes(snapshot):
        if component == "tasks":
            for t in proc.get("tasks") or []:
                rows.append({"process": label, **t})
            for t in proc.get("executing") or []:
                rows.append({"process": label, "stage": "exec", **t})
        elif component == "actors":
            for a in proc.get("actors") or []:
                rows.append({"process": label, **a})
        elif component == "objects":
            om = proc.get("objects")
            if om:
                rows.append({"process": label, **om})
        elif component == "leases":
            for l in proc.get("leases") or []:
                rows.append({"process": label, **l})
            for l in proc.get("pending_leases") or []:
                rows.append({"process": label, "stage": "raylet_queue",
                             **l})
        elif component == "transfers":
            tr = proc.get("transfers")
            for kind in ("pulls", "serves"):
                for t in (tr or {}).get(kind) or []:
                    rows.append({"process": label, "kind": kind[:-1], **t})
            if tr and tr.get("pins"):
                rows.append({"process": label, "kind": "pins",
                             "pins": tr["pins"]})
        elif component == "collectives":
            for g in proc.get("collectives") or []:
                rows.append({"process": label, **g})
        elif component == "placement":
            # per-pg bundle->node rows with topology coords and the
            # chosen strategy/cost-model (GCS placement_table)
            for row in proc.get("placement_table") or []:
                rows.append({"process": label, **row})
        elif component == "serve":
            # per-router admission rows: queue depth vs bound, shed and
            # admitted totals (shed RATE comes from the metrics history;
            # these are the live instantaneous truth)
            for r in proc.get("routers") or []:
                rows.append({
                    "process": label, "kind": "router",
                    "endpoint": r.get("endpoint"),
                    "queued": r.get("queued"),
                    "max_queued": r.get("max_queued"),
                    "shed_total": r.get("shed_total"),
                    "admitted_total": r.get("admitted_total"),
                    "streams_open": r.get("streams_open"),
                    "sessions": r.get("sessions"),
                    "age_s": r.get("oldest_age_s"),
                    "inflight": r.get("inflight_batches"),
                })
            comp = proc.get("component")
            if isinstance(comp, dict) and comp.get("kind", "").startswith(
                    "serve-"):
                row = {"process": label, "kind": comp.get("kind"),
                       **{k: v for k, v in comp.items()
                          if k not in ("kind", "engine")}}
                eng = comp.get("engine")
                if isinstance(eng, dict):
                    # decode-engine occupancy: batch fill, stream
                    # backlog, per-session page counts, leak report —
                    # the `ray-tpu state serve` streaming-tier rows
                    row.update({
                        "decode_batch": f"{eng.get('decode_batch')}"
                                        f"/{eng.get('max_decode_batch')}",
                        "waiting": eng.get("waiting"),
                        "steps": eng.get("steps"),
                        "open_streams": eng.get("open_streams"),
                        "stream_backlog": eng.get("stream_backlog"),
                        "kv_pages": f"{(eng.get('kv') or {}).get('pages_in_use')}"
                                    f"/{(eng.get('kv') or {}).get('pages_total')}",
                        "sessions": eng.get("sessions"),
                        "age_s": eng.get("stall_age_s"),
                        "kv_leaked": eng.get("kv_leaked") or "",
                        "engine_dead": eng.get("dead") or "",
                    })
                    pref = (eng.get("kv") or {}).get("prefix") or {}
                    if pref.get("enabled"):
                        # prefix-tree occupancy: node fill, pages held
                        # by >1 owner, and the adoption hit-rate — the
                        # KV-economy health row
                        kv = eng.get("kv") or {}
                        row.update({
                            "prefix_nodes": f"{pref.get('nodes')}"
                                            f"/{pref.get('max_nodes')}",
                            "kv_shared": kv.get("pages_shared"),
                            "kv_cached": kv.get("pages_cached"),
                            "prefix_hit_rate": pref.get("hit_rate"),
                        })
                rows.append(row)
    rows.sort(key=lambda r: -float(r.get("age_s") or 0.0))
    return rows


# ---------------------------------------------------------------------------
# the stall doctor
# ---------------------------------------------------------------------------


def _merged_p99(metrics: dict,
                exemplars: dict | None = None) -> dict[str, float]:
    """p99 per histogram name, merged across every process snapshot in a
    cluster_metrics() result (raylets already fold worker snapshots in).
    With `exemplars` (a dict to fill), also merges each histogram's
    best p99 exemplar — the trace id a finding can print when the live
    item itself is untraced."""
    merged: dict[str, dict] = {}

    def fold(snap):
        for name, m in (snap or {}).items():
            if not isinstance(m, dict) or m.get("type") != "histogram":
                continue
            if exemplars is not None and m.get("exemplars"):
                ex = _stats.quantile_exemplar(m, 0.99)
                cur_ex = exemplars.get(name)
                if ex is not None and (cur_ex is None
                                       or ex["value"] >= cur_ex["value"]):
                    exemplars[name] = ex
            cur = merged.get(name)
            if cur is None:
                merged[name] = {"boundaries": m.get("boundaries") or [],
                                "counts": list(m.get("counts") or []),
                                "count": m.get("count", 0)}
            elif cur["boundaries"] == (m.get("boundaries") or []):
                cur["counts"] = [a + b for a, b in
                                 zip(cur["counts"], m.get("counts") or [])]
                cur["count"] += m.get("count", 0)

    fold(metrics.get("gcs"))
    # "driver": the calling process's own registry (api.doctor adds it —
    # the submit-side task histograms live in the OWNER process, so
    # without this fold the lease_wait/queued/executing thresholds would
    # never see their stage's p99). Raylet snapshots already fold their
    # workers' and connected drivers' registries in.
    fold(metrics.get("driver"))
    for snap in (metrics.get("raylets") or {}).values():
        fold(snap)
    return {name: _stats.percentile(m, 0.99) for name, m in merged.items()}


def _threshold(stage: str, p99s: dict, floor_s: float, k: float) -> float:
    hist = STAGE_HISTOGRAMS.get(stage)
    p99 = p99s.get(hist, 0.0) if hist else 0.0
    return max(floor_s, k * p99)


def diagnose(snapshot: dict, metrics: dict | None = None, *,
             floor_s: float = None, p99_factor: float = None) -> list[dict]:
    """Cross-reference a cluster_state() snapshot against the per-hop
    latency histograms: every in-flight item whose age exceeds
    max(floor, K×p99-of-its-stage) becomes a finding naming its stage,
    age, owning process and (when traced) trace id. Pure function — no
    IO, so it runs identically in the driver, the CLI, and tests."""
    floor_s = DOCTOR_FLOOR_S if floor_s is None else float(floor_s)
    k = DOCTOR_P99_FACTOR if p99_factor is None else float(p99_factor)
    exemplars: dict[str, dict] = {}
    p99s = _merged_p99(metrics or {}, exemplars)
    findings: list[dict] = []

    def flag(kind, proc, stage, age, item, detail=""):
        if age is None:
            return
        limit = _threshold(stage, p99s, floor_s, k)
        if age <= limit:
            return
        trace_id = item.get("trace_id") or ""
        trace_source = "item" if trace_id else ""
        if not trace_id:
            # untraced item: fall back to the stage histogram's p99
            # EXEMPLAR — one real outlier of the same stage whose span
            # tree `ray-tpu trace --trace-id` resolves
            hist = STAGE_HISTOGRAMS.get(stage)
            ex = exemplars.get(hist) if hist else None
            if ex is not None:
                trace_id, trace_source = ex["trace_id"], "exemplar"
        findings.append({
            "kind": kind,
            "process": proc,
            "stage": stage,
            "age_s": round(float(age), 3),
            "threshold_s": round(limit, 3),
            "trace_id": trace_id,
            "trace_source": trace_source,
            "id": item.get("task_id") or item.get("object_id")
                  or item.get("group") or item.get("lease_id") or "",
            "name": (item.get("name") or item.get("op")
                     or item.get("endpoint") or ""),
            "detail": detail,
        })

    for label, proc in iter_processes(snapshot):
        for t in proc.get("tasks") or []:
            flag("task", label, t.get("stage", "executing"),
                 t.get("age_s"), t,
                 detail=f"lease={t.get('lease_worker', '')}")
        for t in proc.get("executing") or []:
            flag("task", label, "exec", t.get("age_s"), t,
                 detail=f"thread={t.get('thread', '')}")
        for l in proc.get("pending_leases") or []:
            flag("lease", label, "raylet_queue", l.get("age_s"), l)
        for q in proc.get("router_queues") or []:
            flag("query", label, "router_queue", q.get("age_s"), q,
                 detail=f"endpoint={q.get('endpoint', '')}")
        tr = proc.get("transfers") or {}
        for kind in ("pulls", "serves"):
            for t in tr.get(kind) or []:
                flag("transfer", label, "transfer", t.get("age_s"), t,
                     detail=f"{kind[:-1]} {t.get('progress', '')}")
        for g in proc.get("collectives") or []:
            if g.get("op"):
                flag("collective", label, "collective", g.get("age_s"), g,
                     detail=f"phase={g.get('phase', '')} "
                            f"rank={g.get('rank')}")
        comp = proc.get("component")
        eng = comp.get("engine") if isinstance(comp, dict) else None
        if isinstance(eng, dict) and eng.get("stall_age_s") is not None \
                and not eng.get("dead"):
            # a decode engine with running sequences whose last step
            # age exceeds the decode-stage threshold is a WEDGED decode
            # loop (stuck allreduce, dead follower the leader hasn't
            # typed yet) — the stall doctor's streaming-tier finding
            flag("decode", label, "decode_step", eng.get("stall_age_s"),
                 {"name": eng.get("backend")},
                 detail=f"batch={eng.get('decode_batch')} "
                        f"open_streams={eng.get('open_streams')} "
                        f"steps={eng.get('steps')}")
        pref = ((eng.get("kv") or {}).get("prefix") or {}) \
            if isinstance(eng, dict) else {}
        if (pref.get("enabled") and pref.get("nodes", 0) > 0
                and pref.get("lookups", 0) >= PREFIX_COLD_MIN_LOOKUPS
                and pref.get("hits", 0) == 0):
            # prefix_cold: the tree holds indexed pages and plenty of
            # admissions walked it, yet NOTHING ever matched — the
            # classic symptom of mis-aligned page hashing (router and
            # engine disagree on kv_page_size, or prompts are tokenized
            # differently per session so no page boundary ever lines
            # up). A hot shared prefix is paying full prefill N times.
            # Age-less (a property of the workload, not a stall).
            findings.append({
                "kind": "prefix_cold",
                "process": label,
                "stage": "kv_prefix",
                "age_s": 0.0,
                "threshold_s": 0.0,
                "trace_id": "",
                "trace_source": "",
                "id": "",
                "name": (eng.get("backend", "")
                         if isinstance(eng, dict) else ""),
                "detail": (f"{pref['lookups']} prefix lookups with 0 "
                           f"hits despite {pref['nodes']} indexed "
                           f"nodes: likely mis-aligned page hashing "
                           f"(page-size mismatch or non-page-aligned "
                           f"shared prefix)"),
            })
        compiles = proc.get("jax_compiles")
        if (isinstance(compiles, dict)
                and compiles.get("recent_60s", 0) >= COMPILE_STORM_MIN
                and compiles.get("recent_s", 0.0) >= floor_s):
            # recompile storm: many compile events in the last minute
            # with real wall time behind them — a shape-churning loader
            # or a cache-thrashing collective, not a wedged item
            findings.append({
                "kind": "compile_storm",
                "process": label,
                "stage": "compile",
                "age_s": round(float(compiles["recent_s"]), 3),
                "threshold_s": round(floor_s, 3),
                "trace_id": "",
                "trace_source": "",
                "id": "",
                "name": compiles.get("last_key", ""),
                "detail": (f"{compiles['recent_60s']} compiles in 60s "
                           f"({compiles['recent_s']:.1f}s wall, "
                           f"{compiles.get('total', 0)} total)"),
            })
        # topology_mismatch: a CREATED gang whose members span ICI
        # slices — its collectives pay DCN on every op even though a
        # same-slice placement may exist; age-less (a property of the
        # placement, not a stall)
        for pg, rows in _pgs_by_id(proc.get("placement_table")).items():
            slices = {r.get("slice") for r in rows if r.get("slice")}
            if len(slices) > 1:
                findings.append({
                    "kind": "placement_group",
                    "process": label,
                    "stage": "topology_mismatch",
                    "age_s": 0.0,
                    "threshold_s": 0.0,
                    "trace_id": "",
                    "trace_source": "",
                    "id": pg,
                    "name": rows[0].get("name", ""),
                    "detail": (f"gang spans slices "
                               f"{sorted(slices)} "
                               f"(strategy={rows[0].get('strategy')}): "
                               f"collective ops cross DCN"),
                })
    findings.sort(key=lambda f: -f["age_s"])
    return findings


def _pgs_by_id(table) -> dict[str, list[dict]]:
    """Group GCS placement_table bundle rows by pg id (CREATED rows
    only — pending/infeasible rows carry no bundle geometry)."""
    out: dict[str, list[dict]] = {}
    for row in table or []:
        if row.get("state") == "CREATED" and "bundle" in row:
            out.setdefault(row.get("pg", "?"), []).append(row)
    return out


# Doctor findings dedup (satellite: one WARNING event per stalled trace,
# not one per 1s doctor tick). Keyed by trace id when present, else by
# (process, kind, id, name, stage) — name matters because untraced
# pending-lease/router rows carry no id, and collapsing every such row
# on a process into one forever-entry would swallow distinct stalls.
# Entries EXPIRE (STALL_EVENT_TTL_S): a stall still live after the TTL
# re-announces rather than staying silent for the process lifetime.
STALL_EVENT_TTL_S = float(os.environ.get("RAY_TPU_STALL_EVENT_TTL_S",
                                         "300"))
_stall_events_seen: dict = {}  # key -> monotonic ts of last emit
_stall_seen_lock = threading.Lock()


def stall_event_key(finding: dict) -> tuple:
    tid = finding.get("trace_id")
    if tid:
        return ("trace", tid)
    return (finding.get("process"), finding.get("kind"),
            finding.get("id"), finding.get("name"),
            finding.get("stage"))


def novel_findings(findings: list[dict]) -> list[dict]:
    """Filter findings to those not recently reported (dedup + TTL)."""
    out = []
    now = time.monotonic()
    with _stall_seen_lock:
        if len(_stall_events_seen) > 10_000:
            _stall_events_seen.clear()
        for f in findings:
            key = stall_event_key(f)
            last = _stall_events_seen.get(key)
            if last is not None and now - last < STALL_EVENT_TTL_S:
                continue
            _stall_events_seen[key] = now
            out.append(f)
    return out


def reset_stall_dedup():
    with _stall_seen_lock:
        _stall_events_seen.clear()


def make_stall_event(finding: dict) -> dict:
    """Structured WARNING event payload for one doctor finding (ships to
    the GCS events ring via report_event)."""
    from ray_tpu._private import events

    msg = (f"{finding['kind']} {finding.get('name') or finding.get('id')} "
           f"stalled in {finding['stage']} for {finding['age_s']:.1f}s "
           f"(threshold {finding['threshold_s']:.1f}s) on "
           f"{finding['process']}")
    return {
        "timestamp": time.time(),
        "severity": events.WARNING,
        "label": "STALL_DETECTED",
        "message": msg,
        "source_type": "doctor",
        "source_id": finding["process"],
        "source_pid": os.getpid(),
        "custom_fields": {k: v for k, v in finding.items()},
    }


# ---------------------------------------------------------------------------
# final-snapshot hook (conftest leak-check naming) + artifact dumps
# ---------------------------------------------------------------------------

# The most recent cluster snapshot captured at driver shutdown: the
# leak check names orphan processes / leaked pins / unreturned leases
# from it instead of reporting bare pids and paths.
FINAL_SNAPSHOT: dict | None = None


def note_final_snapshot(snap: dict) -> None:
    global FINAL_SNAPSHOT
    FINAL_SNAPSHOT = snap


def dump_artifact(path: str, snapshot: dict, stacks: dict | None = None,
                  reason: str = "") -> str:
    """Write a cluster snapshot (+ local stacks) as a JSON artifact —
    the chaos sweeps call this on deadline overrun so seeded-hang triage
    starts from the flight recording, not a reproduction run."""
    import json

    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    doc = {"reason": reason, "dumped_at": time.time(),
           "snapshot": snapshot, "stacks": stacks or collect_stacks()}
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, default=_json_default)
    return path


def _json_default(obj):
    if isinstance(obj, bytes):
        return obj.hex()
    if isinstance(obj, set):
        return sorted(obj)
    return repr(obj)
