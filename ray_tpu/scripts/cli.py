"""ray-tpu CLI — out-of-process cluster lifecycle (reference:
python/ray/scripts/scripts.py — `ray start` :439, `ray stop` :582,
`ray status` :1412, `ray memory` :1389, `ray microbenchmark` :1346).

Two-shell flow:
    shell A:  ray-tpu start --head
    shell B:  RAY_TPU_ADDRESS=<printed addr> python my_driver.py
              (driver calls ray_tpu.init(address="auto"))
    shell A:  ray-tpu stop

Cluster bookkeeping lives in <tmpdir>/cluster.json so stop/status/memory
find the processes without arguments."""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import signal
import subprocess
import sys


def _tmpdir() -> str:
    return os.environ.get("RAY_TPU_TMPDIR", "/tmp/ray_tpu")


def _cluster_file() -> str:
    return os.path.join(_tmpdir(), "cluster.json")


def _load_cluster() -> dict | None:
    try:
        with open(_cluster_file()) as f:
            return json.load(f)
    except (FileNotFoundError, json.JSONDecodeError):
        return None


def _save_cluster(rec: dict):
    os.makedirs(_tmpdir(), exist_ok=True)
    tmp = _cluster_file() + ".tmp"
    with open(tmp, "w") as f:
        json.dump(rec, f, indent=1)
    os.rename(tmp, _cluster_file())


def _rpc_call(address: str, method: str, data=None):
    from ray_tpu._private import rpc

    async def _go():
        conn = await rpc.connect(address, name="cli", timeout=5)
        try:
            return await conn.call(method, data or {}, timeout=10)
        finally:
            await conn.close()

    return asyncio.run(_go())


# ---------------------------------------------------------------------------
# start / stop
# ---------------------------------------------------------------------------

def cmd_start(args) -> int:
    from ray_tpu._private.config import Config, set_config
    from ray_tpu._private.node import new_session_dir, start_gcs, start_raylet

    config = Config.load(json.loads(args.system_config)
                         if args.system_config else None)
    set_config(config)
    pids: list[int] = []

    if args.head:
        session_dir = new_session_dir()
        gcs_svc, gcs_address = start_gcs(session_dir, config,
                                         port=args.port or config.gcs_port)
        pids.append(gcs_svc.proc.pid)
    else:
        if not args.address:
            print("error: worker nodes need --address <gcs host:port>",
                  file=sys.stderr)
            return 2
        gcs_address = args.address
        rec = _load_cluster()
        session_dir = (rec or {}).get("session_dir") or new_session_dir()

    raylet_svc, raylet_addr, node_id, _store = start_raylet(
        session_dir, gcs_address, config,
        num_cpus=args.num_cpus, num_tpus=args.num_tpus or 0,
        resources=json.loads(args.resources) if args.resources else None,
        tpu_slice=(json.loads(args.tpu_slice)
                   if getattr(args, "tpu_slice", None) else None),
        is_head=args.head)
    pids.append(raylet_svc.proc.pid)

    client_port = None
    if args.head and args.client_server_port is not None:
        # Ray-Client analog: remote drivers connect here with no local
        # runtime (reference: `ray start --ray-client-server-port`).
        # Spawned like the other services (_spawn: config overrides via
        # child_env, JAX_PLATFORMS=cpu) and health-checked via the
        # ready file, which also reports the actual port for --port 0.
        import uuid as _uuid

        from ray_tpu._private.node import _spawn, _wait_ready

        ready = os.path.join(session_dir,
                             f"client_ready_{_uuid.uuid4().hex[:6]}")
        svc = _spawn([
            sys.executable, "-m", "ray_tpu.util.client.server",
            "--address", gcs_address,
            "--port", str(args.client_server_port),
            "--ready-file", ready,
        ], config, "client_server")
        client_port = int(_wait_ready(ready, svc.proc, "client_server",
                                      timeout=60))
        pids.append(svc.proc.pid)

    rec = _load_cluster() if not args.head else None
    if rec is None:
        rec = {"gcs_address": gcs_address, "session_dir": session_dir,
               "pids": []}
    rec["pids"].extend(pids)
    if client_port is not None:
        rec["client_server_port"] = client_port
    _save_cluster(rec)

    role = "head" if args.head else "worker node"
    print(f"started {role}: node {node_id.hex()[:8]} raylet {raylet_addr}")
    print(f"GCS address: {gcs_address}")
    if client_port is not None:
        print(f"client server port: {client_port} "
              f"(ray_tpu.util.client.connect('<host>:{client_port}'))")
    print(f"session dir: {session_dir}")
    print()
    print("connect a driver with:")
    print(f"    export RAY_TPU_ADDRESS={gcs_address}")
    print("    python -c 'import ray_tpu; ray_tpu.init(address=\"auto\")'")
    return 0


def cmd_stop(args) -> int:
    rec = _load_cluster()
    if rec is None:
        print("no cluster record found; nothing to stop")
        return 0
    from ray_tpu._private.node import (
        ProcessesStillAlive,
        end_process_groups,
        has_left,
    )

    # the recorded pids are the services, each the leader of the group
    # that holds everything it started (a raylet's workers)
    pids = rec.get("pids", [])
    stopped = sum(not has_left(pid) for pid in pids)
    try:
        end_process_groups(pids, signal.SIGTERM, bound=5.0)
    except ProcessesStillAlive:  # user code may hold SIGTERM off
        end_process_groups(pids, signal.SIGKILL)
    try:
        os.unlink(_cluster_file())
    except FileNotFoundError:
        pass
    print(f"stopped {stopped} process group(s)")
    return 0


# ---------------------------------------------------------------------------
# status / memory
# ---------------------------------------------------------------------------

def _gcs_address(args) -> str | None:
    if getattr(args, "address", None):
        return args.address
    if os.environ.get("RAY_TPU_ADDRESS"):
        return os.environ["RAY_TPU_ADDRESS"]
    rec = _load_cluster()
    return rec["gcs_address"] if rec else None


def _fmt_resources(raw: dict) -> str:
    from ray_tpu._private.common import ResourceSet

    d = ResourceSet.from_raw(raw).to_dict()
    return ", ".join(f"{k}={v:g}" for k, v in sorted(d.items()))


def cmd_status(args) -> int:
    """reference: scripts.py:1412 `ray status` — node table + resources."""
    addr = _gcs_address(args)
    if not addr:
        print("no cluster found (no --address, RAY_TPU_ADDRESS, or record)",
              file=sys.stderr)
        return 1
    nodes = _rpc_call(addr, "get_all_nodes")
    avail = _rpc_call(addr, "get_available_resources")
    print(f"cluster at {addr}: {len(nodes)} node(s)")
    for n in nodes:
        a = avail.get(n["node_id"], {})
        head = " (head)" if n.get("is_head") else ""
        print(f"  node {n['node_id'].hex()[:8]}{head} @ {n['address']} "
              f"[{n.get('hostname', '')}]")
        print(f"    total:     {_fmt_resources(n['resources'])}")
        print(f"    available: {_fmt_resources(a) if a else '(no heartbeat)'}")
    return 0


def cmd_drain(args) -> int:
    """Graceful scale-down of one node: ALIVE -> DRAINING (stops taking
    leases/spillback, migrates its objects, checkpoints restartable
    actors) -> DRAINED. The node argument is an id prefix (as printed by
    `ray-tpu status`) or a raylet address."""
    addr = _gcs_address(args)
    if not addr:
        print("no cluster found", file=sys.stderr)
        return 1
    nodes = _rpc_call(addr, "get_all_nodes")
    want = args.node.lower()
    matches = [n for n in nodes
               if n["node_id"].hex().startswith(want)
               or n["address"] == args.node]
    if not matches:
        print(f"no node matches {args.node!r}", file=sys.stderr)
        return 1
    if len(matches) > 1:
        print(f"{args.node!r} is ambiguous: "
              + ", ".join(n["node_id"].hex()[:8] for n in matches),
              file=sys.stderr)
        return 1
    node = matches[0]
    if node.get("is_head"):
        print("refusing to drain the head node (use `ray-tpu stop`)",
              file=sys.stderr)
        return 1
    reply = _rpc_call(addr, "drain_node", {
        "node_id": node["node_id"],
        "preempt": bool(args.preempt),
    })
    print(f"node {node['node_id'].hex()[:8]}: {reply.get('state')}")
    if not args.wait:
        return 0
    import time as _time

    deadline = _time.monotonic() + args.timeout
    while _time.monotonic() < deadline:
        left = _rpc_call(addr, "get_all_nodes")
        if all(n["node_id"] != node["node_id"] for n in left):
            print(f"node {node['node_id'].hex()[:8]}: DRAINED")
            return 0
        _time.sleep(0.5)
    print(f"node {node['node_id'].hex()[:8]}: still draining after "
          f"{args.timeout:.0f}s", file=sys.stderr)
    return 1


def cmd_memory(args) -> int:
    """reference: scripts.py:1389 `ray memory` — object store usage."""
    addr = _gcs_address(args)
    if not addr:
        print("no cluster found", file=sys.stderr)
        return 1
    nodes = _rpc_call(addr, "get_all_nodes")
    total_used = total_objects = 0
    for n in nodes:
        try:
            info = _rpc_call(n["address"], "cluster_info")
        except Exception as e:
            print(f"  node {n['node_id'].hex()[:8]}: unreachable ({e})")
            continue
        used = info["store_used"]
        cnt = info["num_local_objects"]
        total_used += used
        total_objects += cnt
        print(f"  node {n['node_id'].hex()[:8]} @ {n['address']}: "
              f"{cnt} object(s), {used / 1e6:.1f} MB in store, "
              f"{info['num_workers']} worker(s)")
    print(f"total: {total_objects} object(s), {total_used / 1e6:.1f} MB")
    return 0


def cmd_metrics(args) -> int:
    """reference: the `ray status -v` / metrics export surface
    (src/ray/stats/metric.h)."""
    addr = _gcs_address(args)
    if not addr:
        print("no cluster found", file=sys.stderr)
        return 1

    def show(title, snap):
        print(title)
        for name in sorted(snap):
            m = snap[name]
            if m["type"] == "histogram":
                print(f"  {name}: n={m['count']} sum={m['sum']:.3f}")
            else:
                print(f"  {name}: {m['value']:g}")

    show("gcs:", _rpc_call(addr, "get_metrics"))
    for n in _rpc_call(addr, "get_all_nodes"):
        try:
            snap = _rpc_call(n["address"], "get_metrics")
        except Exception as e:
            print(f"node {n['node_id'].hex()[:8]}: unreachable ({e})")
            continue
        show(f"node {n['node_id'].hex()[:8]}:", snap)
    return 0


def cmd_timeline(args) -> int:
    """reference: `ray timeline` (scripts.py) — chrome-trace dump."""
    addr = _gcs_address(args)
    if not addr:
        print("no cluster found", file=sys.stderr)
        return 1
    from ray_tpu._private.profiling import to_chrome_trace

    trace = to_chrome_trace(_rpc_call(addr, "get_profile_events"))
    out = args.out or "timeline.json"
    with open(out, "w") as f:
        json.dump(trace, f)
    print(f"wrote {len(trace)} events to {out} "
          f"(open in chrome://tracing or Perfetto)")
    return 0


def cmd_trace(args) -> int:
    """Export the GCS trace table (causally-linked cross-process span
    trees, tracing.py) as Perfetto/chrome-trace JSON — the whole table,
    or one tree via --trace-id."""
    addr = _gcs_address(args)
    if not addr:
        print("no cluster found", file=sys.stderr)
        return 1
    from ray_tpu._private.profiling import spans_to_chrome_trace

    rows = _rpc_call(addr, "get_trace_spans",
                     {"trace_id": args.trace_id})
    if not rows:
        print("(no trace spans recorded — is sampling on? see "
              "RAY_TPU_TRACE_SAMPLE / ray_tpu.set_trace_sampling)")
        return 0
    trace = spans_to_chrome_trace(rows)
    out = args.out or "trace.json"
    with open(out, "w") as f:
        json.dump(trace, f)
    traces = {r["extra_data"].get("tid") for r in rows}
    print(f"wrote {len(rows)} spans across {len(traces)} trace(s) to "
          f"{out} (open in Perfetto / chrome://tracing)")
    return 0


def _top_snapshot(reply, flt=None) -> dict:
    """Structured rate/p99 table off one get_metrics_history reply —
    shared by the live text render and `--json --once` (scripts/CI).
    {"meta", "sources": {source: {metric: {latest, ts, rate?, p99_ms?,
    saturated?, exemplar?}}}}."""
    if isinstance(reply, dict) and "series" in reply:
        hist = reply["series"]
        meta = reply.get("meta") or {}
        exemplars = reply.get("exemplars") or {}
    else:  # pre-meta GCS
        hist, meta, exemplars = reply, {}, {}
    sources: dict = {}
    for source in sorted(hist):
        rings = hist[source]
        rows: dict = {}
        for name in sorted(rings):
            series = rings[name]
            if not series or (flt and flt not in name):
                continue
            if name.endswith(".p99_saturated"):
                continue  # folded into the .p99 row below
            ts, val = series[-1]
            row = {"latest": val, "ts": ts}
            if name.endswith(".p99"):
                row["p99_ms"] = val * 1e3
                sat = rings.get(name + "_saturated")
                row["saturated"] = bool(sat and sat[-1][1])
                base = name[:-len(".p99")]
                ex = (exemplars.get(source) or {}).get(base)
                if ex:
                    row["exemplar"] = ex.get("trace_id")
                    row["exemplar_value_ms"] = ex.get("value", 0) * 1e3
            elif len(series) >= 2 and (name.endswith("_total")
                                       or name.endswith(".count")):
                # rate-over-window is only meaningful for counters —
                # a rising gauge (bytes in use) is a level, not a flow
                (t0, v0), (t1, v1) = series[0], series[-1]
                if t1 > t0 and v1 >= v0:
                    row["rate_per_s"] = (v1 - v0) / (t1 - t0)
            rows[name] = row
        if rows:
            sources[source] = rows
    return {"meta": meta, "sources": sources}


def cmd_top(args) -> int:
    """Live cluster metrics view off the GCS time-series ring (the
    `ray-tpu top` analog of `ray status -v`, refreshed in place).
    Shows, per source, the latest sample plus a rate over the window
    for counters and the current p99 for latency histograms — with a
    `≥` marker when the p99 saturated its top bucket and the p99
    exemplar's trace id (resolve it: `ray-tpu trace --trace-id`).
    `--json --once`: one machine-readable snapshot for scripts/CI."""
    import time as _time

    addr = _gcs_address(args)
    if not addr:
        print("no cluster found", file=sys.stderr)
        return 1
    if getattr(args, "once", False) or getattr(args, "json", False):
        # --json is a one-shot machine-readable snapshot: looping would
        # interleave clear-screen escapes into the JSON stream
        args.iterations = 1

    epoch = [None]  # GCS history epoch across renders (reset marker)

    def render() -> int:
        reply = _rpc_call(addr, "get_metrics_history",
                          {"samples": 0, "meta": True})
        snap = _top_snapshot(reply, args.filter)
        if getattr(args, "json", False):
            snap["collected_at"] = _time.time()
            print(json.dumps(snap, indent=1, default=str))
            return len(snap["sources"])
        started = snap["meta"].get("started_at")
        reset = (epoch[0] is not None and started is not None
                 and started != epoch[0])
        if started is not None:
            epoch[0] = started
        lines = []
        if reset:
            # metrics history + trace rings are director-memory-only
            # (documented lossy-restart contract): a restart resets
            # them — render the discontinuity instead of silently
            # splicing fresh samples onto the old view
            lines.append("  ===== history reset: GCS (re)started — "
                         "rings cleared, rates restart from zero =====")
        for source, rows_d in snap["sources"].items():
            rows = []
            newest = 0.0
            for name, row in rows_d.items():
                newest = max(newest, row["ts"])
                if "p99_ms" in row:
                    sat = "≥" if row.get("saturated") else " "
                    ex = (f"  trace={row['exemplar']}"
                          if row.get("exemplar") else "")
                    rows.append(f"    {name:<44}{sat}"
                                f"{row['p99_ms']:8.2f} ms{ex}")
                    continue
                rate = (f"  ({row['rate_per_s']:8.1f}/s)"
                        if "rate_per_s" in row else "")
                rows.append(f"    {name:<44} {row['latest']:12g}{rate}")
            if rows:
                age = _time.time() - newest
                lines.append(f"  {source}  (sample {age:.1f}s old, "
                             f"{len(rows)} metrics)")
                lines.extend(rows)
        print(f"ray-tpu top — {_time.strftime('%H:%M:%S')} — "
              f"{len(snap['sources'])} sources")
        if lines:
            print("\n".join(lines))
        else:
            print("  (no samples yet — history fills on the ~2s "
                  "heartbeat/flush cadence)")
        return len(lines)

    if args.iterations == 1:
        render()
        return 0
    try:
        n = 0
        while args.iterations <= 0 or n < args.iterations:
            if n:
                print("\x1b[2J\x1b[H", end="")  # clear + home
            render()
            n += 1
            if args.iterations <= 0 or n < args.iterations:
                _time.sleep(args.interval)
    except KeyboardInterrupt:
        pass
    return 0


def cmd_profile(args) -> int:
    """Cluster-wide CPU flamegraph off the continuous profiling plane:
    collect `--seconds` of sampler windows from the GCS profile ring
    and write collapsed-stack text (flamegraph.pl / speedscope input),
    optionally Perfetto tracks (--perfetto). `--hz` re-arms the
    cluster sampler rate for the window (restored after)."""
    import time as _time

    from ray_tpu._private import sampling_profiler as _sprof

    addr = _gcs_address(args)
    if not addr:
        print("no cluster found", file=sys.stderr)
        return 1
    prev_hz = None
    if args.hz is not None:
        prev_hz = _rpc_call(addr, "kv_get", {"key": _sprof.KV_KEY})
        _rpc_call(addr, "kv_put", {"key": _sprof.KV_KEY,
                                   "value": repr(float(args.hz)).encode()})
    try:
        since = _time.time()
        _time.sleep(max(0.0, args.seconds))
        batches = _sprof.wait_for_coverage(
            lambda: _rpc_call(addr, "get_profile_samples",
                              {"since": since,
                               "component": args.component}),
            args.component)
        classes = _sprof.components_of(batches)
    finally:
        if args.hz is not None:
            # restore the prior override, or b"default" — every process
            # re-derives ITS OWN env/budget rate (writing this host's
            # number would pin a derated node to the CLI box's default)
            _rpc_call(addr, "kv_put", {
                "key": _sprof.KV_KEY,
                "value": prev_hz or b"default"})
    if not batches:
        print("(no profile samples — is the profiler armed? see "
              "RAY_TPU_PROFILE_HZ / ray_tpu.set_profiling)")
        return 1
    collapsed = _sprof.collapse_text(batches, args.component)
    out = args.out or "profile.collapsed"
    if out == "-":
        print(collapsed)
    else:
        with open(out, "w") as f:
            f.write(collapsed + "\n")
    if args.perfetto:
        with open(args.perfetto, "w") as f:
            json.dump(_sprof.samples_to_chrome_trace(batches), f)
    samples = sum(b.get("samples", 0) for b in batches)
    print(f"{samples} samples across {len(classes)} process class(es) "
          f"({', '.join(classes)}); wrote {len(collapsed.splitlines())} "
          f"collapsed stacks to {out}"
          + (f" + Perfetto tracks to {args.perfetto}"
             if args.perfetto else ""))
    return 0


def _fmt_row(row: dict, drop=("process",)) -> str:
    parts = []
    for k, v in row.items():
        if k in drop or v in ("", None, [], {}):
            continue
        parts.append(f"{k}={v}")
    return "  ".join(parts)


def cmd_state(args) -> int:
    """Live cluster introspection (`ray-tpu state [component]`): every
    process's debug_state() aggregated over the rpc plane — no driver
    runtime needed. Without a component: a per-process summary; with
    one (serve|placement|tasks|actors|objects|leases|transfers|
    collectives): flat rows across the cluster, oldest first."""
    addr = _gcs_address(args)
    if not addr:
        print("no cluster found", file=sys.stderr)
        return 1
    from ray_tpu._private import debug_state

    snap = debug_state.collect_via_rpc(
        addr, include_workers=not args.no_workers, timeout=args.timeout)
    if not args.component:
        for label, proc in debug_state.iter_processes(snap):
            if "error" in proc:
                print(f"{label}: UNREACHABLE ({proc['error']})")
                continue
            bits = [f"pid={proc.get('pid')}"]
            lag = proc.get("event_loop_lag_s")
            if lag is not None:
                bits.append(f"loop_lag={lag * 1e3:.1f}ms")
            for key, fmt in (("tasks", "tasks"), ("executing", "exec"),
                             ("leases", "leases"), ("actors", "actors"),
                             ("pending_leases", "lease_queue"),
                             ("worker_pool", "workers"),
                             ("collectives", "collective_groups")):
                n = len(proc.get(key) or [])
                if n:
                    bits.append(f"{fmt}={n}")
            tr = proc.get("transfers") or {}
            n = len(tr.get("pulls") or []) + len(tr.get("serves") or [])
            if n:
                bits.append(f"transfers={n}")
            print(f"{label}: " + "  ".join(bits))
        return 0
    rows = debug_state.flatten(snap, args.component)
    if args.filter:
        rows = [r for r in rows
                if any(args.filter in str(v) for v in r.values())]
    if not rows:
        print(f"(no live {args.component})")
        return 0
    for row in rows:
        print(f"{row.get('process', '?'):<28} {_fmt_row(row)}")
    return 0


def _find_stack_address(snap, target: str):
    """Resolve a `ray-tpu stack` target (pid | worker/node id prefix |
    address) to (label, rpc address) from a cluster snapshot."""
    from ray_tpu._private import debug_state

    for label, proc in debug_state.iter_processes(snap):
        addr = proc.get("address")
        if str(proc.get("pid")) == target:
            return label, addr
        if target and (target in label
                       or (addr and target in addr)
                       or target == proc.get("worker_id", "")[:len(target)]
                       or target == proc.get("node_id", "")):
            return label, addr
    return None, None


def cmd_stack(args) -> int:
    """All-thread Python stacks of any live runtime process
    (sys._current_frames over rpc): `ray-tpu stack gcs`, a pid, a
    node/worker id prefix, or an rpc address."""
    addr = _gcs_address(args)
    if not addr:
        print("no cluster found", file=sys.stderr)
        return 1
    target = args.target
    if target == "gcs":
        label, stacks = "gcs", _rpc_call(addr, "debug_stacks")
    else:
        from ray_tpu._private import debug_state

        snap = debug_state.collect_via_rpc(addr, timeout=args.timeout)
        label, proc_addr = _find_stack_address(snap, target)
        if proc_addr is None:
            print(f"no live process matches {target!r} (try "
                  f"`ray-tpu state` for pids/ids)", file=sys.stderr)
            return 1
        stacks = _rpc_call(proc_addr, "debug_stacks")
    print(f"=== {label} (pid {stacks.get('pid')}), "
          f"{len(stacks.get('threads', []))} thread(s) ===")
    for t in stacks.get("threads", []):
        daemon = " daemon" if t.get("daemon") else ""
        print(f"\n--- thread {t['name']}{daemon} ---")
        print(t["stack"].rstrip())
    return 0


def cmd_doctor(args) -> int:
    """The stall doctor, out of process: collect cluster_state + the
    per-hop latency histograms, flag anything whose age exceeds
    max(floor, K×p99) for its stage, and print each finding with its
    owning process (+ stacks with --stacks). Exit code 1 when stalls
    were found."""
    addr = _gcs_address(args)
    if not addr:
        print("no cluster found", file=sys.stderr)
        return 1
    from ray_tpu._private import debug_state

    snap = debug_state.collect_via_rpc(addr, timeout=args.timeout)
    metrics = {"raylets": {}}
    try:
        metrics["gcs"] = _rpc_call(addr, "get_metrics")
        for n in _rpc_call(addr, "get_all_nodes"):
            try:
                metrics["raylets"][n["node_id"].hex()[:8]] = _rpc_call(
                    n["address"], "get_metrics")
            except Exception:
                pass
    except Exception:
        pass
    findings = debug_state.diagnose(snap, metrics, floor_s=args.floor,
                                    p99_factor=args.p99_factor)
    if not findings:
        print("doctor: no stalls detected "
              f"(floor {args.floor if args.floor is not None else debug_state.DOCTOR_FLOOR_S}s, "
              f"K={args.p99_factor if args.p99_factor is not None else debug_state.DOCTOR_P99_FACTOR})")
        return 0
    seen_procs = set()
    for f in findings:
        tid = f" trace={f['trace_id']}" if f.get("trace_id") else ""
        print(f"STALLED {f['kind']} {f.get('name') or f.get('id')}: "
              f"stage={f['stage']} age={f['age_s']:.1f}s "
              f"(threshold {f['threshold_s']:.1f}s) on {f['process']}"
              f"{tid}  {f.get('detail', '')}")
        if args.stacks and f["process"] not in seen_procs:
            seen_procs.add(f["process"])
            _, proc_addr = _find_stack_address(snap, f["process"])
            if proc_addr:
                try:
                    stacks = _rpc_call(proc_addr, "debug_stacks")
                    for t in stacks.get("threads", []):
                        print(f"  --- {f['process']} thread "
                              f"{t['name']} ---")
                        for line in t["stack"].rstrip().splitlines():
                            print(f"  {line}")
                except Exception as e:
                    print(f"  (stacks unreachable: {e})")
    print(f"{len(findings)} finding(s)")
    return 1


def cmd_submit(args) -> int:
    """Run a driver script against the recorded cluster (reference:
    `ray submit` — there via the cluster launcher; here the cluster is
    local/recorded, so submit = exec with RAY_TPU_ADDRESS wired)."""
    addr = _gcs_address(args)
    if not addr:
        print("no cluster found", file=sys.stderr)
        return 1
    env = dict(os.environ)
    env["RAY_TPU_ADDRESS"] = addr
    # the driver runs with ITS script dir as sys.path[0]; make the
    # framework importable from anywhere the user submits from
    pkg_root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    existing = env.get("PYTHONPATH")
    env["PYTHONPATH"] = (pkg_root + os.pathsep + existing
                         if existing else pkg_root)
    cmd = [sys.executable, args.script, *args.script_args]
    return subprocess.call(cmd, env=env)


def cmd_events(args) -> int:
    """reference: the structured-event surface (RAY_EVENT/event.h; the
    reference ships events to its event log dir + dashboard)."""
    import time as _time

    addr = _gcs_address(args)
    if not addr:
        print("no cluster found", file=sys.stderr)
        return 1
    events = _rpc_call(addr, "get_events",
                       {"severity": args.severity, "limit": args.limit})
    for e in events:
        ts = _time.strftime("%H:%M:%S", _time.localtime(e["timestamp"]))
        print(f"{ts} {e['severity']:<7} {e['label']:<14} "
              f"[{e['source_type']}] {e['message']}")
    if not events:
        print("(no events)")
    return 0


def cmd_dashboard(args) -> int:
    """reference: `ray dashboard` / the dashboard head process."""
    addr = _gcs_address(args)
    if not addr:
        print("no cluster found", file=sys.stderr)
        return 1
    from ray_tpu.dashboard import Dashboard

    dash = Dashboard(addr, args.host, args.port)
    asyncio.run(dash.run(ready_cb=lambda p: print(
        f"dashboard at http://{args.host}:{p}", flush=True)))
    return 0


def cmd_debug(args) -> int:
    """Attach to a live rpdb breakpoint (reference: `ray debug`,
    scripts/scripts.py + util/rpdb.py)."""
    addr = _gcs_address(args)
    if not addr:
        print("no cluster found (no --address, RAY_TPU_ADDRESS, or "
              "record)", file=sys.stderr)
        return 2
    import ray_tpu

    ray_tpu.init(address=addr)
    from ray_tpu.util import rpdb

    sessions = rpdb.active_sessions()
    if not sessions:
        print("no active breakpoints (call ray_tpu.util.rpdb.set_trace()"
              " inside a task/actor)")
        return 0
    for i, s in enumerate(sessions):
        print(f"[{i}] pid {s['pid']} at {s['filename']}:{s['lineno']}")
    idx = args.index
    if idx is None:
        if len(sessions) == 1:
            idx = 0
        else:
            try:
                idx = int(input("attach to which breakpoint? "))
            except (ValueError, EOFError):
                print("not a breakpoint number", file=sys.stderr)
                return 2
    if not 0 <= idx < len(sessions):
        print(f"breakpoint index {idx} out of range "
              f"(0..{len(sessions) - 1})", file=sys.stderr)
        return 2
    print(f"attaching to [{idx}] — pdb commands apply remotely "
          f"(c to continue, q to abort the task)")
    try:
        rpdb.connect(sessions[idx])
    except OSError as e:
        print(f"breakpoint unreachable ({e}); it may have just "
              f"finished — rerun `ray-tpu debug`", file=sys.stderr)
        return 1
    return 0


def cmd_up(args) -> int:
    from ray_tpu.autoscaler import launcher

    state = launcher.up(args.config)
    print(f"cluster {state['cluster_name']!r} up: "
          f"{len(state['nodes'])} nodes")
    print(f"GCS address: {state['gcs_address']}")
    print(f"attach with: ray-tpu attach {state['cluster_name']}")
    return 0


def cmd_down(args) -> int:
    from ray_tpu.autoscaler import launcher

    errors = launcher.down(args.cluster)
    if errors:
        print(f"warning: {errors} node(s) failed to stop cleanly",
              file=sys.stderr)
    print("cluster down")
    return 1 if errors else 0


def cmd_attach(args) -> int:
    from ray_tpu.autoscaler import launcher

    cmdline = launcher.attach_command(args.cluster)
    if args.print_only:
        print(cmdline)
        return 0
    import subprocess

    return subprocess.call(cmdline, shell=True)


def cmd_exec(args) -> int:
    from ray_tpu.autoscaler import launcher

    out = launcher.exec_on_head(args.cluster, args.command)
    print(out, end="")
    return 0


def cmd_microbenchmark(args) -> int:
    from ray_tpu import microbenchmark

    out = microbenchmark.main()
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


def cmd_scalesim(args) -> int:
    """Control-plane scale-sim: spoofed raylets against a real GCS
    (director + store shards) on this box — scheduler decisions/s and
    GCS op throughput, interleaved A/B vs the single-shard legacy arm
    (ray_tpu/scalesim/harness.py). --topology runs the placement arm
    instead: ICI_RING vs PACK over spoofed 4x4-torus raylets
    (ray_tpu/scalesim/topology_sim.py). --elastic runs the membership
    ramp arm: drain-aware vs static vs kill-based scale-down scored on
    node-hours x SLO violations (ray_tpu/scalesim/elastic_sim.py)."""
    from ray_tpu.scalesim import run_scalesim

    if args.elastic:
        from ray_tpu.scalesim import run_elastic_sim

        result = run_elastic_sim(raylets=args.raylets,
                                 windows=args.windows, out=args.out)
        for label, arm in result["arms"].items():
            print(f"{label}: node-hours {arm['node_hours']}  "
                  f"objects lost {arm['objects_lost']}/"
                  f"{arm['objects_departed']}  shortfall "
                  f"{arm['capacity_shortfall']}  score {arm['score']}  "
                  f"recovery {arm['mean_recovery_ms']}ms")
        print(f"score vs drain-aware: kill "
              f"{result['score_ratio_kill_over_drain']}x, static "
              f"{result['score_ratio_static_over_drain']}x; "
              f"{result['bytes_saved_vs_kill']} bytes saved vs kill, "
              f"{result['node_hours_saved_vs_static']} node-hours "
              f"saved vs static")
        if args.out:
            print(f"wrote {args.out}")
        return 0

    if args.topology:
        from ray_tpu.scalesim import run_topology_sim

        result = run_topology_sim(raylets=args.raylets,
                                  windows=args.windows, seed=args.seed,
                                  out=args.out)
        for label, arm in result["arms"].items():
            print(f"{label}: circumference "
                  f"{arm['mean_ring_circumference']}  spillback hops "
                  f"{arm['mean_spillback_hops']}  latency "
                  f"{arm['placement_latency_ms']['mean']}ms  "
                  f"score p99 {arm['score_p99_s'] * 1e3:.2f}ms")
        print(f"PACK/ICI_RING circumference ratio "
              f"{result['circumference_ratio']}x, spillback hops "
              f"{result['spillback_hops_ratio']}x, score p99 ratio "
              f"{result['score_p99_ratio']}")
        if args.out:
            print(f"wrote {args.out}")
        return 0

    result = run_scalesim(
        shards=args.shards, raylets=args.raylets, windows=args.windows,
        window_s=args.window_s, seed=args.seed,
        kill_shard=args.kill_shard, legacy_arm=not args.no_legacy_arm,
        out=args.out)
    for label, arm in result["arms"].items():
        print(f"{label}: gcs ops/s "
              f"{arm['gcs_ops_per_s']['median']:.0f}  "
              f"decisions/s {arm['decisions_per_s']['median']:.0f}")
    if "speedup_gcs_ops" in result:
        print(f"speedup vs shards=1: gcs ops {result['speedup_gcs_ops']}x, "
              f"decisions {result['speedup_decisions']}x")
    if "director_bypass_ratio" in result:
        print(f"director bypass: {result['director_bypass_ratio']}x the "
              f"legacy arm's director CPU per op "
              f"({result['cores']} cores on this box; rates understate "
              f"the sharded arm below shards+2 cores)")
    if result.get("kill"):
        print(f"shard kill: {result['kill']}")
    if args.out:
        print(f"wrote {args.out}")
    return 0


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="ray-tpu", description="ray_tpu cluster CLI")
    sub = parser.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("start", help="start a head or worker node")
    p.add_argument("--head", action="store_true")
    p.add_argument("--address", help="GCS address to join (worker nodes)")
    p.add_argument("--port", type=int, default=0, help="GCS port (head)")
    p.add_argument("--num-cpus", type=float, default=None)
    p.add_argument("--num-tpus", type=float, default=None)
    p.add_argument("--resources", help="JSON dict of custom resources")
    p.add_argument("--tpu-slice",
                   help="JSON TpuSliceDescriptor for this host's ICI "
                        "domain (util/accelerators.py)")
    p.add_argument("--system-config", help="JSON dict of config overrides")
    p.add_argument("--client-server-port", type=int, default=None,
                   help="also serve ray-client connections on this port")
    p.set_defaults(fn=cmd_start)

    p = sub.add_parser("stop", help="stop the recorded cluster")
    p.set_defaults(fn=cmd_stop)

    p = sub.add_parser("status", help="node table + resources")
    p.add_argument("--address", default=None)
    p.set_defaults(fn=cmd_status)

    p = sub.add_parser("drain",
                       help="gracefully drain one node out of the "
                            "cluster (migrate objects, checkpoint "
                            "actors, then exit)")
    p.add_argument("node", help="node id prefix (see `ray-tpu status`) "
                                "or raylet address")
    p.add_argument("--address", default=None)
    p.add_argument("--preempt", action="store_true",
                   help="compressed drain: checkpoint gangs first, "
                        "objects best-effort (preemption-notice path)")
    p.add_argument("--wait", action="store_true",
                   help="block until the node reaches DRAINED")
    p.add_argument("--timeout", type=float, default=120.0,
                   help="--wait limit in seconds")
    p.set_defaults(fn=cmd_drain)

    p = sub.add_parser("memory", help="object-store usage per node")
    p.add_argument("--address", default=None)
    p.set_defaults(fn=cmd_memory)

    p = sub.add_parser("metrics", help="metric snapshots from gcs + raylets")
    p.add_argument("--address", default=None)
    p.set_defaults(fn=cmd_metrics)

    p = sub.add_parser("trace",
                       help="export distributed-trace span trees "
                            "(Perfetto JSON)")
    p.add_argument("--address", default=None)
    p.add_argument("--trace-id", default=None,
                   help="hex trace id — export one tree only")
    p.add_argument("--out", default=None, help="output path (trace.json)")
    p.set_defaults(fn=cmd_trace)

    p = sub.add_parser("top",
                       help="live metrics view off the GCS time-series")
    p.add_argument("--address", default=None)
    p.add_argument("--interval", type=float, default=2.0)
    p.add_argument("--iterations", type=int, default=0,
                   help="stop after N refreshes (0 = until Ctrl-C)")
    p.add_argument("--filter", default=None,
                   help="only metrics whose name contains this substring")
    p.add_argument("--once", action="store_true",
                   help="render one snapshot and exit (= --iterations 1)")
    p.add_argument("--json", action="store_true",
                   help="machine-readable snapshot (rates, p99s, "
                        "saturation flags, exemplar trace ids) for "
                        "scripts and CI")
    p.set_defaults(fn=cmd_top)

    p = sub.add_parser("profile",
                       help="cluster-wide CPU flamegraph (collapsed "
                            "stacks off the continuous profiler)")
    p.add_argument("--address", default=None)
    p.add_argument("--seconds", type=float, default=2.0,
                   help="collection window (default 2)")
    p.add_argument("--component", default=None,
                   choices=["driver", "worker", "raylet", "gcs",
                            "gcs-shard"],
                   help="one process class only (default: all)")
    p.add_argument("-o", "--out", default=None,
                   help="collapsed-stack output path "
                        "(profile.collapsed; '-' = stdout)")
    p.add_argument("--perfetto", default=None,
                   help="also write merged Perfetto tracks JSON here")
    p.add_argument("--hz", type=float, default=None,
                   help="re-arm the cluster sampler at this rate for "
                        "the window (restored after)")
    p.set_defaults(fn=cmd_profile)

    p = sub.add_parser("state",
                       help="live cluster introspection (debug_state "
                            "of every process)")
    p.add_argument("component", nargs="?", default=None,
                   choices=["serve", "placement", "tasks", "actors",
                            "objects", "leases", "transfers",
                            "collectives"],
                   help="flat rows for one component class "
                        "(omit for a per-process summary; `serve` shows "
                        "per-router queue depth vs bound + shed/admitted "
                        "totals, replica-group state, and per-engine "
                        "decode-batch occupancy / per-session KV page "
                        "counts / stream backlog for streaming backends; "
                        "`placement` shows per-pg bundle→node rows with "
                        "topology coords and the chosen strategy / "
                        "cost-model)")
    p.add_argument("--address", default=None)
    p.add_argument("--filter", default=None,
                   help="only rows containing this substring")
    p.add_argument("--no-workers", action="store_true",
                   help="skip the per-worker fan-out (faster)")
    p.add_argument("--timeout", type=float, default=5.0)
    p.set_defaults(fn=cmd_state)

    p = sub.add_parser("stack",
                       help="all-thread Python stacks of a live "
                            "process (gcs | pid | id prefix | address)")
    p.add_argument("target")
    p.add_argument("--address", default=None)
    p.add_argument("--timeout", type=float, default=5.0)
    p.set_defaults(fn=cmd_stack)

    p = sub.add_parser("doctor",
                       help="stall doctor: flag in-flight work whose "
                            "age exceeds max(floor, K*p99) of its stage")
    p.add_argument("--address", default=None)
    p.add_argument("--floor", type=float, default=None,
                   help="absolute stall floor in seconds (default 1.0 / "
                        "RAY_TPU_DOCTOR_FLOOR_S)")
    p.add_argument("--p99-factor", type=float, default=None,
                   help="K in max(floor, K*p99) (default 3.0 / "
                        "RAY_TPU_DOCTOR_P99_K)")
    p.add_argument("--stacks", action="store_true",
                   help="print the flagged processes' thread stacks")
    p.add_argument("--timeout", type=float, default=5.0)
    p.set_defaults(fn=cmd_doctor)

    p = sub.add_parser("timeline", help="dump chrome-trace profile timeline")
    p.add_argument("--address", default=None)
    p.add_argument("--out", default=None)
    p.set_defaults(fn=cmd_timeline)

    p = sub.add_parser("submit", help="run a driver script on the cluster")
    p.add_argument("--address", default=None)
    p.add_argument("script")
    # REMAINDER: everything after the script (including --flags) belongs
    # to the driver, not to this parser
    p.add_argument("script_args", nargs=argparse.REMAINDER)
    p.set_defaults(fn=cmd_submit)

    p = sub.add_parser("events", help="structured cluster events")
    p.add_argument("--address", default=None)
    p.add_argument("--severity", default=None,
                   choices=["INFO", "WARNING", "ERROR", "FATAL"])
    p.add_argument("--limit", type=int, default=100)
    p.set_defaults(fn=cmd_events)

    p = sub.add_parser("dashboard", help="serve the cluster dashboard")
    p.add_argument("--address", default=None)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8265)
    p.set_defaults(fn=cmd_dashboard)

    p = sub.add_parser("debug", help="attach to a live rpdb breakpoint")
    p.add_argument("--address", default=None)
    p.add_argument("--index", type=int, default=None,
                   help="breakpoint number (skip the prompt)")
    p.set_defaults(fn=cmd_debug)

    p = sub.add_parser("up", help="launch a cluster from a YAML spec")
    p.add_argument("config", help="cluster YAML (see autoscaler/launcher.py)")
    p.set_defaults(fn=cmd_up)

    p = sub.add_parser("down", help="stop a launched cluster")
    p.add_argument("cluster", help="cluster name or YAML path")
    p.set_defaults(fn=cmd_down)

    p = sub.add_parser("attach", help="open a shell on the head node")
    p.add_argument("cluster", help="cluster name or YAML path")
    p.add_argument("--print-only", action="store_true",
                   help="print the attach command instead of exec'ing it")
    p.set_defaults(fn=cmd_attach)

    p = sub.add_parser("exec", help="run a command on the head node")
    p.add_argument("cluster", help="cluster name or YAML path")
    p.add_argument("command")
    p.set_defaults(fn=cmd_exec)

    p = sub.add_parser("microbenchmark", help="run the core benchmark suite")
    p.add_argument("--out", default=None)
    p.set_defaults(fn=cmd_microbenchmark)

    p = sub.add_parser("scalesim",
                       help="control-plane scale-sim (spoofed raylets "
                            "vs a real sharded GCS)")
    p.add_argument("--shards", type=int, default=4)
    p.add_argument("--raylets", type=int, default=16,
                   help="spoofed raylet clients")
    p.add_argument("--windows", type=int, default=5)
    p.add_argument("--window-s", type=float, default=1.0,
                   help="seconds per measurement slice")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--kill-shard", action="store_true",
                   help="SIGKILL+restart a seeded shard mid-window and "
                        "verify zero lost acked ops")
    p.add_argument("--no-legacy-arm", action="store_true",
                   help="skip the interleaved shards=1 control arm")
    p.add_argument("--topology", action="store_true",
                   help="run the topology placement arm instead: "
                        "ICI_RING vs PACK over spoofed 4x4-torus "
                        "raylets (circumference / spillback hops / "
                        "placement latency)")
    p.add_argument("--elastic", action="store_true",
                   help="run the elastic membership ramp arm instead: "
                        "drain-aware vs static vs kill-based "
                        "scale-down, scored on node-hours x SLO "
                        "violations")
    p.add_argument("--out", default=None, help="write result JSON here")
    p.set_defaults(fn=cmd_scalesim)

    args = parser.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
