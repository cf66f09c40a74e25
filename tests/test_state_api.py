"""Live cluster state introspection + stall doctor (debug_state.py).

Covers the acceptance surface: a live multi-node cluster answers
cluster_state() for every component class within a deadline; a
deliberately stalled task (failpoint-delayed lease) is flagged by
api.doctor() with its stage, age and owning process (and emits a
deduped STALL_DETECTED event); a collective.device_dispatch-killed
group's timeout error carries an attached state snapshot naming the
wedged op; the CLI/stack surfaces work out-of-process; and the
MICROBENCH state-A/B rows gate the introspection overhead at <=5%.
"""

import json
import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu._private import debug_state
from ray_tpu._private import failpoints as fp
from ray_tpu._private import stats
from tests.conftest import scale_timeout


# ---------------------------------------------------------------------------
# cluster_state: every component class answers within a deadline
# ---------------------------------------------------------------------------


def test_cluster_state_all_components(ray_start_cluster_2_nodes):
    ray_start_cluster_2_nodes.connect_driver()

    @ray_tpu.remote
    def work(x):
        return x * 2

    @ray_tpu.remote
    class Holder:
        def get(self):
            return 7

    h = Holder.remote()
    assert ray_tpu.get([work.remote(i) for i in range(4)],
                       timeout=scale_timeout(60)) == [0, 2, 4, 6]
    assert ray_tpu.get(h.get.remote(), timeout=scale_timeout(60)) == 7

    deadline = scale_timeout(15)
    t0 = time.monotonic()
    snap = ray_tpu.cluster_state(timeout=scale_timeout(5))
    took = time.monotonic() - t0
    assert took < deadline, f"cluster_state took {took:.1f}s"

    # driver
    drv = snap["driver"]
    assert drv["role"] == "driver" and drv["pid"] == os.getpid()
    assert "event_loop_lag_s" in drv and drv["collect_s"] < deadline
    assert any(a["state"] == "ALIVE" for a in drv["actors"])

    # gcs director
    gcs = snap["gcs"]
    assert gcs["role"] == "gcs" and gcs["started_at"] > 0
    assert len(gcs["nodes_table"]) == 2
    assert gcs["actors_by_state"].get("ALIVE", 0) >= 1
    assert all(n["heartbeat_age_s"] is not None
               for n in gcs["nodes_table"])

    # raylets + their workers
    assert len(snap["nodes"]) == 2
    worker_snaps = []
    for nid, node in snap["nodes"].items():
        assert node["role"] == "raylet", node
        assert "worker_pool" in node and "transfers" in node
        assert "pending_leases" in node
        worker_snaps.extend((node.get("workers") or {}).values())
    live_workers = [w for w in worker_snaps if w.get("role") == "worker"]
    assert live_workers, "no worker debug_state in the node fan-out"
    for w in live_workers:
        assert "exec_queue_depth" in w and "executing" in w

    # the introspection plane observes itself: both satellite gauges
    # are registered and the collection latency was recorded
    snap_stats = stats.snapshot()
    assert snap_stats["debug.state_collect_s"]["value"] > 0
    assert "proc.event_loop_lag_s" in snap_stats
    # ...in the remote processes too (the drift-gate surface)
    metrics = ray_tpu.cluster_metrics()
    assert "proc.event_loop_lag_s" in metrics["gcs"]
    for rsnap in metrics["raylets"].values():
        assert "proc.event_loop_lag_s" in rsnap
        assert "debug.state_collect_s" in rsnap

    # flat component views answer for every component class
    for component in debug_state.COMPONENTS:
        rows = ray_tpu.cluster_state(component)
        assert isinstance(rows, list), component
    actors = ray_tpu.cluster_state("actors")
    assert any(a.get("state") == "ALIVE" for a in actors), actors
    objects = ray_tpu.cluster_state("objects")
    assert any(o.get("memstore_entries") is not None
               or o.get("local_objects") is not None for o in objects)


def test_cluster_state_degrades_on_dead_component(ray_start_regular):
    """A snapshot of a sick cluster must answer (with an error entry)
    instead of hanging on the sick part."""
    from ray_tpu import api as _api

    node = _api._global_node
    node.kill_gcs()
    t0 = time.monotonic()
    try:
        snap = ray_tpu.cluster_state(timeout=2.0)
    except Exception:
        snap = {}
    took = time.monotonic() - t0
    assert took < scale_timeout(20), f"snapshot hung {took:.1f}s"
    # driver state always answers locally
    if snap:
        assert snap.get("driver", {}).get("role") == "driver"
    # wait for the monitor to restart the GCS so teardown is clean
    deadline = time.monotonic() + scale_timeout(40)
    while time.monotonic() < deadline:
        gcs = next((s for s in node.processes
                    if s.name == "gcs_server"), None)
        if gcs is not None and gcs.alive():
            break
        time.sleep(0.2)


# ---------------------------------------------------------------------------
# the stall doctor
# ---------------------------------------------------------------------------


def test_doctor_flags_failpoint_delayed_lease(ray_start_regular):
    """Acceptance: a deliberately stalled task (lease.grant delayed by a
    failpoint) is flagged with its stage (lease_wait), age, owning
    process and trace id; the finding carries the owner's thread
    stacks; and a deduped STALL_DETECTED warning event reaches the GCS
    events ring."""
    debug_state.reset_stall_dedup()

    @ray_tpu.remote
    def quick():
        return 1

    # warm: worker spawned, histograms populated
    assert ray_tpu.get(quick.remote(), timeout=scale_timeout(60)) == 1
    ray_tpu.set_trace_sampling(1.0)
    delay_ms = scale_timeout(12) * 1000

    @ray_tpu.remote(resources={"CPU": 2})
    def stalled():
        return 2

    try:
        fp.arm_cluster(f"lease.grant=delay(ms={delay_ms},role=raylet)")
        ref = stalled.remote()
        time.sleep(scale_timeout(2.5))
        doc = ray_tpu.doctor(floor_s=1.0, p99_factor=0.0)
        findings = [f for f in doc["findings"]
                    if f["kind"] == "task" and "stalled" in f["name"]]
        assert findings, doc["findings"]
        f = findings[0]
        assert f["stage"] == "lease_wait", f
        assert f["age_s"] >= 1.0 and f["age_s"] > f["threshold_s"], f
        assert f["process"] == "driver", f
        assert f["trace_id"], f
        assert f.get("stacks", {}).get("threads"), \
            "finding should carry the owning process's thread stacks"

        # the out-of-process surfaces see driver-owned state too: the
        # raylet fans out to connected drivers over the duplex conn, so
        # `ray-tpu state tasks` / `ray-tpu doctor` (no driver runtime)
        # still name a task wedged in the owner's submitted table
        from ray_tpu import api as _api

        rpc_snap = debug_state.collect_via_rpc(
            _api._global_node.gcs_address)
        rpc_rows = debug_state.flatten(rpc_snap, "tasks")
        assert any(r.get("stage") == "lease_wait"
                   and "stalled" in str(r.get("name"))
                   and "/driver-" in str(r.get("process"))
                   for r in rpc_rows), rpc_rows

        # satellite: one STALL_DETECTED warning event, deduped per trace
        def stall_events():
            return [e for e in ray_tpu.cluster_events(severity="WARNING")
                    if e.get("label") == "STALL_DETECTED"
                    and (e.get("custom_fields") or {}).get("trace_id")
                    == f["trace_id"]]

        deadline = time.monotonic() + scale_timeout(10)
        while time.monotonic() < deadline and not stall_events():
            time.sleep(0.2)
        first = stall_events()
        assert len(first) == 1, first
        ray_tpu.doctor(floor_s=1.0, p99_factor=0.0)  # same stall again
        time.sleep(0.5)
        assert len(stall_events()) == 1, "stall event was not deduped"
    finally:
        fp.arm_cluster("")
        ray_tpu.set_trace_sampling(0.01)
    assert ray_tpu.get(ref, timeout=scale_timeout(60)) == 2


def test_diagnose_threshold_math():
    """Pure-function check: the stall threshold is max(floor, K*p99) of
    the stage's histogram, merged across process snapshots."""
    hist = {"type": "histogram", "boundaries": [0.1, 1.0],
            "counts": [98, 2, 0], "sum": 5.0, "count": 100}
    metrics = {"gcs": {}, "raylets": {"n1": {
        "core.task_lease_wait_s": hist}}}
    snapshot = {"driver": {
        "role": "driver", "pid": 1, "address": "x",
        "tasks": [
            {"task_id": "aa", "name": "slow", "stage": "lease_wait",
             "age_s": 4.0, "trace_id": "tt"},
            {"task_id": "bb", "name": "fastish", "stage": "lease_wait",
             "age_s": 2.0, "trace_id": ""},
        ]}}
    # p99 of the histogram = 1.0 (second bucket boundary); K=3 -> 3.0:
    # only the 4s task is stalled. With K=0 the 1s floor flags both.
    findings = debug_state.diagnose(snapshot, metrics, floor_s=1.0,
                                    p99_factor=3.0)
    assert [f["id"] for f in findings] == ["aa"]
    assert findings[0]["threshold_s"] == 3.0
    assert findings[0]["trace_id"] == "tt"
    both = debug_state.diagnose(snapshot, metrics, floor_s=1.0,
                                p99_factor=0.0)
    assert {f["id"] for f in both} == {"aa", "bb"}
    # findings sort oldest-first
    assert both[0]["id"] == "aa"


# ---------------------------------------------------------------------------
# collective group timeout carries a state snapshot
# ---------------------------------------------------------------------------


@ray_tpu.remote
class StallGroupWorker:
    def init_group(self, world, rank, name, timeout, multihost_name=None):
        from ray_tpu import collective as col

        if multihost_name is not None:
            from ray_tpu.parallel import multihost

            multihost.initialize(multihost_name, world, rank)
        col.init_collective_group(world, rank, backend="host",
                                  group_name=name, timeout=timeout)
        self.name = name
        self.rank = rank
        return rank

    def arm(self, point, action, **kw):
        from ray_tpu._private import failpoints

        failpoints.arm(point, action, **kw)
        return True

    def allreduce_snapshot(self, transport, nbytes):
        """Run one allreduce; on TimeoutError return the attached state
        snapshot (the acceptance artifact)."""
        from ray_tpu.collective import collective as C

        group = C._manager.get_group(self.name)
        group.force_transport = transport
        arr = np.ones(nbytes // 4, np.float32)
        t0 = time.monotonic()
        try:
            group.allreduce(arr)
            return {"ok": True, "elapsed": time.monotonic() - t0}
        except TimeoutError as e:
            return {"ok": False, "elapsed": time.monotonic() - t0,
                    "snapshot": getattr(e, "state_snapshot", None),
                    "error": str(e)}

    def group_debug(self):
        from ray_tpu.collective import collective as C

        return C._manager.debug_state()

    def destroy(self):
        from ray_tpu import collective as col

        col.destroy_collective_group(self.name)
        return True


def test_device_dispatch_kill_timeout_carries_snapshot(ray_start_regular):
    """Acceptance: a collective.device_dispatch-killed group leaves
    every survivor with a TimeoutError that CARRIES a state snapshot
    naming the wedged op (+ phase, rank, age) — the hang is
    self-describing, no reproduction run needed."""
    timeout = scale_timeout(8)
    world = 3
    workers = [StallGroupWorker.remote() for _ in range(world)]
    ray_tpu.get([w.init_group.remote(world, i, "g_state_dev", timeout,
                                     "statedev")
                 for i, w in enumerate(workers)],
                timeout=scale_timeout(240))
    # registry rows answer before any op
    rows = ray_tpu.get(workers[0].group_debug.remote(), timeout=60)
    assert rows and rows[0]["group"] == "g_state_dev"
    assert rows[0]["phase"] == "idle" and rows[0]["op"] == ""

    # rank 0 hosts the jax.distributed coordinator — kill a client rank
    victim = workers[-1]
    ray_tpu.get(victim.arm.remote("collective.device_dispatch", "exit",
                                  nth=1), timeout=60)
    refs = [w.allreduce_snapshot.remote("device", 1 << 20)
            for w in workers]
    outs = []
    for r in refs:
        try:
            outs.append(ray_tpu.get(r, timeout=scale_timeout(120)))
        except Exception:
            outs.append({"ok": False, "died": True})
    survivors = outs[:-1]
    assert all(not o["ok"] for o in survivors), outs
    for out in survivors:
        if out.get("died"):
            continue
        snap = out.get("snapshot")
        assert snap is not None, \
            f"timeout error carried no state snapshot: {out}"
        assert snap["op"] == "allreduce", snap
        assert snap["group"] == "g_state_dev", snap
        assert snap["phase"] != "idle", snap
        assert snap["age_s"] >= 0.0 and "rank" in snap, snap
    ray_tpu.get([w.destroy.remote() for w in workers[:-1]],
                timeout=scale_timeout(60))
    for w in workers[:-1]:
        ray_tpu.kill(w)


# ---------------------------------------------------------------------------
# CLI + stacks surfaces
# ---------------------------------------------------------------------------


def test_cli_state_stack_doctor(ray_start_regular, capsys):
    from ray_tpu import api as _api
    from ray_tpu.scripts import cli

    addr = _api._global_node.gcs_address

    @ray_tpu.remote
    def snooze(sec):
        time.sleep(sec)
        return 1

    ref = snooze.remote(scale_timeout(6))

    def executing_for():
        snap = debug_state.collect_via_rpc(addr)
        return max((task["age_s"]
                    for _, proc in debug_state.iter_processes(snap)
                    if proc.get("role") == "worker"
                    for task in proc.get("executing") or ()), default=0.0)

    # the doctor below flags what has been executing for its floor of
    # 0.5 s: wait for that, not for a guess at how long a worker takes to
    # start (a sleep of 1.5 s here left 0.0 to 0.4 s over the floor)
    deadline = time.monotonic() + scale_timeout(30)
    while executing_for() < 0.6:
        assert time.monotonic() < deadline, "snooze never reached a worker"
        time.sleep(0.05)

    assert cli.main(["state", "--address", addr]) == 0
    out = capsys.readouterr().out
    assert "gcs:" in out and "/raylet" in out

    assert cli.main(["state", "tasks", "--address", addr]) == 0
    out = capsys.readouterr().out
    assert "snooze" in out, out

    # stack of the worker executing the sleeping task, found by pid
    snap = debug_state.collect_via_rpc(addr)
    worker_pid = None
    for label, proc in debug_state.iter_processes(snap):
        if proc.get("role") == "worker" and proc.get("executing"):
            worker_pid = proc["pid"]
            break
    assert worker_pid is not None, "no executing worker in snapshot"
    assert cli.main(["stack", str(worker_pid), "--address", addr]) == 0
    out = capsys.readouterr().out
    assert "snooze" in out or "time.sleep" in out, out

    assert cli.main(["stack", "gcs", "--address", addr]) == 0
    capsys.readouterr()

    # doctor CLI: exec stage stalls need to outlive the floor to flag;
    # with a huge floor nothing is stalled -> rc 0
    assert cli.main(["doctor", "--address", addr,
                     "--floor", "9999"]) == 0
    out = capsys.readouterr().out
    assert "no stalls" in out
    rc = cli.main(["doctor", "--address", addr, "--floor", "0.5",
                   "--p99-factor", "0.0", "--stacks"])
    out = capsys.readouterr().out
    assert rc == 1 and "STALLED" in out, out
    assert ray_tpu.get(ref, timeout=scale_timeout(60)) == 1


def test_debug_stacks_local_and_remote(ray_start_regular):
    local = ray_tpu.debug_stacks()
    assert local["pid"] == os.getpid()
    assert any(t["name"] == "MainThread" for t in local["threads"])
    snap = ray_tpu.cluster_state()
    (node,) = snap["nodes"].values()
    remote = ray_tpu.debug_stacks(node["address"])
    assert remote["pid"] != os.getpid()
    assert remote["threads"]


# ---------------------------------------------------------------------------
# serve + collective rows ride the same plane
# ---------------------------------------------------------------------------


def test_state_covers_serve_components(ray_start_regular):
    from ray_tpu import serve

    client = serve.start(http=True)
    try:
        client.create_backend("st_echo", lambda x=None: "ok")
        client.create_endpoint("st_ep", backend="st_echo",
                               route="/st_ep")
        handle = client.get_handle("st_ep")
        assert ray_tpu.get(handle.remote(None),
                           timeout=scale_timeout(60)) == "ok"
        snap = ray_tpu.cluster_state()
        comps = []
        for _, proc in debug_state.iter_processes(snap):
            comp = proc.get("component")
            if isinstance(comp, dict) and comp.get("kind"):
                comps.append(comp)
        kinds = {c["kind"] for c in comps}
        assert "serve-controller" in kinds, kinds
        assert "serve-proxy" in kinds, kinds
        assert "serve-replica" in kinds, kinds
        ctrl = next(c for c in comps if c["kind"] == "serve-controller")
        assert "st_echo" in ctrl["backends"]
        # the driver's own handle router reports through the registry
        assert any(r["endpoint"] == "st_ep"
                   for r in snap["driver"].get("routers", []))
    finally:
        serve.shutdown()


# ---------------------------------------------------------------------------
# microbench gate: state collection armed at the 1s doctor cadence
# ---------------------------------------------------------------------------


def test_microbench_state_overhead_gate():
    """Gate on the recorded interleaved state-on/off A/B rows: >5%
    throughput regression with the doctor armed at its 1s cadence on
    the tasks-sync or serve-http row fails tier-1 (reads
    MICROBENCH.json — deterministic, no benchmarking in CI; same gate
    style as the PR 6 tracing gate)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    doc = json.load(open(os.path.join(root, "MICROBENCH.json")))
    rows = {r["name"]: r for r in doc["results"]}
    for case in ("state A/B tasks sync", "state A/B serve http qps"):
        on_name, off_name = case, f"{case} (state-off control)"
        assert on_name in rows and off_name in rows, (
            f"missing state A/B row {case!r} in MICROBENCH.json")
        on, off = rows[on_name], rows[off_name]
        if on.get("high_variance") or off.get("high_variance"):
            continue  # window noise, not signal
        assert on["per_second"] >= 0.95 * off["per_second"], (
            f"{case}: state-on {on['per_second']:.1f}/s is >5% below "
            f"state-off {off['per_second']:.1f}/s")
