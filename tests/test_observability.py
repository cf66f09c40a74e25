"""Metrics + profiling/timeline (reference: src/ray/stats/metric.h,
src/ray/core_worker/profiling.h:28, python/ray/state.py:946 timeline)."""

import time

import pytest

import ray_tpu
from ray_tpu._private import stats


def test_stats_primitives():
    c = stats.Count("t.count")
    c.inc()
    c.inc(2.5)
    g = stats.Gauge("t.gauge")
    g.set(7)
    h = stats.Histogram("t.hist", boundaries=[1.0, 10.0])
    for v in (0.5, 5.0, 50.0, 5.0):
        h.observe(v)
    snap = stats.snapshot()
    assert snap["t.count"]["value"] == 3.5
    assert snap["t.gauge"]["value"] == 7
    assert snap["t.hist"]["counts"] == [1, 2, 1]
    assert snap["t.hist"]["count"] == 4


def test_cluster_metrics_and_timeline(ray_start_regular):
    @ray_tpu.remote
    def traced_work(x):
        time.sleep(0.05)
        return x

    assert ray_tpu.get([traced_work.remote(i) for i in range(4)],
                       timeout=60) == [0, 1, 2, 3]

    metrics = ray_tpu.cluster_metrics()
    assert "gcs" in metrics and metrics["gcs"]["gcs.nodes_alive"][
        "value"] == 1
    (node_snap,) = metrics["raylets"].values()
    assert node_snap["raylet.leases_granted_total"]["value"] >= 1
    assert node_snap["raylet.workers_started_total"]["value"] >= 1
    assert node_snap["raylet.num_workers"]["value"] >= 1

    # Profile flush runs every ~2s in each worker; poll the timeline until
    # the task spans land.
    deadline = time.monotonic() + 15
    names = set()
    while time.monotonic() < deadline:
        trace = ray_tpu.timeline()
        names = {ev["name"] for ev in trace}
        if any("traced_work" in n for n in names):
            break
        time.sleep(0.5)
    assert any("traced_work" in n for n in names), (
        f"no task span in timeline: {names}")
    ev = next(e for e in ray_tpu.timeline()
              if "traced_work" in e["name"])
    assert ev["ph"] == "X" and ev["dur"] >= 0.04 * 1e6


def test_timeline_file_export(ray_start_regular, tmp_path):
    @ray_tpu.remote
    def f():
        return 1

    ray_tpu.get(f.remote(), timeout=60)
    out = tmp_path / "timeline.json"
    time.sleep(2.5)  # allow one flush cycle
    ray_tpu.timeline(str(out))
    import json

    data = json.loads(out.read_text())
    assert isinstance(data, list)


def test_structured_events(ray_start_regular):
    """RAY_EVENT analog: lifecycle transitions produce structured events
    readable through the API, and worker crashes surface as WORKER_DIED
    (reference: src/ray/util/event.h + dashboard event view)."""
    import time

    import ray_tpu

    events = ray_tpu.cluster_events()
    assert any(e["label"] == "NODE_ADDED" for e in events), events

    # crash a worker: must yield a WORKER_DIED ERROR event
    @ray_tpu.remote
    class Bomb:
        def go(self):
            import os

            os._exit(1)

    b = Bomb.remote()
    with pytest.raises(Exception):
        ray_tpu.get(b.go.remote(), timeout=30)
    deadline = time.monotonic() + 10
    seen = []
    while time.monotonic() < deadline:
        seen = ray_tpu.cluster_events(severity="ERROR")
        if any(e["label"] == "WORKER_DIED" for e in seen):
            break
        time.sleep(0.2)
    assert any(e["label"] == "WORKER_DIED" for e in seen), seen
    # actor death is also evented
    assert any(e["label"] == "ACTOR_DEAD" for e in
               ray_tpu.cluster_events()), "no ACTOR_DEAD event"


def test_event_log_files(tmp_path):
    from ray_tpu._private import events as ev

    ev.init_events("TEST", "t1", str(tmp_path))
    ev.report_event(ev.WARNING, "SOMETHING", "hello", detail=42)
    out = ev.read_events(str(tmp_path))
    assert len(out) == 1
    e = out[0]
    assert (e["severity"], e["label"], e["message"]) == (
        "WARNING", "SOMETHING", "hello")
    assert e["custom_fields"] == {"detail": 42}
    assert e["source_type"] == "TEST"
    # reset so other tests' global state is clean
    ev.init_events("unknown", "", None)


# ---------------------------------------------------------------------------
# distributed tracing (tracing.py): causally-linked spans across every hop
# ---------------------------------------------------------------------------


def _wait_spans(pred, timeout=20.0):
    """Poll the GCS trace table until `pred(spans)` returns truthy
    (spans flush on the ~2s cadence, sooner after task completion)."""
    deadline = time.monotonic() + timeout
    spans = []
    while time.monotonic() < deadline:
        spans = ray_tpu.trace_spans()
        got = pred(spans)
        if got:
            return got
        time.sleep(0.25)
    raise AssertionError(
        f"trace spans never matched; have "
        f"{[(s['event_type'], s['component_type']) for s in spans]}")


def _tree_of(spans, tid):
    return [s for s in spans if s["extra_data"].get("tid") == tid]


def _assert_connected(tree):
    """Every span's parent link resolves inside the tree, and exactly
    one root exists — i.e. ONE causally-connected tree, not islands."""
    sids = {s["extra_data"]["sid"] for s in tree}
    roots = [s for s in tree
             if s["extra_data"].get("psid", "") not in sids]
    assert len(roots) == 1, (
        f"expected one root, got {[(r['event_type']) for r in roots]}")
    return roots[0]


def test_task_trace_tree_spans_three_processes(ray_start_regular):
    """A sampled multi-arg remote task yields ONE connected span tree
    crossing driver -> raylet -> worker, exported to Perfetto JSON with
    cross-process flow arrows."""
    ray_tpu.set_trace_sampling(1.0)
    try:
        @ray_tpu.remote
        def combine(a, b, c):
            return a + b + c

        assert ray_tpu.get(combine.remote(1, 2, 3), timeout=60) == 6

        def have_tree(spans):
            for s in spans:
                if (s["event_type"] == "task.e2e"
                        and s["extra_data"].get("name", "").endswith(
                            "combine")):
                    tree = _tree_of(spans, s["extra_data"]["tid"])
                    procs = {(t["component_type"], t["component_id"])
                             for t in tree}
                    if len(procs) >= 3:
                        return tree
            return None

        tree = _wait_spans(have_tree)
        root = _assert_connected(tree)
        assert root["event_type"] == "task.e2e"
        kinds = {t["component_type"] for t in tree}
        assert {"driver", "raylet", "worker"} <= kinds, kinds
        # every hop of the round trip is represented
        names = {t["event_type"] for t in tree}
        assert {"task.e2e", "task.queue_wait", "raylet.lease",
                "task"} <= names, names

        # Perfetto export: the spans appear with flow-link ('s'/'f')
        # pairs keyed by child span id
        trace = ray_tpu.timeline()
        sids = {t["extra_data"]["sid"] for t in tree}
        starts = {e["id"] for e in trace if e.get("ph") == "s"}
        finishes = {e["id"] for e in trace if e.get("ph") == "f"}
        linked = sids & starts & finishes
        assert linked, "no flow links for the task tree in the export"
    finally:
        ray_tpu.set_trace_sampling(0.01)


def test_serve_http_trace_tree_spans_three_processes(ray_start_regular):
    """One HTTP request through proxy -> router -> replica -> nested
    task = ONE connected tree spanning >=3 processes (the composition
    pattern: a replica fanning out to a downstream remote function)."""
    import urllib.request

    from ray_tpu import serve

    ray_tpu.set_trace_sampling(1.0)
    client = serve.start()
    try:
        @ray_tpu.remote
        def embed(x):
            return {"embedded": x}

        def model(data=None):
            import ray_tpu as rt

            return rt.get(embed.remote(7), timeout=30)

        client.create_backend("model", model)
        client.create_endpoint("model", backend="model", route="/model",
                               methods=["GET"])
        port = client.enable_http()
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/model", timeout=30) as r:
            assert b"embedded" in r.read()

        def have_tree(spans):
            for s in spans:
                if s["event_type"] == "http.request":
                    tree = _tree_of(spans, s["extra_data"]["tid"])
                    procs = {(t["component_type"], t["component_id"])
                             for t in tree}
                    if len(procs) >= 3:
                        return tree
            return None

        tree = _wait_spans(have_tree)
        root = _assert_connected(tree)
        assert root["event_type"] == "http.request"
        names = {t["event_type"] for t in tree}
        assert "serve.router_queue" in names, names
        procs = {(t["component_type"], t["component_id"]) for t in tree}
        assert len(procs) >= 3, procs
        # the filtered query surface returns exactly this tree
        tid = root["extra_data"]["tid"]
        only = ray_tpu.trace_spans(tid)
        assert {s["extra_data"]["sid"] for s in only} == {
            s["extra_data"]["sid"] for s in tree}
    finally:
        client.shutdown()
        ray_tpu.set_trace_sampling(0.01)


def test_trace_sampling_live_override(ray_start_regular):
    """set_trace_sampling rides the KV+pubsub plane: rate 0 stops new
    roots cluster-wide, rate 1.0 (set LIVE, no restarts) traces the next
    call."""
    ray_tpu.set_trace_sampling(0.0)
    try:
        @ray_tpu.remote
        def quiet():
            return 1

        @ray_tpu.remote
        def loud():
            return 2

        assert ray_tpu.get(quiet.remote(), timeout=60) == 1
        time.sleep(2.5)  # a flush cycle
        assert not any(
            s["extra_data"].get("name", "").endswith("quiet")
            for s in ray_tpu.trace_spans()), "rate 0 still minted a root"

        ray_tpu.set_trace_sampling(1.0)
        assert ray_tpu.get(loud.remote(), timeout=60) == 2
        _wait_spans(lambda spans: [
            s for s in spans
            if s["extra_data"].get("name", "").endswith("loud")])
    finally:
        ray_tpu.set_trace_sampling(0.01)


# ---------------------------------------------------------------------------
# metrics time series (GCS ring) + per-hop histograms
# ---------------------------------------------------------------------------


def test_metrics_history_accumulates_samples(ray_start_regular):
    """A counter incremented between pushes shows >=2 distinct
    timestamped samples in api.cluster_metrics(history=...)."""
    c = stats.Count("obs_test.history_counter")
    c.inc(5)

    def series():
        hist = ray_tpu.cluster_metrics(history=10)
        for source, rings in hist.items():
            if "driver" in source and "obs_test.history_counter" in rings:
                return rings["obs_test.history_counter"]
        return []

    deadline = time.monotonic() + 15
    while time.monotonic() < deadline and len(series()) < 1:
        time.sleep(0.3)
    c.inc(2)
    while time.monotonic() < deadline:
        ss = series()
        if len(ss) >= 2 and ss[-1][1] > ss[0][1]:
            break
        time.sleep(0.3)
    ss = series()
    assert len(ss) >= 2, f"history never got 2 samples: {ss}"
    ts = [t for t, _ in ss]
    assert ts == sorted(ts) and ts[0] < ts[-1]
    assert ss[0][1] == 5.0 and ss[-1][1] == 7.0, ss


def test_per_hop_histograms_feed_history(ray_start_regular):
    """The task-path latency histograms (always on, no sampling needed)
    land in the time-series ring as .count/.sum/.p99 scalar series —
    the feed the serve autoscaler consumes."""
    @ray_tpu.remote
    def tick():
        return 1

    assert ray_tpu.get([tick.remote() for _ in range(5)],
                       timeout=60) == [1] * 5
    snap = stats.snapshot()
    assert snap["core.task_e2e_s"]["count"] >= 5
    assert snap["core.task_queue_wait_s"]["count"] >= 5
    p99 = stats.percentile(snap["core.task_e2e_s"], 0.99)
    assert p99 > 0

    deadline = time.monotonic() + 15
    found = {}
    while time.monotonic() < deadline:
        hist = ray_tpu.cluster_metrics(history=5)
        for source, rings in hist.items():
            if "driver" in source and "core.task_e2e_s.p99" in rings:
                found = rings
        if found:
            break
        time.sleep(0.3)
    assert "core.task_e2e_s.count" in found and \
        "core.task_e2e_s.sum" in found, sorted(found)[:20]


def test_stats_snapshot_lock_consistency():
    """Hammer test for the satellite fix: Histogram.snapshot() and
    Gauge.set() take the metric lock, so a snapshot can never observe a
    torn (counts, sum, n) triple mid-observe()."""
    import threading

    h = stats.Histogram("obs_test.hammer_hist",
                        boundaries=[0.001, 0.01, 0.1, 1.0])
    g = stats.Gauge("obs_test.hammer_gauge")
    stop = threading.Event()

    def pound():
        while not stop.is_set():
            h.observe(0.005)
            g.set(3.0)
            g.add(1.0)

    threads = [threading.Thread(target=pound, daemon=True)
               for _ in range(3)]
    for t in threads:
        t.start()
    try:
        for _ in range(400):
            snap = h.snapshot()
            # invariants a torn read breaks: bucket counts sum to n,
            # and every observation contributed exactly 0.005 to sum
            assert sum(snap["counts"]) == snap["count"]
            assert abs(snap["sum"] - snap["count"] * 0.005) < 1e-9, snap
            gv = g.snapshot()["value"]
            assert gv >= 3.0 or gv == 0.0
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=5)


def test_profile_buffer_requeue_bounded_and_counted():
    """Satellite: a failed GCS flush requeues the drained batch at the
    front (retried next cycle); only bound-evicted events are lost, and
    those are counted in profiling.events_dropped_total."""
    from ray_tpu._private import profiling

    buf = profiling.ProfileBuffer("test", maxlen=4)
    base = profiling.M_EVENTS_DROPPED.snapshot()["value"]
    for i in range(3):
        buf.record("e", float(i), float(i) + 1, {"i": i})
    events = buf.drain()
    assert len(buf) == 0 and len(events) == 3
    # failed flush: everything fits back, in original order, ahead of
    # newer events
    assert buf.requeue(events) == 0
    buf.record("tail", 9.0, 10.0)
    replay = buf.drain()
    assert [e["extra_data"].get("i") for e in replay] == [0, 1, 2, None]
    # overflowing requeue keeps the NEWEST events and counts the drops
    big = [{"event_type": "x", "start_time": float(i),
            "end_time": float(i) + 1, "extra_data": {"i": i}}
           for i in range(6)]
    assert buf.requeue(big) == 2
    kept = buf.drain()
    assert [e["extra_data"]["i"] for e in kept] == [2, 3, 4, 5]
    assert profiling.M_EVENTS_DROPPED.snapshot()["value"] - base == 2


# ---------------------------------------------------------------------------
# events.py: forwarder -> GCS ring -> API round trip + degradation
# ---------------------------------------------------------------------------


def test_event_forwarder_roundtrip_and_severity_filter(
        ray_start_regular, tmp_path):
    """Satellite: an event reported with a GCS forwarder lands in the
    cluster ring (readable via cluster_events and /api/events), severity
    filtering works, and a DEAD forwarder degrades to local-file-only
    without raising in the reporting process."""
    from ray_tpu._private import events as ev
    from ray_tpu._private import global_state

    cw = global_state.require_core_worker()

    def forward(event):
        cw._io.run(cw.gcs.call("report_event", event))

    ev.init_events("TESTSRC", "t1", str(tmp_path), forward=forward)
    try:
        ev.report_event(ev.ERROR, "OBS_TEST_ERR", "boom", k=1)
        ev.report_event(ev.INFO, "OBS_TEST_INFO", "fine")

        errs = ray_tpu.cluster_events(severity="ERROR")
        assert any(e["label"] == "OBS_TEST_ERR" for e in errs), errs
        assert not any(e["label"] == "OBS_TEST_INFO" for e in errs)
        assert any(e["label"] == "OBS_TEST_INFO"
                   for e in ray_tpu.cluster_events())
        # forwarded copy preserved source identity + custom fields
        mine = next(e for e in errs if e["label"] == "OBS_TEST_ERR")
        assert mine["source_type"] == "TESTSRC"
        assert mine["custom_fields"] == {"k": 1}

        # dead forwarder: must NOT raise, must still write the file
        def dead(event):
            raise ConnectionError("gcs unreachable")

        ev.init_events("TESTDEAD", "t2", str(tmp_path), forward=dead)
        ev.report_event(ev.WARNING, "LOCAL_ONLY", "still recorded")
        local = ev.read_events(str(tmp_path), "TESTDEAD")
        assert len(local) == 1 and local[0]["label"] == "LOCAL_ONLY"
        assert not any(e["label"] == "LOCAL_ONLY"
                       for e in ray_tpu.cluster_events())
    finally:
        ev.init_events("unknown", "", None)


# ---------------------------------------------------------------------------
# CI gates: metric-name drift + microbench tracing overhead
# ---------------------------------------------------------------------------


def _referenced_metric_names() -> set[str]:
    """Metric names the docs/dashboard promise: every `_total`-suffixed
    backticked token anywhere in ARCHITECTURE.md, plus the first
    backticked token of each row of the Observability section's metrics
    table (marked `<!-- metrics-registry-check -->`)."""
    import os
    import re

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    text = open(os.path.join(root, "ARCHITECTURE.md")).read()
    names = set(re.findall(r"`([a-z]+\.[a-z0-9_.]*_total)`", text))
    marker = "<!-- metrics-registry-check -->"
    if marker in text:
        section = text.split(marker, 1)[1]
        for line in section.splitlines():
            if line.startswith("<!-- end"):
                break
            m = re.match(r"\|\s*`([a-z]+\.[a-z0-9_.]+)`", line)
            if m:
                names.add(m.group(1))
    return {re.sub(r"\.(count|sum|p99)$", "", n) for n in names}


def test_metric_name_drift_gate(ray_start_regular):
    """Tier-1 drift gate (satellite): every metric name referenced in
    ARCHITECTURE.md exists in the live registry — a renamed or deleted
    counter fails here instead of silently breaking dashboards."""
    # register every metric-bearing module + exercise the task path so
    # instance metrics exist
    import ray_tpu.serve.http_proxy   # noqa: F401
    import ray_tpu.serve.replica      # noqa: F401
    import ray_tpu.serve.router       # noqa: F401
    from ray_tpu._private import profiling  # noqa: F401
    from ray_tpu.collective import metrics as _cmetrics  # noqa: F401
    from ray_tpu.gcs import shard           # noqa: F401
    from ray_tpu.raylet import transfer     # noqa: F401
    from ray_tpu.train import metrics as _train_metrics  # noqa: F401

    @ray_tpu.remote
    def poke():
        return 1

    assert ray_tpu.get(poke.remote(), timeout=60) == 1

    live = set(stats.snapshot())
    cm = ray_tpu.cluster_metrics()
    live |= set(cm["gcs"])
    for snap in cm["raylets"].values():
        live |= set(snap)

    referenced = _referenced_metric_names()
    assert referenced, "no metric names found in ARCHITECTURE.md"
    missing = sorted(referenced - live)
    assert not missing, (
        f"ARCHITECTURE.md references metrics missing from the live "
        f"registry (renamed/deleted?): {missing}")


def test_microbench_tracing_overhead_gate():
    """Gate on the recorded interleaved tracing-on/off A/B rows: >5%
    throughput regression with default sampling on the tasks-sync or
    serve-http row fails tier-1 (reads MICROBENCH.json — deterministic,
    no benchmarking in CI)."""
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    doc = json.load(open(os.path.join(root, "MICROBENCH.json")))
    rows = {r["name"]: r for r in doc["results"]}
    for case in ("tracing A/B tasks sync", "tracing A/B serve http qps"):
        on_name, off_name = case, f"{case} (tracing-off control)"
        assert on_name in rows and off_name in rows, (
            f"missing tracing A/B row {case!r} in MICROBENCH.json")
        on, off = rows[on_name], rows[off_name]
        if on.get("high_variance") or off.get("high_variance"):
            continue  # window noise, not signal (see timeit docstring)
        assert on["per_second"] >= 0.95 * off["per_second"], (
            f"{case}: tracing-on {on['per_second']:.1f}/s is >5% below "
            f"tracing-off {off['per_second']:.1f}/s")


# ---------------------------------------------------------------------------
# failure injection through the new seams
# ---------------------------------------------------------------------------


def test_trace_flush_failure_bounded_and_retried(ray_start_regular):
    """trace.flush failpoint (models an unreachable GCS): flushes fail
    silently-but-typed, the local buffer stays bounded (drops counted),
    tasks keep completing, and disarming lets the retained spans reach
    the GCS on the next cycle."""
    from ray_tpu._private import failpoints as fp
    from ray_tpu._private import global_state

    ray_tpu.set_trace_sampling(1.0)
    try:
        fp.configure("trace.flush=raise")

        @ray_tpu.remote
        def survivor():
            return 1

        for _ in range(3):
            assert ray_tpu.get(survivor.remote(), timeout=60) == 1
        time.sleep(2.5)  # let a flush cycle fail
        cw = global_state.require_core_worker()
        assert 0 < len(cw._profile) <= 20_000
        assert not any(
            s["component_type"] == "driver"
            and s["extra_data"].get("name", "").endswith("survivor")
            for s in ray_tpu.trace_spans()), \
            "driver flush should have been failing"

        fp.configure("")  # GCS "reachable" again -> requeued batch lands
        _wait_spans(lambda spans: [
            s for s in spans
            if s["component_type"] == "driver"
            and s["extra_data"].get("name", "").endswith("survivor")])
    finally:
        fp.configure("")
        ray_tpu.set_trace_sampling(0.01)


def test_gcs_trace_table_apply_failpoint(ray_start_regular):
    """gcs.trace_table.apply=raise: the GCS drops the batch with a typed
    counter instead of crashing; client-side flushing is unaffected."""
    from ray_tpu._private import failpoints as fp

    ray_tpu.set_trace_sampling(1.0)
    try:
        fp.arm_cluster("gcs.trace_table.apply=raise")

        @ray_tpu.remote
        def dropped():
            return 1

        assert ray_tpu.get(dropped.remote(), timeout=60) == 1
        time.sleep(2.5)
        cm = ray_tpu.cluster_metrics()
        fp.arm_cluster("")
        assert cm["gcs"].get("gcs.trace_apply_failures_total",
                             {}).get("value", 0) >= 1
        # cluster recovered: fresh spans apply again
        @ray_tpu.remote
        def landed():
            return 2

        assert ray_tpu.get(landed.remote(), timeout=60) == 2
        _wait_spans(lambda spans: [
            s for s in spans
            if s["extra_data"].get("name", "").endswith("landed")])
    finally:
        fp.arm_cluster("")
        ray_tpu.set_trace_sampling(0.01)


def test_metrics_history_lossy_restart_contract(ray_start_regular):
    """Satellite: the GCS metrics-history and trace rings are DIRECTOR
    MEMORY ONLY by contract (ARCHITECTURE.md "State introspection &
    stall doctor" — the jobs/actors/KV tables persist via WAL+journal,
    the observability rings deliberately do not). A director restart
    therefore resets them; consumers detect the reset via the history
    epoch (`get_metrics_history` with meta=True), which `ray-tpu top`
    renders as a visible "history reset" marker instead of silently
    splicing fresh samples onto the old view."""
    from tests.conftest import scale_timeout

    from ray_tpu import api as _api
    from ray_tpu._private import global_state

    node = _api._global_node
    cw = global_state.require_core_worker()

    def history(meta=False):
        return cw._io.run(cw.gcs.call(
            "get_metrics_history", {"samples": 0, "meta": meta}),
            timeout=10)

    # let at least one sample land (raylet heartbeat piggyback, ~2s)
    deadline = time.monotonic() + scale_timeout(30)
    while time.monotonic() < deadline and not history():
        time.sleep(0.5)
    reply = history(meta=True)
    assert "meta" in reply and reply["series"], reply
    epoch0 = reply["meta"]["started_at"]
    # meta=False preserves the pre-epoch wire shape for old consumers
    assert "meta" not in history()

    old_pid = next(s.proc.pid for s in node.processes
                   if s.name == "gcs_server")
    node.kill_gcs()
    deadline = time.monotonic() + scale_timeout(40)
    while time.monotonic() < deadline:
        gcs = next((s for s in node.processes
                    if s.name == "gcs_server"), None)
        if gcs is not None and gcs.alive() and gcs.proc.pid != old_pid:
            break
        time.sleep(0.2)
    else:
        raise TimeoutError("GCS was not restarted")

    deadline = time.monotonic() + scale_timeout(30)
    while True:
        try:
            reply2 = history(meta=True)
            break
        except Exception:
            if time.monotonic() > deadline:
                raise
            time.sleep(0.5)
    epoch1 = reply2["meta"]["started_at"]
    assert epoch1 != epoch0, "history epoch must change across a restart"
    # every surviving sample was collected AFTER the restart: the rings
    # were reset, not spliced (the lossy contract)
    for source, rings in reply2["series"].items():
        for name, series in rings.items():
            assert all(ts >= epoch1 - 1.0 for ts, _ in series), (
                f"pre-restart sample survived in {source}/{name}")


@pytest.mark.chaos
def test_chaos_gcs_killed_mid_flush(ray_start_regular):
    """Seeded chaos case (satellite): the GCS dies while traced work is
    flushing spans + metrics at 100% sampling AND the continuous
    profiler is flushing sample windows at 100 Hz. Required: no hang,
    no unbounded buffer growth on either plane (failed sample flushes
    merge back into the bounded table — typed degradation, drops
    counted), and full recovery once the node monitor restarts the GCS
    (spans AND samples flow into the fresh rings)."""
    from ray_tpu import api as _api
    from ray_tpu._private import global_state
    from ray_tpu._private import sampling_profiler as sp

    node = _api._global_node
    ray_tpu.set_trace_sampling(1.0)
    ray_tpu.set_profiling(100.0)
    try:
        @ray_tpu.remote
        def work(i):
            return i

        assert ray_tpu.get([work.remote(i) for i in range(10)],
                           timeout=60) == list(range(10))
        old_pid = next(s.proc.pid for s in node.processes
                       if s.name == "gcs_server")
        node.kill_gcs()
        # GCS down: tasks must still complete (driver->raylet->worker
        # path does not touch it) and flush failures must stay bounded
        for i in range(10):
            assert ray_tpu.get(work.remote(i), timeout=60) == i
        cw = global_state.require_core_worker()
        assert len(cw._profile) <= 20_000
        time.sleep(2.5)  # at least one failed sample-flush cycle
        prof = sp.get_profiler()
        assert len(prof) <= prof.max_stacks
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            gcs = next((s for s in node.processes
                        if s.name == "gcs_server"), None)
            if gcs is not None and gcs.alive() and gcs.proc.pid != old_pid:
                break
            time.sleep(0.2)
        else:
            raise TimeoutError("GCS was not restarted")

        @ray_tpu.remote
        def after():
            return "back"

        assert ray_tpu.get(after.remote(), timeout=60) == "back"
        # spans recorded after the restart reach the (fresh) trace table
        _wait_spans(lambda spans: [
            s for s in spans
            if s["extra_data"].get("name", "").endswith("after")],
            timeout=30)
        # and profiler samples refill the fresh profile ring from every
        # process class (driver flush loop, raylet heartbeat, GCS self)
        deadline = time.monotonic() + 30
        classes: set = set()
        while time.monotonic() < deadline:
            classes = set(ray_tpu.profile(seconds=None)["components"])
            if {"driver", "raylet", "gcs"} <= classes:
                break
            time.sleep(0.5)
        assert {"driver", "raylet", "gcs"} <= classes, classes
    finally:
        ray_tpu.set_trace_sampling(0.01)
        ray_tpu.set_profiling(0.0)


# ---------------------------------------------------------------------------
# CLI surfaces: ray-tpu trace / ray-tpu top
# ---------------------------------------------------------------------------


def test_cli_trace_export_and_top(ray_start_regular, tmp_path, capsys):
    import json

    from ray_tpu import api as _api
    from ray_tpu.scripts import cli

    addr = _api._global_node.gcs_address
    ray_tpu.set_trace_sampling(1.0)
    try:
        @ray_tpu.remote
        def cli_traced():
            return 1

        assert ray_tpu.get(cli_traced.remote(), timeout=60) == 1
        # wait for the DRIVER-side root too (flushes a cycle after the
        # worker's exec span) so the export has a linkable tree
        _wait_spans(lambda spans: [
            s for s in spans
            if s["event_type"] == "task.e2e"
            and s["extra_data"].get("name", "").endswith("cli_traced")
            and len(_tree_of(spans, s["extra_data"]["tid"])) >= 2])

        out = tmp_path / "trace.json"
        assert cli.main(["trace", "--address", addr,
                         "--out", str(out)]) == 0
        data = json.loads(out.read_text())
        assert any("cli_traced" in str(e.get("name")) for e in data)
        assert any(e.get("ph") == "s" for e in data), "no flow links"

        # single-tree filter
        tid = next(s["extra_data"]["tid"] for s in ray_tpu.trace_spans()
                   if s["extra_data"].get("name", "").endswith(
                       "cli_traced"))
        one = tmp_path / "one.json"
        assert cli.main(["trace", "--address", addr, "--trace-id", tid,
                         "--out", str(one)]) == 0
        data1 = json.loads(one.read_text())
        slices = [e for e in data1 if e.get("ph") == "X"]
        assert slices and all(e["args"].get("tid") == tid for e in slices)

        # top: history needs a push cycle; poll until a sample lands
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            if ray_tpu.cluster_metrics(history=1):
                break
            time.sleep(0.3)
        capsys.readouterr()
        assert cli.main(["top", "--address", addr,
                         "--iterations", "1"]) == 0
        top_out = capsys.readouterr().out
        assert "ray-tpu top" in top_out and "raylet" in top_out, top_out
    finally:
        ray_tpu.set_trace_sampling(0.01)


# ---------------------------------------------------------------------------
# histogram exemplars: bucket capture -> p99 -> trace link
# ---------------------------------------------------------------------------


def test_histogram_exemplars_and_saturation_unit():
    """Satellites: observe(exemplar=) keeps the most recent AND the
    max-valued exemplar per bucket; percentile(with_saturation=True)
    tells an overflow-bucket clamp from a real reading; overflow_count
    surfaces the overflow population."""
    h = stats.Histogram("obs_test.exemplar_hist",
                        boundaries=[0.01, 0.1, 1.0])
    h.observe(0.005)
    h.observe(0.05, exemplar="aa01")
    h.observe(0.09, exemplar="aa02")  # same bucket, later + larger
    h.observe(0.5, exemplar="bb01")
    snap = h.snapshot()
    ex = snap["exemplars"]
    mid = ex["1"]  # bucket (0.01, 0.1]
    assert mid["last"]["trace_id"] == "aa02"
    assert mid["max"]["trace_id"] == "aa02"
    # a later-but-smaller observation updates `last`, keeps `max`
    h.observe(0.02, exemplar="aa03")
    mid = h.snapshot()["exemplars"]["1"]
    assert mid["last"]["trace_id"] == "aa03"
    assert mid["max"]["trace_id"] == "aa02"

    # p99 in-range: not saturated; exemplar resolves to the tail bucket
    val, sat = stats.percentile(h.snapshot(), 0.99,
                                with_saturation=True)
    assert not sat and val == 1.0
    assert stats.quantile_exemplar(h.snapshot(), 0.99)[
        "trace_id"] == "bb01"
    assert stats.overflow_count(h.snapshot()) == 0

    # push the tail into the overflow bucket: saturation is explicit
    h.observe(5.0, exemplar="cc01")
    h.observe(7.0)
    snap = h.snapshot()
    val, sat = stats.percentile(snap, 0.99, with_saturation=True)
    assert sat and val == 1.0  # clamped to the top boundary
    assert stats.overflow_count(snap) == 2
    assert stats.quantile_exemplar(snap, 0.99)["trace_id"] == "cc01"
    # plain percentile() keeps the old scalar shape for old callers
    assert stats.percentile(snap, 0.99) == 1.0


def test_registry_reregister_warns_and_preserves_counts():
    """Satellite: registering a same-named metric twice keeps the FIRST
    instance (prior increments preserved) and proxies the second to it
    — a re-registered counter must not silently zero."""
    c1 = stats.Count("obs_test.reregistered_counter")
    c1.inc(3)
    c2 = stats.Count("obs_test.reregistered_counter")
    c2.inc(2)  # proxies to c1
    assert stats.snapshot()["obs_test.reregistered_counter"][
        "value"] == 5.0
    assert stats.registry().get("obs_test.reregistered_counter") is c1
    c1.inc()
    assert c2.snapshot()["value"] == 6.0
    # histograms proxy too (observe + snapshot share state)
    h1 = stats.Histogram("obs_test.reregistered_hist", boundaries=[1.0])
    h1.observe(0.5)
    h2 = stats.Histogram("obs_test.reregistered_hist", boundaries=[1.0])
    h2.observe(2.0)
    assert h1.snapshot()["count"] == 2


def test_exemplar_roundtrip_outlier_task_to_trace_tree(
        ray_start_regular):
    """Acceptance: a deliberately slow task becomes the task-e2e p99
    exemplar, and its trace id resolves through trace_spans() to a
    connected cross-process span tree — the `ray-tpu top` p99 row ->
    `ray-tpu trace --trace-id` path."""
    ray_tpu.set_trace_sampling(1.0)
    try:
        @ray_tpu.remote
        def quick(i):
            return i

        @ray_tpu.remote
        def outlier():
            time.sleep(0.5)
            return "slow"

        assert ray_tpu.get([quick.remote(i) for i in range(10)],
                           timeout=60) == list(range(10))
        assert ray_tpu.get(outlier.remote(), timeout=60) == "slow"

        snap = stats.snapshot()["core.task_e2e_s"]
        ex = stats.quantile_exemplar(snap, 0.99)
        assert ex is not None and ex["value"] >= 0.4, ex
        tid = ex["trace_id"]
        assert tid

        # driver and worker flush their spans on INDEPENDENT ~2s
        # cadences: wait until the tree holds both sides (the e2e root
        # and the worker exec span), not merely until it exists
        def whole_tree(spans):
            t = _tree_of(spans, tid)
            names = {s["event_type"] for s in t}
            return t if {"task", "task.e2e"} <= names else None

        tree = _wait_spans(whole_tree)
        root = _assert_connected(tree)
        assert root["event_type"] == "task.e2e"
        kinds = {s["component_type"] for s in tree}
        assert "driver" in kinds and "worker" in kinds, kinds
    finally:
        ray_tpu.set_trace_sampling(0.01)


def test_metrics_history_carries_p99_exemplars(ray_start_regular):
    """The GCS metrics-history meta reply surfaces each histogram's p99
    exemplar beside the scalar rings (the `ray-tpu top` trace= link),
    and the flattening adds the explicit .p99_saturated signal."""
    from ray_tpu._private import global_state

    ray_tpu.set_trace_sampling(1.0)
    try:
        @ray_tpu.remote
        def tick():
            return 1

        assert ray_tpu.get([tick.remote() for _ in range(5)],
                           timeout=60) == [1] * 5
        cw = global_state.require_core_worker()
        deadline = time.monotonic() + 20
        exemplars, series = {}, {}
        while time.monotonic() < deadline:
            reply = cw._io.run(cw.gcs.call(
                "get_metrics_history", {"samples": 0, "meta": True}))
            exemplars = reply.get("exemplars") or {}
            series = reply.get("series") or {}
            if any("core.task_e2e_s" in d for d in exemplars.values()):
                break
            time.sleep(0.4)
        src_name, d = next(
            (s, d) for s, d in exemplars.items()
            if "core.task_e2e_s" in d)
        ex = d["core.task_e2e_s"]
        assert ex["trace_id"] and ex["value"] > 0
        # the GCS-side exemplar is one this driver actually recorded
        # (same histogram the push carried); the trace-table resolution
        # of the p99 exemplar is test_exemplar_roundtrip's pin
        local = stats.snapshot()["core.task_e2e_s"]
        local_tids = {slot[k]["trace_id"]
                      for slot in (local.get("exemplars") or {}).values()
                      for k in slot}
        assert ex["trace_id"] in local_tids, (ex, local_tids)
        # saturation flag series rides next to the p99 series (its
        # VALUE is asserted on a deterministic histogram below — the
        # accumulated task histogram may legitimately be saturated)
        rings = series[src_name]
        assert "core.task_e2e_s.p99" in rings
        assert "core.task_e2e_s.p99_saturated" in rings

        # deterministic saturation semantics end-to-end: in-range
        # observations -> flag 0; overflow-bucket p99 -> flag 1 plus an
        # .overflow count beside it
        h = stats.Histogram("obs_test.sat_ring_hist",
                            boundaries=[0.01, 0.1])
        for _ in range(10):
            h.observe(0.05)

        def sat_rings():
            reply = cw._io.run(cw.gcs.call(
                "get_metrics_history", {"samples": 0, "meta": True}))
            for rs in reply["series"].values():
                if "obs_test.sat_ring_hist.p99_saturated" in rs:
                    return rs
            return None

        deadline = time.monotonic() + 20
        rs = None
        while time.monotonic() < deadline:
            rs = sat_rings()
            if rs is not None:
                break
            time.sleep(0.4)
        assert rs is not None, "saturation series never reached the ring"
        assert rs["obs_test.sat_ring_hist.p99_saturated"][-1][1] == 0.0
        assert "obs_test.sat_ring_hist.overflow" not in rs
        for _ in range(50):
            h.observe(5.0)  # past the 0.1 top boundary
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            rs = sat_rings()
            if rs and rs["obs_test.sat_ring_hist.p99_saturated"][-1][1]:
                break
            time.sleep(0.4)
        assert rs["obs_test.sat_ring_hist.p99_saturated"][-1][1] == 1.0
        assert rs.get("obs_test.sat_ring_hist.overflow"), rs.keys()
        assert rs["obs_test.sat_ring_hist.overflow"][-1][1] == 50.0
    finally:
        ray_tpu.set_trace_sampling(0.01)


def test_doctor_exemplar_fallback_and_compile_storm_unit():
    """diagnose() is pure: an untraced stalled item borrows the stage
    histogram's p99 exemplar (trace_source="exemplar"), and a process
    snapshot showing a recompile storm yields a compile_storm finding."""
    from ray_tpu._private import debug_state

    hist = {"type": "histogram", "boundaries": [0.1, 1.0],
            "counts": [100, 1, 0], "count": 101, "sum": 12.0,
            "exemplars": {"1": {"max": {"trace_id": "feed00", "value":
                                        0.9, "ts": 1.0},
                                "last": {"trace_id": "feed00", "value":
                                         0.9, "ts": 1.0}}}}
    snapshot = {
        "driver": {
            "pid": 1, "tasks": [
                {"task_id": "t1", "name": "stuck", "stage": "exec",
                 "age_s": 99.0}],  # untraced
            "jax_compiles": {"total": 9, "recent_60s": 6,
                             "recent_s": 4.2, "last_key":
                             "train.step:grad:8x4"},
        },
    }
    metrics = {"driver": {"core.task_exec_s": hist}}
    findings = debug_state.diagnose(snapshot, metrics, floor_s=1.0,
                                    p99_factor=3.0)
    task = next(f for f in findings if f["kind"] == "task")
    assert task["trace_id"] == "feed00"
    assert task["trace_source"] == "exemplar"
    storm = next(f for f in findings if f["kind"] == "compile_storm")
    assert storm["stage"] == "compile"
    assert "6 compiles" in storm["detail"]
    # below the storm threshold: no finding
    snapshot["driver"]["jax_compiles"]["recent_60s"] = 1
    findings = debug_state.diagnose(snapshot, metrics, floor_s=1.0)
    assert not any(f["kind"] == "compile_storm" for f in findings)


# ---------------------------------------------------------------------------
# continuous profiling plane (sampling_profiler.py)
# ---------------------------------------------------------------------------


def test_sampling_profiler_collapse_flush_unit():
    """Sampler unit contract: collapsed stacks aggregate per (thread,
    stack), drain produces the wire batch, a failed flush merges back
    bounded with drops counted, and exports render."""
    import threading

    from ray_tpu._private import sampling_profiler as sp

    prof = sp.SamplingProfiler("testrole", max_stacks=8)
    done = threading.Event()
    t = threading.Thread(target=done.wait, name="parked-thread",
                         daemon=True)
    t.start()
    try:
        for _ in range(20):
            prof.sample_once()
        batch = prof.drain()
        assert batch["samples"] >= 20
        assert prof.drain() is None  # window cleared
        threads = {r["thread"] for r in batch["stacks"]}
        assert "parked-thread" in threads, threads
        parked = next(r for r in batch["stacks"]
                      if r["thread"] == "parked-thread")
        # root-first collapsed format, ';'-separated, count aggregated
        assert parked["stack"].split(";")[0].startswith("_bootstrap")
        assert parked["stack"].split(";")[-1].startswith("wait")
        assert parked["count"] == 20

        # failed-flush merge-back: bounded, counted, retried next drain
        base = sp.M_FLUSH_DROPPED.snapshot()["value"]
        assert prof.merge_back(batch) == 0
        again = prof.drain()
        assert again["samples"] == batch["samples"]
        big = {"t_start": 0.0, "stacks": [
            {"thread": "x", "stack": f"frame{i}", "count": 1}
            for i in range(12)]}
        dropped = prof.merge_back(big)
        assert dropped > 0
        assert sp.M_FLUSH_DROPPED.snapshot()["value"] - base == dropped
        kept = prof.drain()
        folded = next(r for r in kept["stacks"]
                      if r["stack"] == sp.OVERFLOW_STACK)
        assert folded["count"] == dropped  # counts folded, not lost
        assert sum(r["count"] for r in kept["stacks"]) == 12

        # exports
        batch["component_type"] = "testrole"
        text = sp.collapse_text([batch])
        line = text.splitlines()[0]
        assert line.startswith("testrole;")
        assert line.rsplit(" ", 1)[1].isdigit()
        trace = sp.samples_to_chrome_trace([batch])
        assert trace and all(e["ph"] == "X" for e in trace)
        assert sp.components_of([batch]) == ["testrole"]
    finally:
        done.set()
        prof.stop()
        assert not prof.running


def test_sampler_thread_arming_and_rate_zero():
    """set_rate arms the named daemon thread; rate 0 stops it (the
    conftest leak check names any survivor)."""
    import threading

    from ray_tpu._private import sampling_profiler as sp

    prof = sp.SamplingProfiler("armrole")
    prof.set_rate(200)
    try:
        assert prof.running
        assert any(t.name == sp.THREAD_NAME
                   for t in threading.enumerate())
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and len(prof) == 0:
            time.sleep(0.05)
        assert len(prof) > 0, "armed sampler never sampled"
        prof.set_rate(0)
        assert not prof.running
    finally:
        prof.stop()


def test_profile_plane_end_to_end(ray_start_regular):
    """Tentpole acceptance: the always-on plane covers >=3 process
    classes (driver, raylet, GCS) in one collection window, and
    set_profiling() re-arms it live cluster-wide."""
    from ray_tpu._private import sampling_profiler as sp
    from tests.conftest import scale_timeout

    @ray_tpu.remote
    def churn(i):
        return sum(range(1000)) + i

    assert ray_tpu.get([churn.remote(i) for i in range(8)],
                       timeout=60) == [sum(range(1000)) + i
                                       for i in range(8)]
    rep = ray_tpu.profile(seconds=2.0)
    assert rep["samples"] > 0
    assert {"driver", "raylet", "gcs"} <= set(rep["components"]), (
        rep["components"])
    # collapsed text: component-prefixed, flamegraph-parseable
    for line in rep["collapsed"].splitlines()[:5]:
        stack, count = line.rsplit(" ", 1)
        assert int(count) > 0 and stack.count(";") >= 1

    # live disarm stops the local sampler thread; re-arm restarts it
    ray_tpu.set_profiling(0.0)
    deadline = time.monotonic() + scale_timeout(5)
    while time.monotonic() < deadline and sp.rate() != 0.0:
        time.sleep(0.1)
    assert sp.rate() == 0.0
    assert not sp.get_profiler().running
    ray_tpu.set_profiling(100.0)
    deadline = time.monotonic() + scale_timeout(5)
    while time.monotonic() < deadline and not sp.get_profiler().running:
        time.sleep(0.1)
    assert sp.get_profiler().running
    rep2 = ray_tpu.profile(seconds=1.0, component="driver")
    assert rep2["components"] == ["driver"] and rep2["samples"] > 0


def test_compile_probe_records_metrics_and_span(ray_start_regular):
    """Compile observability: the paged-KV jax seam records its first-
    dispatch compile into jax.compiles_total / jax.compile_s, and
    record_compile emits a `jax.compile` span joining the ambient
    trace."""
    from ray_tpu._private import profiling, tracing
    from ray_tpu.serve.kv_cache import PagedKVCache

    base = profiling.M_COMPILES.snapshot()["value"]
    kv = PagedKVCache(8, 4, 4, name="kv:obs_test", backend="jax")
    kv.alloc_table("seq1")
    import numpy as np

    kv.append("seq1", np.ones((3, 4), dtype=np.float32))
    assert profiling.M_COMPILES.snapshot()["value"] > base
    hist = stats.snapshot()["jax.compile_s"]
    assert hist["count"] >= 1
    st = profiling.compile_state()
    assert st["total"] >= 1 and st["last_key"]

    # span joins an ambient trace
    ray_tpu.set_trace_sampling(1.0)
    try:
        ctx = tracing.new_context()
        with tracing.use(ctx):
            profiling.record_compile("obs_test:shape", time.time() - 0.1,
                                     time.time())
        _wait_spans(lambda spans: [
            s for s in spans
            if s["event_type"] == "jax.compile"
            and s["extra_data"].get("key") == "obs_test:shape"
            and s["extra_data"].get("tid") == ctx.trace_id.hex()])
    finally:
        ray_tpu.set_trace_sampling(0.01)


def test_microbench_profiling_overhead_gate():
    """Gate on the recorded interleaved profiler-on/off A/B rows: >5%
    throughput regression with the sampler armed at its default rate on
    the tasks-sync or serve-http row fails tier-1 (reads
    MICROBENCH.json — deterministic, no benchmarking in CI)."""
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    doc = json.load(open(os.path.join(root, "MICROBENCH.json")))
    rows = {r["name"]: r for r in doc["results"]}
    for case in ("profiling A/B tasks sync",
                 "profiling A/B serve http qps"):
        on_name, off_name = case, f"{case} (profiler-off control)"
        assert on_name in rows and off_name in rows, (
            f"missing profiling A/B row {case!r} in MICROBENCH.json")
        on, off = rows[on_name], rows[off_name]
        if on.get("high_variance") or off.get("high_variance"):
            continue  # window noise, not signal (see timeit docstring)
        assert on["per_second"] >= 0.95 * off["per_second"], (
            f"{case}: profiler-on {on['per_second']:.1f}/s is >5% below "
            f"profiler-off {off['per_second']:.1f}/s")
