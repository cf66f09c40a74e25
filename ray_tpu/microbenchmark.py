"""Core microbenchmark suite (reference: python/ray/ray_perf.py, invoked
as `ray microbenchmark`; harness: _private/ray_microbenchmark_helpers.py).
Metric names match the reference's release logs
(release/release_logs/1.2.0/microbenchmark.txt) so numbers are directly
comparable with BASELINE.md."""

from __future__ import annotations

import json
import time

import numpy as np

import ray_tpu


def timeit(name: str, fn, multiplier: int = 1, seconds: float = 2.0,
           results: list | None = None, trials: int = 3):
    """reference: ray_microbenchmark_helpers.py:timeit — N>=3 repetitions,
    MEDIAN reported (this box is 1 time-shared core: a single scheduler
    hiccup skews a mean; the median survives one bad window). Cases whose
    trial spread exceeds 50% of the median are flagged high_variance —
    read those numbers as window noise, not signal."""
    # warmup
    fn()
    trials = max(3, trials)
    rates = []
    for _ in range(trials):
        start = time.perf_counter()
        count = 0
        while time.perf_counter() - start < seconds / trials:
            fn()
            count += 1
        dt = time.perf_counter() - start
        rates.append(count * multiplier / dt)
    med = float(np.median(rates))
    sd = float(np.std(rates))
    flagged = bool(med > 0 and sd > 0.5 * med)
    print(f"{name} per second {med:.2f} +- {sd:.2f} "
          f"(median of {trials})"
          + ("  [HIGH VARIANCE: sd > 50% of median]" if flagged else ""))
    if results is not None:
        row = {"name": name, "per_second": med, "sd": sd,
               "trials": [round(r, 2) for r in rates]}
        if flagged:
            row["high_variance"] = True
        results.append(row)
    return med


def timeit_ab(name: str, arms: dict, multiplier: int = 1,
              seconds_per_window: float = 0.7, windows: int = 3,
              results: list | None = None):
    """Paired interleaved A/B: every arm runs once inside EACH window
    (so a box-load swing hits all arms equally), median of N windows per
    arm. `arms` maps suffix -> (setup, fn): setup() flips the process
    into that arm (e.g. tracing off) before its slice runs."""
    rates: dict[str, list] = {suffix: [] for suffix in arms}
    for suffix, (setup, fn) in arms.items():
        setup()
        fn()  # warm this arm
    for _ in range(windows):
        for suffix, (setup, fn) in arms.items():
            setup()
            start = time.perf_counter()
            count = 0
            while time.perf_counter() - start < seconds_per_window:
                fn()
                count += 1
            rates[suffix].append(
                count * multiplier / (time.perf_counter() - start))
    # leave the process in the FIRST (default) arm
    next(iter(arms.values()))[0]()
    out = {}
    for suffix, rr in rates.items():
        med = float(np.median(rr))
        sd = float(np.std(rr))
        full = name if not suffix else f"{name} ({suffix})"
        flagged = bool(med > 0 and sd > 0.5 * med)
        print(f"{full} per second {med:.2f} +- {sd:.2f} "
              f"(median of {windows} interleaved windows)"
              + ("  [HIGH VARIANCE]" if flagged else ""))
        if results is not None:
            row = {"name": full, "per_second": med, "sd": sd,
                   "trials": [round(r, 2) for r in rr]}
            if flagged:
                row["high_variance"] = True
            results.append(row)
        out[suffix] = med
    return out


def calibrate(results: list) -> None:
    """Same-process calibration controls captured with EVERY run:
    a pure-python loop rate (interpreter speed
    under the current box load) and a raw-socket echo rate (syscall +
    scheduler round-trip, zero framework). Cross-session comparisons of
    the framework metrics should be read against these — if calibration
    moved 3x between windows, so did everything else."""
    def py_loop():
        n = 0
        for _ in range(10_000):
            n += 1
        return n

    timeit("calibration python loop iters", py_loop, multiplier=10_000,
           seconds=1.0, results=results)

    import socket
    import threading

    a, b = socket.socketpair()
    done = threading.Event()

    def echo():
        while not done.is_set():
            try:
                d = b.recv(64)
                if not d:
                    return
                b.sendall(d)
            except OSError:
                return

    t = threading.Thread(target=echo, daemon=True)
    t.start()

    def roundtrip():
        a.sendall(b"x")
        a.recv(64)

    timeit("calibration raw-socket echo roundtrips", roundtrip,
           seconds=1.0, results=results)
    done.set()
    a.close()
    b.close()


def main(seconds_per_case: float = 2.0) -> list[dict]:
    results: list[dict] = []
    calibrate(results)
    ray_tpu.init()

    arr = np.zeros(100, dtype=np.int64)            # small: inline path
    big = np.zeros(10 * 1024 * 1024, dtype=np.uint8)  # 10MB: plasma path

    def put_small():
        ray_tpu.put(arr)

    timeit("single client put calls", put_small, results=results)

    def get_small():
        ref = ray_tpu.put(arr)
        ray_tpu.get(ref)

    timeit("single client get calls", get_small, results=results)

    def put_large():
        ray_tpu.get(ray_tpu.put(big))

    n = timeit("single client put+get large (10MB)", put_large,
               results=results)
    gb_s = n * big.nbytes / 1e9
    print(f"single client put gigabytes per second {gb_s:.2f}")
    results.append({"name": "single client put gigabytes",
                    "per_second": gb_s, "sd": 0.0})

    @ray_tpu.remote
    def small_task():
        return b"ok"

    def task_sync():
        ray_tpu.get(small_task.remote())

    timeit("single client tasks sync", task_sync, results=results)

    def tasks_async():
        ray_tpu.get([small_task.remote() for _ in range(100)])

    timeit("single client tasks async", tasks_async, multiplier=100,
           results=results)

    @ray_tpu.remote
    class TaskClient:
        """Client actor driving its own task fan-out (BASELINE.md 'multi
        client' rows use independent client processes)."""

        def batch(self, fn, n):
            import ray_tpu as rt

            rt.get([fn.remote() for _ in range(n)])
            return n

    clients = [TaskClient.remote() for _ in range(2)]

    def multi_client_tasks():
        ray_tpu.get([c.batch.remote(small_task, 50) for c in clients])

    timeit("multi client tasks async", multi_client_tasks, multiplier=100,
           results=results)

    @ray_tpu.remote
    class Actor:
        def small_value(self):
            return b"ok"

    a = Actor.remote()

    def actor_sync():
        ray_tpu.get(a.small_value.remote())

    timeit("1:1 actor calls sync", actor_sync, results=results)

    def actor_async():
        ray_tpu.get([a.small_value.remote() for _ in range(100)])

    timeit("1:1 actor calls async", actor_async, multiplier=100,
           results=results)

    @ray_tpu.remote
    class AsyncActor:
        async def small_value(self):
            return b"ok"

    aa = AsyncActor.remote()
    ray_tpu.get(aa.small_value.remote())  # warm the async loop

    def async_actor_async():
        ray_tpu.get([aa.small_value.remote() for _ in range(100)])

    timeit("1:1 async-actor calls async", async_actor_async,
           multiplier=100, results=results)

    n_actors = 4
    actors = [Actor.remote() for _ in range(n_actors)]

    def actors_1n_async():
        refs = []
        for actor in actors:
            refs.extend(actor.small_value.remote() for _ in range(25))
        ray_tpu.get(refs)

    # NOTE: this single-driver fan-out carried the label "n:n actor
    # calls async" through round 7; it is 1:n-shaped (one client, n
    # server actors) and is now labeled to match BASELINE.md column
    # definitions. The true n:n row below drives the same targets from
    # n concurrent CLIENT actors.
    timeit("1:n actor calls async", actors_1n_async, multiplier=100,
           results=results)

    @ray_tpu.remote
    class CallerClient:
        def __init__(self, targets):
            self.targets = targets

        def fan(self, calls_per_target):
            import ray_tpu as rt

            refs = []
            for t in self.targets:
                refs.extend(t.small_value.remote()
                            for _ in range(calls_per_target))
            rt.get(refs)
            return len(refs)

    callers = [CallerClient.remote(actors) for _ in range(2)]

    def actors_nn_async():
        ray_tpu.get([c.fan.remote(13) for c in callers])

    timeit("n:n actor calls async", actors_nn_async,
           multiplier=2 * n_actors * 13, results=results)

    _collective_bench(results)

    _serve_qps(results)

    _tracing_ab(results)

    _profiling_ab(results)

    _state_ab(results)

    _serve_mixed(results)

    _serve_stream(results)

    _serve_prefix(results)

    _train_sharded(results)

    ray_tpu.shutdown()

    _cross_node_bench(results)
    _control_plane(results)
    _placement_topology(results)
    return results


def _cross_node_bench(results: list[dict], windows: int = 5):
    """Cross-node object pull (needs real raylet process boundaries, so
    it runs on its own cluster_utils cluster AFTER the single-node
    suite). Per size, each window times ONE streaming bulk-channel pull
    — median of N windows. Also: a 2-source striped pull, and the
    control-plane probe: peer_ping RTTs over the shared raylet<->raylet
    CONTROL connection while a 16MB pull is in flight (streaming must
    leave that conn idle)."""
    from ray_tpu._private import global_state
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
    try:
        _cross_node_bench_body(results, windows, cluster)
    finally:
        # a failed assert/timeout must not orphan the gcs/raylet
        # children (orphans poison every later benchmark on this box)
        cw = global_state.get_core_worker()
        if cw is not None:
            cw.shutdown()
        cluster.shutdown()


def _cross_node_bench_body(results: list[dict], windows: int, cluster):
    import asyncio

    src_b = cluster.add_node(num_cpus=1, resources={"srcb": 1})
    src_c = cluster.add_node(num_cpus=1, resources={"srcc": 1})
    cw = cluster.connect_driver()
    head = cw.raylet

    def rcall(method, data, timeout=180.0):
        return cw._io.run(head.call(method, data), timeout=timeout)

    def pull(oid, free_after=True) -> float:
        t0 = time.perf_counter()
        ok = rcall("wait_object_local", {"object_id": oid, "timeout": 150})
        dt = time.perf_counter() - t0
        assert ok is True, f"pull did not complete: {ok!r}"
        if free_after:
            rcall("free_objects", {"object_ids": [oid]})
        return dt

    @ray_tpu.remote(num_cpus=1, resources={"srcb": 1})
    def produce(nbytes):
        import numpy as _np

        return _np.arange(nbytes, dtype=_np.uint8)

    @ray_tpu.remote(num_cpus=1, resources={"srcc": 1})
    def touch(arr):
        return int(arr.nbytes)

    def wait_locations(oid, n):
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if len(cw._io.run(cw.gcs.call(
                    "get_object_locations", {"object_id": oid}))) >= n:
                return
            time.sleep(0.1)
        raise TimeoutError("object location never registered")

    refs = {}
    for mb in (1, 16, 64):
        refs[mb] = produce.remote(mb * 1024 * 1024)
        wait_locations(refs[mb].id().binary(), 1)

    def record(name, rates, nbytes):
        med = float(np.median(rates))
        sd = float(np.std(rates))
        gb_s = med * nbytes / 1e9
        flagged = bool(med > 0 and sd > 0.5 * med)
        print(f"{name} per second {med:.2f} ({gb_s:.3f} GB/s, median of "
              f"{len(rates)})" + ("  [HIGH VARIANCE]" if flagged else ""))
        row = {"name": name, "per_second": med, "sd": sd,
               "gb_s": round(gb_s, 4),
               "trials": [round(r, 3) for r in rates]}
        if flagged:
            row["high_variance"] = True
        results.append(row)

    for mb in (1, 16, 64):
        oid = refs[mb].id().binary()
        pull(oid)  # warm the connections
        rates = [1.0 / pull(oid) for _ in range(windows)]
        record(f"cross_node_pull {mb}MB", rates, mb * 1024 * 1024)

    # --- 1src vs 2src striped pull (64MB), PAIRED interleaved: the
    # second source's directory entry is removed for the 1src slice of
    # each window and restored for the 2src slice, so a box-load swing
    # hits both sides equally (the arms' trial spread on this shared
    # 2-core host is wider than the striping delta — unpaired medians
    # are noise).
    nbytes = 64 * 1024 * 1024
    oid = refs[64].id().binary()
    assert ray_tpu.get(touch.remote(refs[64]), timeout=300) > 0
    wait_locations(oid, 2)

    def set_second_source(present: bool):
        method = ("add_object_location" if present
                  else "remove_object_location")
        data = {"object_id": oid, "node_id": src_c.node_id.binary()}
        if present:
            data["size"] = nbytes
        cw._io.run(cw.gcs.call(method, data))

    striped0 = rcall("get_metrics", {}).get(
        "raylet.pulls_striped_total", {}).get("value", 0)
    for present in (False, True):  # warm both shapes
        set_second_source(present)
        pull(oid)
    rates1, rates2 = [], []
    for _ in range(max(windows, 7)):
        set_second_source(False)
        rates1.append(1.0 / pull(oid))
        set_second_source(True)
        rates2.append(1.0 / pull(oid))
    striped = rcall("get_metrics", {}).get(
        "raylet.pulls_striped_total", {}).get("value", 0) - striped0
    record("cross_node_pull 64MB 1src (paired)", rates1, nbytes)
    record("cross_node_pull 64MB 2src", rates2, nbytes)
    results[-1]["striped_pulls"] = striped

    # --- control-plane RTT during a 64MB bulk pull ---
    # peer_ping rides the head raylet's shared control connection to the
    # source — the one the control-path pull fallback would also use.
    async def ping_during_pull(oid):
        lats = []
        pull_fut = asyncio.ensure_future(head.call(
            "wait_object_local", {"object_id": oid, "timeout": 150}))
        await asyncio.sleep(0.005)  # let the pull get going
        while not pull_fut.done():
            lats.append(await head.call("peer_ping",
                                        {"address": src_b.address}))
        assert (await pull_fut) is True
        await head.call("free_objects", {"object_ids": [oid]})
        return lats

    oid = refs[16].id().binary()  # single-source (B) object
    lats: list[float] = []
    for _ in range(windows):
        lats.extend(cw._io.run(ping_during_pull(oid), timeout=300))
    name = "cross_node_pull control ping during 16MB pull"
    if not lats:
        # pull outraced every ping this window: no row (NaN would
        # make MICROBENCH.json invalid JSON for strict parsers)
        print(f"{name}: no pings completed during the pull; skipped")
        return
    p99 = float(np.percentile(lats, 99))
    p50 = float(np.median(lats))
    print(f"{name}: p50 {p50 * 1e3:.2f}ms p99 {p99 * 1e3:.2f}ms "
          f"({len(lats)} pings)")
    results.append({"name": name, "per_second": 1.0 / p99,
                    "sd": 0.0, "p99_ms": round(p99 * 1e3, 3),
                    "p50_ms": round(p50 * 1e3, 3),
                    "samples": len(lats)})


def _collective_bench(results: list[dict], nbytes: int = 16 * 1024 * 1024,
                      world: int = 4, windows: int = 5):
    """Host collective data-plane A/B: one 16MB float32 allreduce across
    4 single-node ranks per window, every transport forced in turn
    inside the SAME window (interleaved — a box-load swing hits all arms
    equally), median of N windows, GB/s/rank. The small-hub case guards
    control-plane latency against regressions from the routing layer.
    Round-12 arms: `device` (the Transport.DEVICE tier over the shared
    jax runtime — device-resident payload, timed to block_until_ready)
    and `ring_quantized` (int8 block-scaled wire format on the pipelined
    ring; same payload, ~4x fewer socket bytes)."""
    from ray_tpu.collective import collective as col

    @ray_tpu.remote(num_cpus=0)
    class BenchRank(col.CollectiveActorMixin):
        def join_runtime(self, world, rank):
            # BEFORE first jax backend use: makes the group
            # device-capable so the 'device' arm is forcible
            from ray_tpu.parallel import multihost

            multihost.initialize("bench_mh", world, rank)
            return True

        def timed_allreduce(self, transport, n_elems):
            import time as _t

            import numpy as _np

            from ray_tpu.collective import collective as C

            group = C._manager.get_group("bench_col")
            quantize = None
            if transport == "ring_quantized":
                transport, quantize = "ring", "int8"
            group.barrier()  # hub-direct: lines ranks up, never routed
            group.force_transport = transport
            if transport in ("device", "pallas"):
                import jax
                import jax.numpy as jnp

                arr = jnp.ones(n_elems, jnp.float32)
                jax.block_until_ready(arr)
                t0 = _t.perf_counter()
                out = group.allreduce(arr)
                jax.block_until_ready(out)
                return _t.perf_counter() - t0
            arr = _np.ones(n_elems, _np.float32)
            t0 = _t.perf_counter()
            group.allreduce(arr, quantize=quantize)
            return _t.perf_counter() - t0

        def read_counter(self, name):
            from ray_tpu._private import stats

            snap = stats.snapshot().get(name)
            return float(snap["value"]) if snap else 0.0

        def teardown(self):
            from ray_tpu.collective import collective as C

            C.destroy_collective_group("bench_col")  # rank 0 unlinks
            return True                              # the shm segment

    ranks = [BenchRank.remote() for _ in range(world)]
    ray_tpu.get([r.join_runtime.remote(world, i)
                 for i, r in enumerate(ranks)], timeout=300)
    col.create_collective_group(ranks, world, list(range(world)),
                                backend="host", group_name="bench_col")
    cases = ["shm", "ring", "ring_quantized", "hub", "device"]
    for tr in cases:  # warm at FULL size: segment sized+faulted in, ring
        ray_tpu.get(   # built, hub buffers grown, device bodies jitted —
            [r.timed_allreduce.remote(tr, nbytes // 4) for r in ranks],
            timeout=300)  # no setup in the windows
    # small-message fused-kernel arm (round 15): decode-step-sized
    # payloads — the latency class the PALLAS tier exists for — pallas
    # vs the device (shard_map dispatch stack) control, interleaved in
    # the same windows. 4096 f32 = 16KB, under pallas_max_bytes.
    SMALL_ELEMS = 4096
    small_cases = ["pallas", "device"]
    for tr in small_cases:  # warm: kernels traced, vote round paid once
        ray_tpu.get([r.timed_allreduce.remote(tr, SMALL_ELEMS)
                     for r in ranks], timeout=300)
    samples: dict[str, list[float]] = {tr: [] for tr in cases}
    small: list[float] = []
    small_samples: dict[str, list[float]] = {tr: [] for tr in small_cases}
    for _ in range(windows):
        for tr in cases:
            ts = ray_tpu.get(
                [r.timed_allreduce.remote(tr, nbytes // 4) for r in ranks],
                timeout=300)
            samples[tr].append(max(ts))  # slowest rank bounds the op
        for tr in small_cases:
            ts = ray_tpu.get(
                [r.timed_allreduce.remote(tr, SMALL_ELEMS) for r in ranks],
                timeout=120)
            small_samples[tr].append(max(ts))
        ts = ray_tpu.get(
            [r.timed_allreduce.remote("hub", 256) for r in ranks],
            timeout=120)
        small.append(max(ts))
    for tr in cases:
        med = float(np.median(samples[tr]))
        gbps = nbytes / med / 1e9
        print(f"collective_allreduce_{tr} 16MB/4-rank GB/s/rank "
              f"{gbps:.3f} (median of {windows})")
        results.append({
            "name": f"collective_allreduce_{tr}", "per_second": 1.0 / med,
            "gb_s_per_rank": round(gbps, 4),
            "sd": float(np.std(samples[tr])),
            "trials": [round(t, 4) for t in samples[tr]]})
    med = float(np.median(small))
    print(f"collective_allreduce_hub_small (1KB) per second {1 / med:.1f}")
    results.append({"name": "collective_allreduce_hub_small",
                    "per_second": 1.0 / med, "sd": float(np.std(small)),
                    "trials": [round(t, 5) for t in small]})
    # counter-verify the fused-kernel arm actually ran on the PALLAS
    # tier (ops counted per rank: warm + one per window)
    pallas_ops = ray_tpu.get([r.read_counter.remote(
        "collective.pallas_ops_total") for r in ranks], timeout=60)
    for tr in small_cases:
        med = float(np.median(small_samples[tr]))
        row = {"name": f"collective_allreduce_{tr}_small",
               "per_second": 1.0 / med,
               "payload_bytes": SMALL_ELEMS * 4,
               "sd": float(np.std(small_samples[tr])),
               "trials": [round(t, 5) for t in small_samples[tr]]}
        if tr == "pallas":
            row["pallas_ops_per_rank"] = float(np.mean(pallas_ops))
        results.append(row)
        print(f"collective_allreduce_{tr}_small (16KB decode-step) "
              f"per second {1 / med:.1f} (median of {windows})")
    # counter-verify the quantized wire reduction: saved bytes per op
    # per rank vs the exact f32 wire the same schedule would have sent
    saved = ray_tpu.get([r.read_counter.remote(
        "collective.quantized_bytes_saved_total") for r in ranks],
        timeout=60)
    q_ops = windows + 1  # warm + one per window
    chunk = (nbytes // 4) // world
    exact_wire = 2 * (world - 1) * chunk * 4
    saved_per_op = float(np.mean(saved)) / q_ops
    reduction = exact_wire / max(exact_wire - saved_per_op, 1.0)
    for row in results:
        if row["name"] == "collective_allreduce_ring_quantized":
            row["wire_bytes_exact"] = exact_wire
            row["wire_bytes_saved_per_op"] = int(saved_per_op)
            row["wire_reduction_x"] = round(reduction, 2)
    print(f"collective_allreduce_ring_quantized wire reduction "
          f"{reduction:.2f}x (counter-verified, saved "
          f"{saved_per_op / 1e6:.1f}MB/op/rank of {exact_wire / 1e6:.1f}MB)")
    ray_tpu.get([r.teardown.remote() for r in ranks], timeout=60)
    for r in ranks:
        ray_tpu.kill(r)


def _http_qps_window(pool, tls, port: int, route: str,
                     seconds: float = 0.7) -> float:
    """Keep-alive HTTP throughput over one timed window: 16 pooled
    client threads, one persistent conn per (thread, port) — urllib
    reconnects per request, which would measure TCP handshakes, not the
    proxy. Shared by the serve qps row and the tracing and state A/Bs
    so every row measures through the identical harness."""
    import http.client

    stop = time.perf_counter() + seconds

    def worker(_):
        conns = getattr(tls, "conns", None)
        if conns is None:
            conns = tls.conns = {}
        n = 0
        while time.perf_counter() < stop:
            conn = conns.get(port)
            if conn is None:
                conn = conns[port] = http.client.HTTPConnection(
                    "127.0.0.1", port)
            try:
                conn.request("GET", route)
                conn.getresponse().read()
            except (http.client.HTTPException, OSError):
                conns.pop(port, None)
                raise
            n += 1
        return n

    t0 = time.perf_counter()
    counts = list(pool.map(worker, range(16)))
    return sum(counts) / (time.perf_counter() - t0)


def _rate_rows(results: list[dict], rows, windows: int):
    """Median/sd/high-variance row emission for the hand-rolled
    interleaved A/Bs (timeit_ab covers the closed-loop cases)."""
    for name, rates in rows:
        med = float(np.median(rates))
        sd = float(np.std(rates))
        flagged = bool(med > 0 and sd > 0.5 * med)
        print(f"{name} per second {med:.2f} +- {sd:.2f} "
              f"(median of {windows} interleaved windows)"
              + ("  [HIGH VARIANCE]" if flagged else ""))
        row = {"name": name, "per_second": med, "sd": sd,
               "trials": [round(r, 2) for r in rates]}
        if flagged:
            row["high_variance"] = True
        results.append(row)


def _serve_qps(results: list[dict]):
    """Serve noop throughput (reference: serve release bench, ~3-4k qps
    noop via HTTP). Measured through the handle (router batching path),
    through a router-only asyncio control (no HTTP), and through the
    HTTP proxy (call_async + coalesced wakeups)."""
    import asyncio

    from ray_tpu import serve

    client = serve.start(http=True)
    client.create_backend("noop", lambda _=None: "ok", config={
        "num_replicas": 2, "max_batch_size": 32,
        "batch_wait_timeout": 0.001, "max_concurrent_queries": 8})
    client.create_endpoint("noop", backend="noop", route="/noop")
    handle = client.get_handle("noop")
    ray_tpu.get(handle.remote(None))  # warm the path

    # qps is a CONCURRENT-load metric (the reference measures with wrk):
    # router.assign intentionally blocks each caller until its batch is
    # dispatched, so drive it from a client thread pool.
    from concurrent.futures import ThreadPoolExecutor

    pool = ThreadPoolExecutor(max_workers=16)

    def one_handle_call(_):
        return ray_tpu.get(handle.remote(None), timeout=30)

    def handle_call():
        list(pool.map(one_handle_call, range(64)))

    timeit("serve handle noop calls", handle_call, multiplier=64,
           results=results)

    # Router-only control: call_async at concurrency 16, no HTTP
    # anywhere. Bounds what any proxy in this process could deliver.
    router = handle._router

    def router_window(seconds: float = 0.7) -> float:
        async def drive():
            stop = time.perf_counter() + seconds

            async def worker():
                n = 0
                while time.perf_counter() < stop:
                    await router.call_async(None)
                    n += 1
                return n

            t0 = time.perf_counter()
            counts = await asyncio.gather(*[worker() for _ in range(16)])
            return sum(counts) / (time.perf_counter() - t0)

        return asyncio.run(drive())

    router_rates = [router_window() for _ in range(3)]
    med = float(np.median(router_rates))
    print(f"serve router-only control per second {med:.2f} "
          f"+- {float(np.std(router_rates)):.2f} (median of 3)")
    results.append({"name": "serve router-only control",
                    "per_second": med,
                    "sd": float(np.std(router_rates)),
                    "trials": [round(r, 2) for r in router_rates]})

    import threading as _threading

    tls = _threading.local()

    def http_window(port: int, seconds: float = 0.7) -> float:
        return _http_qps_window(pool, tls, port, "/noop", seconds)

    http_window(client.http_port, 0.2)  # warm the proxy's conns
    _rate_rows(results, [("serve http noop qps",
                          [http_window(client.http_port)
                           for _ in range(5)])], windows=5)
    pool.shutdown()
    serve.shutdown()


def _serve_mixed(results: list[dict], window_s: float = 1.5,
                 windows: int = 3):
    """Mixed-traffic serve bench (ROADMAP item 1 acceptance): sustained
    small-JSON + large (8MB octet-stream) bodies through the HTTP proxy
    at 1x and 2x admission capacity, paired-interleaved windows. Large
    bodies ride the zero-copy plane (plasma + bulk channel past the 1MB
    threshold). Records per arm: qps (2xx only), client-side p99 of
    SUCCESSFUL requests (what admitted traffic experiences), and the
    shed rate (503 fraction). The tier-1 gate
    (tests/test_serve_sharded.py::test_microbench_serve_mixed_gate)
    asserts the recorded 2x row kept p99 bounded WITH nonzero typed
    sheds — overload must degrade via 503s, not latency collapse.

    Capacity arithmetic: 2 replicas x max_concurrent_queries=2 in
    service + max_queued_requests=4 queue ~= 8 outstanding. 1x drives 7
    closed-loop clients (6 small + 1 large, no sheds expected); 2x
    drives 14 (12 small + 2 large, the excess MUST shed)."""
    import http.client
    import threading as _threading

    import numpy as _np

    from ray_tpu import serve

    client = serve.start(http=True)
    client.create_backend(
        "mixed", lambda d=None: (len(d) if isinstance(d, (bytes,
                                                          bytearray))
                                 else "ok"),
        config={"num_replicas": 2, "max_concurrent_queries": 2,
                "max_batch_size": 4, "batch_wait_timeout": 0.001,
                "max_queued_requests": 4,
                "large_payload_threshold": 1 << 20})
    client.create_endpoint("mixed", backend="mixed", route="/mixed",
                           methods=["GET", "POST"])
    port = client.http_port
    big = _np.zeros(8 << 20, dtype=_np.uint8).tobytes()  # 8MB
    tls = _threading.local()

    def one_request(body):
        conns = getattr(tls, "conns", None)
        if conns is None:
            conns = tls.conns = {}
        conn = conns.get(port)
        if conn is None:
            conn = conns[port] = http.client.HTTPConnection(
                "127.0.0.1", port)
        t0 = time.perf_counter()
        try:
            if body is None:
                conn.request("GET", "/mixed")
            else:
                conn.request("POST", "/mixed", body=body, headers={
                    "Content-Type": "application/octet-stream"})
            resp = conn.getresponse()
            resp.read()
            status = resp.status
        except (http.client.HTTPException, OSError):
            conns.pop(port, None)
            raise
        return status, time.perf_counter() - t0

    def drive(n_small: int, n_large: int, seconds: float):
        """One closed-loop window; returns (ok_lat, shed, errors, dt)."""
        stop = time.perf_counter() + seconds
        lock = _threading.Lock()
        ok_lat: list[float] = []
        counts = {"shed": 0, "other": 0}

        def worker(body):
            while time.perf_counter() < stop:
                try:
                    status, dt = one_request(body)
                except (http.client.HTTPException, OSError):
                    # dropped keep-alive conn: reconnect next loop —
                    # a dead worker thread would silently shrink the
                    # offered load mid-window
                    with lock:
                        counts["other"] += 1
                    continue
                with lock:
                    if status == 200:
                        ok_lat.append(dt)
                    elif status == 503:
                        counts["shed"] += 1
                    else:
                        counts["other"] += 1

        threads = ([_threading.Thread(target=worker, args=(None,))
                    for _ in range(n_small)]
                   + [_threading.Thread(target=worker, args=(big,))
                      for _ in range(n_large)])
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return ok_lat, counts["shed"], counts["other"], \
            time.perf_counter() - t0

    # warm the route + the zero-copy path (sleep on EVERY miss — a 404
    # while the route table syncs returns without raising and must not
    # hot-spin; a transient conn drop on the first 8MB body must not
    # abort the whole suite)
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            if one_request(None)[0] == 200:
                break
        except Exception:
            pass
        time.sleep(0.2)
    for _ in range(10):
        try:
            one_request(big)
            break
        except Exception:
            time.sleep(0.5)

    arms = {"serve_mixed 1x": (6, 1), "serve_mixed 2x overload": (12, 2)}
    acc = {name: {"lat": [], "shed": 0, "ok": 0, "other": 0, "dt": 0.0}
           for name in arms}
    for _ in range(windows):  # paired: load swings hit both arms
        for name, (ns, nl) in arms.items():
            lat, shed, other, dt = drive(ns, nl, window_s)
            a = acc[name]
            a["lat"].extend(lat)
            a["shed"] += shed
            a["ok"] += len(lat)
            a["other"] += other
            a["dt"] += dt
    for name, a in acc.items():
        total = a["ok"] + a["shed"] + a["other"]
        qps = a["ok"] / a["dt"] if a["dt"] else 0.0
        p99_ms = (float(_np.percentile(a["lat"], 99)) * 1000.0
                  if a["lat"] else 0.0)
        shed_rate = a["shed"] / total if total else 0.0
        row = {"name": name, "per_second": round(qps, 2),
               "p99_ms": round(p99_ms, 1),
               "shed_rate": round(shed_rate, 4),
               "ok": a["ok"], "shed": a["shed"], "other": a["other"],
               "windows": windows, "window_s": window_s}
        results.append(row)
        print(f"{name}: {qps:.1f} qps ok, p99 {p99_ms:.0f}ms, "
              f"shed rate {shed_rate:.1%} ({a['shed']}/{total})")
    serve.shutdown()


def _serve_stream(results: list[dict], windows: int = 3,
                  gen_tokens: int = 96):
    """Streaming inference bench (ROADMAP item 1 acceptance): tokens/s
    per replica and time-to-first-token through the HTTP proxy at 2x
    admission capacity, paired-interleaved against the PRESERVED
    request-level path (same integer-weight ShardedTokenLM, deployed
    once with streaming=True/SSE and once as a plain request/response
    backend whose whole generation blocks its slot).

    Capacity arithmetic: the continuous arm runs one engine with
    max_decode_batch=4 running sequences; 2x = 8 closed-loop SSE
    clients (the excess waits in the bounded admission queue and is
    admitted into the RUNNING batch between steps). The request-level
    arm serves the same 8 clients with max_batch_size=4 batches — a
    whole batch's generations complete before the next dispatch.

    Recorded per arm: tokens/s/replica (2xx tokens only), client-side
    TTFT p50/p99 (first SSE data frame; for request-level the full
    JSON IS the first byte, so TTFT == total latency — the coupling the
    tier decouples), and full-generation p99. The tier-1 gate
    (tests/test_serve_streaming.py::test_microbench_serve_stream_gate)
    asserts the recorded continuous row kept TTFT p99 under 25% of the
    full-generation p99 at 2x overload with tokens/s >= the
    request-level arm."""
    import http.client
    import threading as _threading

    import numpy as _np

    from ray_tpu import serve
    from ray_tpu.serve.engine import ShardedTokenLM
    from ray_tpu.serve.streaming import iter_sse_lines

    model = ShardedTokenLM.make(11, vocab=2048, hidden=64, inner=256)
    margs = (model.embed.copy(), model.w_up.copy(), model.w_out.copy())
    client = serve.start(http=True)
    client.create_backend(
        "bench_stream", ShardedTokenLM, *margs,
        config={"streaming": True, "max_decode_batch": 4,
                "max_waiting_sequences": 64, "kv_pages_total": 4096,
                "num_replicas": 1, "large_payload_threshold": 0})
    client.create_endpoint("bench_stream", backend="bench_stream",
                           route="/bench_stream", methods=["POST"])
    client.create_backend(
        "bench_reqlvl", ShardedTokenLM, *margs,
        config={"num_replicas": 1, "max_batch_size": 4,
                "batch_wait_timeout": 0.002, "max_concurrent_queries": 1,
                "large_payload_threshold": 0})
    client.create_endpoint("bench_reqlvl", backend="bench_reqlvl",
                           route="/bench_reqlvl", methods=["POST"])
    port = client.http_port
    n_clients = 8  # 2x the engine's 4 running slots

    def _req_tokens(i: int) -> int:
        # long-tailed lengths (x0.25, x0.5, x1, x4 of gen_tokens — the
        # LLM-traffic shape iteration-level scheduling exists for):
        # short sequences retire early and hand their running slot to
        # the admission queue mid-flight, while request-level lockstep
        # batches burn pad compute until their LONGEST row finishes
        return int(gen_tokens * (0.25, 0.5, 1.0, 4.0)[i % 4])

    def one_stream(i) -> tuple[float, float, int]:
        """(ttft, total, tokens) for one SSE generation."""
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        body = json.dumps({"prompt": [i % 7 + 1, 3, 5],
                           "max_tokens": _req_tokens(i), "stream": True})
        t0 = time.perf_counter()
        conn.request("POST", "/bench_stream", body=body, headers={
            "Content-Type": "application/json",
            "Accept": "text/event-stream"})
        resp = conn.getresponse()
        ttft, n = None, 0
        for ev, data in iter_sse_lines(resp.fp):
            if ev == "error":
                break
            if ttft is None and data.get("tokens"):
                ttft = time.perf_counter() - t0
            n += len(data.get("tokens") or [])
            if ev == "done" or data.get("done"):
                break
        total = time.perf_counter() - t0
        conn.close()
        return ttft if ttft is not None else total, total, n

    def one_reqlvl(i) -> tuple[float, float, int]:
        """(ttft, total, tokens) for one request-level generation —
        the full JSON is the first byte the client sees."""
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        body = json.dumps({"prompt": [i % 7 + 1, 3, 5],
                           "max_tokens": _req_tokens(i)})
        t0 = time.perf_counter()
        conn.request("POST", "/bench_reqlvl", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        doc = resp.read()
        total = time.perf_counter() - t0
        conn.close()
        if resp.status != 200:
            return total, total, 0
        return total, total, len(json.loads(doc).get("result") or [])

    def drive(fn, reqs_per_client: int = 3):
        ttfts: list[float] = []
        totals: list[float] = []
        counts = {"tokens": 0}
        lock = _threading.Lock()

        def worker(i):
            # staggered starts: closed-loop clients self-desynchronize
            # after a few requests; the stagger keeps window 1's TTFT
            # from measuring a thundering herd instead of steady state
            time.sleep(i * 0.025)
            for _ in range(reqs_per_client):
                try:
                    ttft, total, n = fn(i)
                except (http.client.HTTPException, OSError):
                    continue
                with lock:
                    if n:
                        ttfts.append(ttft)
                        totals.append(total)
                        counts["tokens"] += n

        threads = [_threading.Thread(target=worker, args=(i,))
                   for i in range(n_clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        return ttfts, totals, counts["tokens"], dt

    # warm both routes (the route table syncs asynchronously) and both
    # engines' first-step paths
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            if one_stream(0)[2] and one_reqlvl(0)[2]:
                break
        except Exception:
            pass
        time.sleep(0.3)

    arms = {"serve_stream continuous 2x": one_stream,
            "serve_stream request-level 2x": one_reqlvl}
    acc = {name: {"ttft": [], "total": [], "tokens": 0, "dt": 0.0}
           for name in arms}
    for _ in range(windows):  # paired: load swings hit both arms
        for name, fn in arms.items():
            ttfts, totals, tokens, dt = drive(fn)
            a = acc[name]
            a["ttft"].extend(ttfts)
            a["total"].extend(totals)
            a["tokens"] += tokens
            a["dt"] += dt
    for name, a in acc.items():
        tps = a["tokens"] / a["dt"] if a["dt"] else 0.0
        row = {
            "name": name,
            "tokens_per_s_per_replica": round(tps, 1),
            "ttft_p50_ms": round(float(_np.percentile(a["ttft"], 50))
                                 * 1000, 1) if a["ttft"] else 0.0,
            "ttft_p99_ms": round(float(_np.percentile(a["ttft"], 99))
                                 * 1000, 1) if a["ttft"] else 0.0,
            "gen_p99_ms": round(float(_np.percentile(a["total"], 99))
                                * 1000, 1) if a["total"] else 0.0,
            "generations": len(a["total"]),
            "gen_tokens": gen_tokens,
            "clients": n_clients,
            "windows": windows,
        }
        results.append(row)
        print(f"{name}: {tps:.1f} tok/s/replica, ttft p99 "
              f"{row['ttft_p99_ms']:.0f}ms, gen p99 "
              f"{row['gen_p99_ms']:.0f}ms ({row['generations']} gens)")
    serve.shutdown()


def _serve_prefix(results: list[dict], windows: int = 3,
                  prefix_tokens: int = 2048, gen_tokens: int = 16):
    """Cross-session prefix-sharing bench (ROADMAP item 4 acceptance):
    a multi-tenant workload where every request carries the same long
    page-aligned system prefix (prefix_tokens, a whole-page multiple of
    kv_page_size) plus a short per-session tail, paired-interleaved
    against an identical backend with prefix_sharing=False — the
    per-session baseline that re-prefills the shared prefix for every
    admission.

    Recorded per arm: tokens/s/replica, client-side TTFT p50/p99 (first
    SSE data frame), full-generation p99; the shared arm additionally
    records the replica's prefix counters (hits, tokens saved, hit
    rate, shared pages) read from engine_state AFTER the drive. The
    tier-1 gate (test_serve_streaming.py::
    test_microbench_serve_prefix_gate) asserts a nonzero recorded
    hit-rate and shared-arm TTFT p99 no worse than the baseline."""
    import http.client
    import threading as _threading

    import numpy as _np

    from ray_tpu import serve
    from ray_tpu.serve.engine import ShardedTokenLM
    from ray_tpu.serve.streaming import iter_sse_lines

    # model sized so prefill embed (~10ms for the full prefix) is the
    # dominant TTFT term — the thing prefix sharing actually removes
    model = ShardedTokenLM.make(11, vocab=2048, hidden=256, inner=512)
    margs = (model.embed.copy(), model.w_up.copy(), model.w_out.copy())
    page = 16
    assert prefix_tokens % page == 0
    base_cfg = {"streaming": True, "max_decode_batch": 4,
                "max_waiting_sequences": 64, "kv_page_size": page,
                "kv_pages_total": 2560, "num_replicas": 1,
                "prefix_index_max_nodes": 2 * prefix_tokens // page,
                "large_payload_threshold": 0}
    client = serve.start(http=True)
    client.create_backend("bench_pfx_shared", ShardedTokenLM, *margs,
                          config={**base_cfg, "prefix_sharing": True})
    client.create_endpoint("bench_pfx_shared",
                           backend="bench_pfx_shared",
                           route="/bench_pfx_shared", methods=["POST"])
    client.create_backend("bench_pfx_base", ShardedTokenLM, *margs,
                          config={**base_cfg, "prefix_sharing": False})
    client.create_endpoint("bench_pfx_base", backend="bench_pfx_base",
                           route="/bench_pfx_base", methods=["POST"])
    port = client.http_port
    n_clients = 8
    # the fleet-shared system prompt: page-aligned by construction
    shared_prefix = [(7 * i + 3) % 2048 for i in range(prefix_tokens)]

    def one(route):
        def fn(i) -> tuple[float, float, int]:
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=120)
            body = json.dumps({
                "prompt": shared_prefix + [i % 7 + 1, 3],
                "max_tokens": gen_tokens, "stream": True})
            t0 = time.perf_counter()
            conn.request("POST", route, body=body, headers={
                "Content-Type": "application/json",
                "Accept": "text/event-stream"})
            resp = conn.getresponse()
            ttft, n = None, 0
            for ev, data in iter_sse_lines(resp.fp):
                if ev == "error":
                    break
                if ttft is None and data.get("tokens"):
                    ttft = time.perf_counter() - t0
                n += len(data.get("tokens") or [])
                if ev == "done" or data.get("done"):
                    break
            total = time.perf_counter() - t0
            conn.close()
            return ttft if ttft is not None else total, total, n
        return fn

    def drive(fn, reqs_per_client: int = 3):
        ttfts: list[float] = []
        totals: list[float] = []
        counts = {"tokens": 0}
        lock = _threading.Lock()

        def worker(i):
            time.sleep(i * 0.025)  # de-herd window starts
            for _ in range(reqs_per_client):
                try:
                    ttft, total, n = fn(i)
                except (http.client.HTTPException, OSError):
                    continue
                with lock:
                    if n:
                        ttfts.append(ttft)
                        totals.append(total)
                        counts["tokens"] += n

        threads = [_threading.Thread(target=worker, args=(i,))
                   for i in range(n_clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return ttfts, totals, counts["tokens"], time.perf_counter() - t0

    arms = {"serve_prefix shared": one("/bench_pfx_shared"),
            "serve_prefix per-session baseline": one("/bench_pfx_base")}
    deadline = time.time() + 30
    while time.time() < deadline:  # route-table warmup
        try:
            if all(fn(0)[2] for fn in arms.values()):
                break
        except Exception:
            pass
        time.sleep(0.3)

    acc = {name: {"ttft": [], "total": [], "tokens": 0, "dt": 0.0}
           for name in arms}
    for _ in range(windows):  # paired: load swings hit both arms
        for name, fn in arms.items():
            ttfts, totals, tokens, dt = drive(fn)
            a = acc[name]
            a["ttft"].extend(ttfts)
            a["total"].extend(totals)
            a["tokens"] += tokens
            a["dt"] += dt

    # the shared replica's own books: hits / tokens saved / hit rate
    import ray_tpu as _rt
    state = _rt.get(client._controller.get_routing_state.remote(
        "bench_pfx_shared"), timeout=30)
    eng = _rt.get(state["backends"]["bench_pfx_shared"]["replicas"][0]
                  .engine_state.remote(), timeout=30)
    pref = (eng.get("kv") or {}).get("prefix") or {}

    for name, a in acc.items():
        tps = a["tokens"] / a["dt"] if a["dt"] else 0.0
        row = {
            "name": name,
            "tokens_per_s_per_replica": round(tps, 1),
            "ttft_p50_ms": round(float(_np.percentile(a["ttft"], 50))
                                 * 1000, 1) if a["ttft"] else 0.0,
            "ttft_p99_ms": round(float(_np.percentile(a["ttft"], 99))
                                 * 1000, 1) if a["ttft"] else 0.0,
            "gen_p99_ms": round(float(_np.percentile(a["total"], 99))
                                * 1000, 1) if a["total"] else 0.0,
            "generations": len(a["total"]),
            "prefix_tokens": prefix_tokens,
            "gen_tokens": gen_tokens,
            "clients": n_clients,
            "windows": windows,
        }
        if name == "serve_prefix shared":
            row.update({
                "prefix_hits": pref.get("hits", 0),
                "prefix_hit_rate": pref.get("hit_rate", 0.0),
                "prefix_tokens_saved": pref.get("tokens_saved", 0),
                "kv_pages_shared": (eng.get("kv") or {}).get(
                    "pages_shared", 0),
            })
        results.append(row)
        print(f"{name}: {tps:.1f} tok/s/replica, ttft p50 "
              f"{row['ttft_p50_ms']:.0f}ms p99 "
              f"{row['ttft_p99_ms']:.0f}ms ({row['generations']} gens)")
    print(f"serve_prefix shared counters: hits={pref.get('hits')} "
          f"saved={pref.get('tokens_saved')} "
          f"hit_rate={pref.get('hit_rate')}")
    serve.shutdown()


def _tracing_ab(results: list[dict]):
    """Distributed-tracing overhead A/B (the tier-1 microbench gate in
    test_observability reads these rows): tracing at the DEFAULT head
    sampling rate (1%, what a cluster pays out of the box) against a
    tracing-off control, paired-interleaved on the two rows the gate
    watches — tasks sync and serve http qps. The sampling flip rides the
    live KV+pubsub plane (`ray_tpu.set_trace_sampling`), so both slices
    of each window run identical code; the only delta is maybe_trace()'s
    rate check on every entry point plus span record/flush for the ~1%
    sampled calls."""
    from ray_tpu import serve

    def arm(rate: float):
        def setup():
            ray_tpu.set_trace_sampling(rate)
            # the pubsub flip reaches raylet/worker/proxy processes
            # asynchronously; give it a beat before the slice starts
            time.sleep(0.1)

        return setup

    TR = lambda fn: {"": (arm(0.01), fn),  # noqa: E731
                     "tracing-off control": (arm(0.0), fn)}

    @ray_tpu.remote
    def small_task():
        return b"ok"

    def task_sync():
        ray_tpu.get(small_task.remote())

    timeit_ab("tracing A/B tasks sync", TR(task_sync), results=results)

    # serve http: the sampling rate toggles between the two slices of
    # EACH window so box-load swings hit both arms equally.
    client = serve.start(http=True)
    client.create_backend("noop_tr", lambda _=None: "ok", config={
        "num_replicas": 2, "max_batch_size": 32,
        "batch_wait_timeout": 0.001, "max_concurrent_queries": 8})
    client.create_endpoint("noop_tr", backend="noop_tr", route="/noop_tr")
    handle = client.get_handle("noop_tr")
    ray_tpu.get(handle.remote(None), timeout=60)  # warm the path

    import threading as _threading
    from concurrent.futures import ThreadPoolExecutor

    pool = ThreadPoolExecutor(max_workers=16)
    tls = _threading.local()
    port = client.http_port

    def http_window(seconds: float = 0.7) -> float:
        return _http_qps_window(pool, tls, port, "/noop_tr", seconds)

    arm(0.01)()
    http_window(0.2)  # warm keep-alive conns
    on_rates, off_rates = [], []
    for _ in range(5):
        arm(0.01)()
        on_rates.append(http_window())
        arm(0.0)()
        off_rates.append(http_window())
    arm(0.01)()  # leave the cluster at the default rate
    _rate_rows(results, [
        ("tracing A/B serve http qps", on_rates),
        ("tracing A/B serve http qps (tracing-off control)", off_rates),
    ], windows=5)
    pool.shutdown()
    serve.shutdown()


def _profiling_ab(results: list[dict]):
    """Continuous-profiler overhead A/B (the tier-1 gate in
    test_observability reads these rows): the wall-clock sampler armed
    at its DEFAULT rate (~67 Hz, what every process pays out of the
    box) against a profiler-off control, paired-interleaved on the two
    rows the gate watches — tasks sync and serve http qps. The arm flip
    rides the live KV+pubsub plane (`ray_tpu.set_profiling`), so both
    slices of each window run identical code; the only delta is the
    sampler thread walking `sys._current_frames` plus the ~2s window
    flush into the GCS profile ring."""
    from ray_tpu import serve
    from ray_tpu._private import sampling_profiler as _sprof

    def arm(hz: float):
        def setup():
            ray_tpu.set_profiling(hz)
            # the pubsub flip reaches raylet/worker/proxy processes
            # asynchronously; give it a beat before the slice starts
            time.sleep(0.1)

        return setup

    default_hz = _sprof.default_hz()
    PR = lambda fn: {"": (arm(default_hz), fn),  # noqa: E731
                     "profiler-off control": (arm(0.0), fn)}

    @ray_tpu.remote
    def small_task():
        return b"ok"

    def task_sync():
        ray_tpu.get(small_task.remote())

    # 5 windows (not the default 3): the sampler's per-window cost is
    # small relative to box drift on this class of 1-2 core runner, so
    # the median needs more interleaved windows to converge
    timeit_ab("profiling A/B tasks sync", PR(task_sync), windows=5,
              results=results)

    client = serve.start(http=True)
    client.create_backend("noop_pr", lambda _=None: "ok", config={
        "num_replicas": 2, "max_batch_size": 32,
        "batch_wait_timeout": 0.001, "max_concurrent_queries": 8})
    client.create_endpoint("noop_pr", backend="noop_pr", route="/noop_pr")
    handle = client.get_handle("noop_pr")
    ray_tpu.get(handle.remote(None), timeout=60)  # warm the path

    import threading as _threading
    from concurrent.futures import ThreadPoolExecutor

    pool = ThreadPoolExecutor(max_workers=16)
    tls = _threading.local()
    port = client.http_port

    def http_window(seconds: float = 0.7) -> float:
        return _http_qps_window(pool, tls, port, "/noop_pr", seconds)

    arm(default_hz)()
    http_window(0.2)  # warm keep-alive conns
    on_rates, off_rates = [], []
    for _ in range(9):  # see the tasks-sync note: more pairs, less drift
        arm(default_hz)()
        on_rates.append(http_window())
        arm(0.0)()
        off_rates.append(http_window())
    arm(default_hz)()  # leave the cluster at the default rate
    _rate_rows(results, [
        ("profiling A/B serve http qps", on_rates),
        ("profiling A/B serve http qps (profiler-off control)",
         off_rates),
    ], windows=9)
    pool.shutdown()
    serve.shutdown()


def _state_ab(results: list[dict]):
    """Live-state-introspection overhead A/B (the tier-1 gate in
    tests/test_state_api.py reads these rows): the stall doctor armed
    at its 1s cadence — a background thread collecting cluster_state
    (GCS + raylet + per-worker debug_state fan-out) plus histogram
    diagnosis plus stall-event dedup EVERY second, ray_tpu.start_doctor
    — against a doctor-off control, paired-interleaved on the same two
    rows the tracing gate watches (tasks sync, serve http qps). The
    introspection plane must be cheap enough to leave armed in
    production: the gate fails tier-1 on >5% regression."""
    from ray_tpu import api as _api
    from ray_tpu import serve

    def arm(on: bool):
        def setup():
            if on:
                _api.start_doctor(interval=1.0)
            else:
                _api.stop_doctor()
            time.sleep(0.05)

        return setup

    AB = lambda fn: {"": (arm(True), fn),  # noqa: E731
                     "state-off control": (arm(False), fn)}

    @ray_tpu.remote
    def small_task():
        return b"ok"

    def task_sync():
        ray_tpu.get(small_task.remote())

    timeit_ab("state A/B tasks sync", AB(task_sync), results=results)
    _api.stop_doctor()

    client = serve.start(http=True)
    client.create_backend("noop_st", lambda _=None: "ok", config={
        "num_replicas": 2, "max_batch_size": 32,
        "batch_wait_timeout": 0.001, "max_concurrent_queries": 8})
    client.create_endpoint("noop_st", backend="noop_st", route="/noop_st")
    handle = client.get_handle("noop_st")
    ray_tpu.get(handle.remote(None), timeout=60)  # warm the path

    import threading as _threading
    from concurrent.futures import ThreadPoolExecutor

    pool = ThreadPoolExecutor(max_workers=16)
    tls = _threading.local()
    port = client.http_port

    def http_window(seconds: float = 0.7) -> float:
        return _http_qps_window(pool, tls, port, "/noop_st", seconds)

    http_window(0.2)  # warm keep-alive conns
    on_rates, off_rates = [], []
    for _ in range(5):
        arm(True)()
        on_rates.append(http_window())
        arm(False)()
        off_rates.append(http_window())
    arm(False)()
    _rate_rows(results, [
        ("state A/B serve http qps", on_rates),
        ("state A/B serve http qps (state-off control)", off_rates),
    ], windows=5)
    pool.shutdown()
    serve.shutdown()


def _control_plane(results: list[dict], shards: int = 4):
    """Sharded-control-plane scale-sim rows (scalesim/harness.py): 16
    spoofed raylets over 3 client processes drive the steady-state
    table-op mix and scheduler-decision stream against a real
    director+shards plane, paired-interleaved per window against the
    single-shard legacy arm (median of 5 windows), with a seeded
    mid-window SIGKILL+journal-replay restart of one shard.

    Besides the two rates, each row carries the **director-bypass**
    check — per-arm server CPU from /proc normalized per op. On boxes
    with fewer than shards+2 cores (this 2-core box included) the
    wall-clock rates UNDERSTATE the sharded plane: every extra server
    process multiplies per-tick socket syscalls (~0.4ms each under
    gVisor) on the same two cores, so the legacy arm's single perfectly-
    coalesced connection wins the transport race while its director
    burns ~14x the CPU per op. The scaling claim rides
    `director_cpu_us_per_op` (the single-process ceiling collapsing),
    not the same-box rate ratio; see PERF.md round 11."""
    from ray_tpu.scalesim.harness import run_scalesim

    sim = run_scalesim(shards=shards, raylets=16, windows=5,
                       window_s=1.0, client_procs=3, kill_shard=True)
    for label in (f"shards{shards}", "shards1"):
        arm = sim["arms"][label]
        suffix = ("" if label != "shards1"
                  else " (single-shard legacy control)")
        for kind, key in (("gcs ops", "gcs_ops_per_s"),
                          ("scheduler decisions", "decisions_per_s")):
            stat = arm[key]
            trials = stat["samples"]
            mean = sum(trials) / len(trials)
            sd = (sum((t - mean) ** 2 for t in trials)
                  / max(len(trials) - 1, 1)) ** 0.5
            row = {"name": f"control_plane {kind}{suffix}",
                   "per_second": stat["median"], "sd": round(sd, 2),
                   "trials": trials,
                   "director_cpu_us_per_op":
                       arm["director_cpu_us_per_op"]}
            if kind == "gcs ops" and not suffix:
                row["director_bypass_ratio"] = sim[
                    "director_bypass_ratio"]
                row["cores"] = sim["cores"]
                row["shard_kill"] = sim["kill"]
            results.append(row)
            print(f"{row['name']} per second "
                  f"{row['per_second']:.1f} "
                  f"(director {row['director_cpu_us_per_op']}us/op)")


def _placement_topology(results: list[dict], windows: int = 3):
    """Topology placement scale-sim row (scalesim/topology_sim.py): 16
    spoofed raylets with seeded-shuffled 4x4-torus coords answer the
    REAL 2PC against two live directors, paired-interleaved ICI_RING vs
    PACK windows. Per arm: mean ring circumference (torus wire around
    consecutive bundle ranks — ICI_RING's target is == world size, the
    perfect ring), simulated spillback-chain hops, client placement
    latency, and the director's own `gcs.placement_score_s` p99
    (warmup-excluded bucket delta; the <=5% latency A/B)."""
    from ray_tpu.scalesim.topology_sim import run_topology_sim

    sim = run_topology_sim(raylets=16, windows=windows, bundles=4)
    for arm in ("ici_ring", "pack"):
        a = sim["arms"][arm]
        lat_ms = a["placement_latency_ms"]["mean"]
        row = {"name": f"placement_topology {arm}",
               "per_second": round(1e3 / max(lat_ms, 1e-9), 2),
               "sd": 0.0,
               "gangs": a["gangs"],
               "mean_ring_circumference": a["mean_ring_circumference"],
               "mean_spillback_hops": a["mean_spillback_hops"],
               "placement_latency_ms": lat_ms,
               "score_p99_s": a["score_p99_s"],
               "fallbacks": a["fallbacks"],
               "leaked_holds": a["leaked_holds"]}
        if arm == "ici_ring":
            row["circumference_ratio_vs_pack"] = sim[
                "circumference_ratio"]
            row["spillback_hops_ratio_vs_pack"] = sim[
                "spillback_hops_ratio"]
            row["score_p99_ratio_vs_pack"] = sim["score_p99_ratio"]
        results.append(row)
        print(f"placement_topology {arm}: circumference "
              f"{a['mean_ring_circumference']}, spillback hops "
              f"{a['mean_spillback_hops']}, latency {lat_ms}ms")


def _train_sharded(results: list[dict], epochs: int = 3,
                   steps_per_epoch: int = 8):
    """ZeRO-sharded trainer A/B (paired arms, same model/data/steps):
    `replicated` = allreduce + full optax state on every worker; `zero`
    = reducescatter → shard update → allgather; `zero_int8` adds the
    int8 block-scaled grad wire. Rows record tokens/s, per-worker
    optimizer bytes (`train.optim_shard_bytes`), peak worker RSS, and —
    for the int8 arm — socket bytes saved, counter-verified against
    `collective.quantized_bytes_saved_total` next to the analytic exact
    wire size. A second pair (`train_ingest off/on`) runs the streaming
    ingest pipeline at depth 2 and records `train.ingest_wait_s` p50 —
    the tier-1 gate (tests/test_train_sharded.py) asserts the sharded
    arm's optimizer memory is below replicated's, the int8 arm saved
    >= 70% of exact wire bytes, and the ingest-on arm is not
    input-bound."""
    import jax.numpy as jnp
    import optax

    from ray_tpu.train import IngestSpec, Trainer, TrainingOperator
    from ray_tpu.train.ingest import hist_quantile
    from ray_tpu.train import sharding as _shardlib

    DIM, OUT, BS = 256, 96, 16  # 24576 params -> 96KiB f32 grad bucket
    WORLD = 3  # ring tier needs world > 2 (pairwise degenerates to hub)

    class BenchOp(TrainingOperator):
        def setup(self, config):
            rng = np.random.default_rng(0)
            X = rng.standard_normal((16, 256)).astype(np.float32)
            Y = rng.standard_normal((16, 96)).astype(np.float32)
            self.register(
                model_init=lambda k: {
                    "w": jnp.zeros((256, 96), jnp.float32)},
                loss_fn=lambda p, b: jnp.mean((b[0] @ p["w"] - b[1]) ** 2),
                optimizer=optax.adam(1e-3))
            if not config.get("bench_ingest"):
                self.register_data(
                    train_loader=[(X, Y)] * config["bench_steps"])

    def dataset_fn(shard_index, num_shards, config):
        rng = np.random.default_rng(shard_index)
        X = rng.standard_normal((16, 256)).astype(np.float32)
        Y = rng.standard_normal((16, 96)).astype(np.float32)
        return [(X, Y)] * config["bench_steps"]

    def run(name, *, sharded=False, quantize=None, ingest=False):
        config = {"bench_steps": steps_per_epoch, "bench_ingest": ingest}
        tr = Trainer(
            BenchOp, num_workers=WORLD, config=config, backend="host",
            collective_transport="ring", placement_strategy=None,
            sharded=sharded, quantize=quantize,
            # zero-CPU actors: the harness runs on 1-core containers and
            # the arms are a paired A/B, so logical-CPU contention
            # cancels out of every comparison the gate reads
            resources_per_worker={"CPU": 0},
            ingest=IngestSpec(dataset_fn, resources={"CPU": 0})
            if ingest else None)
        try:
            rates = []
            for _ in range(epochs):
                res = tr.train()
                rates.append(res["samples_per_s"])
            w = tr.workers
            opt_bytes = max(ray_tpu.get(
                [x.read_counter.remote("train.optim_shard_bytes")
                 for x in w], timeout=60))
            saved = sum(ray_tpu.get(
                [x.read_counter.remote(
                    "collective.quantized_bytes_saved_total")
                 for x in w], timeout=60))
            rss = max(ray_tpu.get(
                [x.peak_rss.remote() for x in w], timeout=60))
            wait = ray_tpu.get(
                w[0].read_metric.remote("train.ingest_wait_s"), timeout=60)
            row = {"name": name,
                   "per_second": float(np.median(rates)),
                   "sd": float(np.std(rates)),
                   "tokens_per_s": float(np.median(rates)),
                   "optim_state_bytes_per_worker": int(opt_bytes),
                   "peak_worker_rss_mb": round(rss / 1e6, 1),
                   "wire_saved_bytes": int(saved)}
            if quantize:
                # analytic exact-tier wire: (w-1) * chunk elems * 4B per
                # reducescatter, one per step per worker
                pad = _shardlib.padded_numel(DIM * OUT, WORLD)
                steps = epochs * steps_per_epoch
                row["wire_exact_bytes"] = int(
                    steps * WORLD * (WORLD - 1) * (pad // WORLD) * 4)
            if ingest:
                row["ingest_wait_p50_s"] = hist_quantile(wait or {}, 0.5)
                row["ingest_wait_count"] = (wait or {}).get("count", 0)
            results.append(row)
            print(f"{name}: {row['per_second']:.1f} tokens/s, "
                  f"opt {opt_bytes / 1024:.0f}KiB/worker, "
                  f"rss {row['peak_worker_rss_mb']}MB, "
                  f"wire saved {int(saved)}B")
        finally:
            tr.shutdown(force=True)

    run("train_sharded replicated")
    run("train_sharded zero", sharded=True)
    run("train_sharded zero_int8", sharded=True, quantize="int8")
    run("train_ingest off", sharded=True)
    run("train_ingest on depth2", sharded=True, ingest=True)


if __name__ == "__main__":
    from ray_tpu._private.bench_meta import run_metadata as _metadata
    import argparse

    parser = argparse.ArgumentParser()
    parser.add_argument("--json", action="store_true",
                        help="also print one JSON line with all results")
    parser.add_argument("--out", default=None,
                        help="write results JSON to this path")
    parser.add_argument("--only", default=None,
                        help="run a single bench group (e.g. serve_mixed)"
                             " instead of the full suite; always includes"
                             " the same-window calibration controls")
    parser.add_argument("--merge", default=None,
                        help="merge this run's rows into an existing "
                             "results JSON (same-name rows replaced, new"
                             " ones appended) — for recording one new "
                             "bench without a full-suite rerun")
    args = parser.parse_args()
    if args.only:
        groups = {"serve_mixed": _serve_mixed, "serve": _serve_qps,
                  "serve_stream": _serve_stream,
                  "serve_prefix": _serve_prefix,
                  "tracing": _tracing_ab, "state": _state_ab,
                  "collective": _collective_bench,
                  "placement_topology": _placement_topology,
                  "train_sharded": _train_sharded}
        if args.only not in groups:
            parser.error(f"--only must be one of {sorted(groups)}")
        results: list = []
        calibrate(results)
        ray_tpu.init()
        try:
            groups[args.only](results)
        finally:
            ray_tpu.shutdown()
    else:
        results = main()
    doc = {"metadata": _metadata(), "results": results}
    if args.merge:
        with open(args.merge) as f:
            base = json.load(f)
        rows = {r["name"]: r for r in results}
        # the base file's calibration rows contextualize ITS rows; this
        # partial window's calibration travels with the partial-run
        # metadata instead of overwriting them
        calib = {n: rows.pop(n) for n in list(rows)
                 if n.startswith("calibration")}
        merged = [rows.pop(r["name"], r) for r in base["results"]]
        merged.extend(rows.values())
        base["results"] = merged
        base.setdefault("metadata", {})
        base["metadata"]["last_partial_run"] = {
            "only": args.only, "calibration": list(calib.values()),
            **_metadata()}
        doc = base
        with open(args.merge, "w") as f:
            json.dump(doc, f, indent=1)
    if args.json:
        print(json.dumps(doc))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=1)
