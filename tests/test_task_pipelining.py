"""Task-path pipelining invariants (round 8: de-churned submit →
lease → dispatch → reply → get).

Guards the properties the fast path must keep while pipelining:
per-caller actor ordering at in-flight > 1, the per-lease in-flight cap,
pre-warmed leases returned once the queue drains (no stranded workers),
correctness under the chaos tier, and — the anti-regression guard — a
fixed bound on per-task loop wakeups / executor hops so per-call churn
can't silently regrow."""

import time

import pytest

import ray_tpu
from ray_tpu import exceptions as exc
from tests.conftest import scale_timeout


def test_actor_order_preserved_at_depth(ray_start_regular):
    """Per-caller ordering must hold when many calls are in flight at
    once (pipelined pushes + reorder buffer + direct task channel)."""

    @ray_tpu.remote
    class Log:
        def __init__(self):
            self.seen = []

        def add(self, i):
            self.seen.append(i)
            return i

        def dump(self):
            return self.seen

    log = Log.remote()
    refs = [log.add.remote(i) for i in range(200)]
    assert ray_tpu.get(refs, timeout=scale_timeout(60)) == list(range(200))
    assert ray_tpu.get(log.dump.remote(),
                       timeout=scale_timeout(30)) == list(range(200))


def test_max_tasks_in_flight_respected():
    """No lease may ever carry more than max_tasks_in_flight_per_worker
    concurrent pushes."""
    cap = 2
    ray_tpu.init(num_cpus=4, _system_config={
        "max_tasks_in_flight_per_worker": cap})
    try:
        from ray_tpu._private import global_state

        cw = global_state.require_core_worker()

        @ray_tpu.remote
        def slowish():
            time.sleep(0.1)
            return 1

        refs = [slowish.remote() for _ in range(12)]
        max_seen = 0
        deadline = time.monotonic() + scale_timeout(30)
        while time.monotonic() < deadline:
            for leases in list(cw.leases.values()):
                for lease in list(leases):
                    max_seen = max(max_seen, lease.inflight)
            done, _ = ray_tpu.wait(refs, num_returns=len(refs), timeout=0)
            if len(done) == len(refs):
                break
            time.sleep(0.005)
        assert sum(ray_tpu.get(refs, timeout=scale_timeout(30))) == 12
        assert 0 < max_seen <= cap, max_seen
    finally:
        ray_tpu.shutdown()


def test_prewarm_leases_returned_when_queue_drains(ray_start_regular):
    """Lease pre-warm must not strand workers: once the burst drains and
    the idle grace passes, every lease goes back to the raylet."""
    from ray_tpu._private import global_state

    cw = global_state.require_core_worker()

    @ray_tpu.remote
    def small():
        return 1

    assert sum(ray_tpu.get([small.remote() for _ in range(100)],
                           timeout=scale_timeout(60))) == 100
    deadline = time.monotonic() + scale_timeout(10)
    while time.monotonic() < deadline and cw.leases:
        time.sleep(0.05)
    assert not cw.leases, {
        k: len(v) for k, v in cw.leases.items()}
    # and the pool is reusable afterwards — nothing stayed leased
    assert ray_tpu.get(small.remote(), timeout=scale_timeout(30)) == 1


def test_task_channel_wired(ray_start_regular):
    """Same-node leases must carry the direct task channel (UDS served
    by the worker's executor); correctness is covered everywhere else —
    this pins the wiring so a refactor can't silently fall back to the
    slow path."""
    from ray_tpu._private import global_state

    cw = global_state.require_core_worker()

    @ray_tpu.remote
    def slowish():
        time.sleep(0.2)
        return 1

    refs = [slowish.remote() for _ in range(4)]
    saw_channel = False
    deadline = time.monotonic() + scale_timeout(20)
    while time.monotonic() < deadline and not saw_channel:
        for leases in list(cw.leases.values()):
            for lease in list(leases):
                if lease.task_conn is not None:
                    saw_channel = True
        time.sleep(0.01)
    ray_tpu.get(refs, timeout=scale_timeout(30))
    assert saw_channel


def test_per_task_churn_bounded(ray_start_regular):
    """Tier-1 anti-regression guard: per completed task the driver must
    stay under a fixed budget of loop wakeups and sent frames, and the
    worker under a fixed executor-hop budget. Round 7 paid one wakeup
    per reply, one timer per push, and one flush submit per execution;
    if those return, these bounds break loudly."""
    from ray_tpu._private import global_state, stats

    cw = global_state.require_core_worker()

    @ray_tpu.remote
    def small():
        return 1

    ray_tpu.get(small.remote(), timeout=scale_timeout(30))  # warm the pool

    n = 200
    before = stats.snapshot()
    for _ in range(2):
        ray_tpu.get([small.remote() for _ in range(n // 2)],
                    timeout=scale_timeout(60))
    after = stats.snapshot()

    def delta(name):
        return (after.get(name, {}).get("value", 0)
                - before.get(name, {}).get("value", 0))

    completed = delta("core.tasks_completed_total")
    assert completed >= n
    # driver-side: coalescing keeps wakeups far below one per task;
    # frames ≈ one push per task plus a little control traffic
    assert delta("rpc.loop_wakeups_total") / completed <= 1.0
    assert delta("rpc.frames_sent_total") / completed <= 3.0
    # worker-side: one dispatcher handoff per executed task, nothing more
    metrics = ray_tpu.cluster_metrics()
    for snap in metrics["raylets"].values():
        executed = snap.get("core.tasks_executed_total", {}).get("value", 0)
        hops = snap.get("core.exec_hops_total", {}).get("value", 0)
        if executed:
            assert hops / executed <= 2.0, (hops, executed)
            break
    else:
        pytest.fail("no worker metrics aggregated")


def test_task_path_survives_chaos(monkeypatch):
    """The pipelined path (batched leases, direct channel, deferred
    replies) under randomized frame delays + connection kills: results
    stay correct, ordering holds."""
    monkeypatch.setenv("RAY_TPU_CHAOS", "delay_p=0.2,delay_ms=20")
    from ray_tpu._private import rpc

    monkeypatch.setattr(rpc, "_CHAOS", rpc._chaos_config())
    ray_tpu.init(num_cpus=4)
    try:
        @ray_tpu.remote
        def square(x):
            return x * x

        refs = [square.remote(i) for i in range(60)]
        assert ray_tpu.get(refs, timeout=scale_timeout(120)) == [
            i * i for i in range(60)]

        @ray_tpu.remote
        class Log:
            def __init__(self):
                self.seen = []

            def add(self, i):
                self.seen.append(i)
                return i

            def dump(self):
                return self.seen

        log = Log.remote()
        ray_tpu.get([log.add.remote(i) for i in range(60)],
                    timeout=scale_timeout(120))
        assert ray_tpu.get(log.dump.remote(),
                           timeout=scale_timeout(60)) == list(range(60))
    finally:
        ray_tpu.shutdown()


# ---- memstore ready-callback semantics (h_get_object owner service) ----

def test_memstore_delete_fires_callbacks():
    from ray_tpu._private.ids import ObjectID
    from ray_tpu._private.memstore import MemoryStore

    store = MemoryStore()
    oid = ObjectID(b"x" * 24)
    store.open(oid)
    fired = []
    assert store.add_ready_callback(oid, lambda: fired.append(1),
                                    create=False)
    store.delete(oid)
    assert fired == [1]
    found, _, _ = store.get_if_ready(oid)
    assert not found  # waiter observes loss, maps to ObjectLostError


def test_memstore_callback_create_flag_and_removal():
    from ray_tpu._private.ids import ObjectID
    from ray_tpu._private.memstore import MemoryStore

    store = MemoryStore()
    oid = ObjectID(b"y" * 24)
    # create=False on a missing entry must not resurrect it
    assert not store.add_ready_callback(oid, lambda: None, create=False)
    assert store.size() == 0

    store.open(oid)
    fired = []
    cb = lambda: fired.append(1)  # noqa: E731
    store.add_ready_callback(oid, cb)
    store.remove_ready_callback(oid, cb)
    store.put(oid, b"v")
    assert fired == []  # removed callback never fires

    # ready entry fires immediately
    store.add_ready_callback(oid, cb)
    assert fired == [1]


def test_memstore_survives_a_finalizer_inside_its_lock(monkeypatch):
    """The cyclic GC may run an ObjectRef's __del__ (which ends in
    `delete`) at any allocation, also at the `_Entry()` a locked region
    of the store makes — on the same thread. That must not deadlock."""
    import threading

    from ray_tpu._private import memstore
    from ray_tpu._private.ids import ObjectID

    store = memstore.MemoryStore()
    doomed, fresh = ObjectID(b"d" * 24), ObjectID(b"f" * 24)
    store.open(doomed)
    fired = []
    store.add_ready_callback(doomed, lambda: fired.append(1))

    class Finalizing(memstore._Entry):
        __slots__ = ()

        def __init__(self):          # what a collection at this point does
            super().__init__()
            store.delete(doomed)

    monkeypatch.setattr(memstore, "_Entry", Finalizing)
    done = []

    def locked_regions():
        store.open(fresh)
        store.add_ready_callback(ObjectID(b"g" * 24), lambda: None)
        store.put(ObjectID(b"h" * 24), b"v")
        done.append(True)

    t = threading.Thread(target=locked_regions, daemon=True)
    t.start()
    t.join(scale_timeout(10))
    assert done == [True], "the store deadlocked on its own lock"
    assert fired == [1] and not store.contains(doomed)
    assert store.get_if_ready(ObjectID(b"h" * 24))[:2] == (True, b"v")


def test_cancel_still_reaches_channel_queued_tasks(ray_start_regular):
    """Tasks buffered behind the direct channel must still be
    cancellable before they start (the socket is not a blind spot)."""

    @ray_tpu.remote
    def busy():
        time.sleep(scale_timeout(5))
        return "done"

    # 3× blockers per worker slot: the victim must still be queued when
    # the cancel lands regardless of how the burst fans across leases
    blockers = [busy.remote() for _ in range(12)]
    victim = busy.remote()
    time.sleep(0.5)
    ray_tpu.cancel(victim)
    with pytest.raises((exc.TaskCancelledError, exc.WorkerCrashedError)):
        ray_tpu.get(victim, timeout=scale_timeout(30))
    del blockers
