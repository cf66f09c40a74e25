"""GCS fault tolerance: kill the GCS mid-session and the cluster keeps
working (reference behavior: python/ray/tests/test_gcs_fault_tolerance.py;
persistence: src/ray/gcs/gcs_server/gcs_table_storage.h:294).

The head node's monitor restarts a crashed GCS on its old port against the
persisted WAL/snapshot; raylets and drivers redial and re-register
(rpc.ReconnectingConnection), so named actors, KV state, and task
submission all survive."""

import time

import pytest

import ray_tpu
from ray_tpu import api as _api
from ray_tpu.experimental import internal_kv


@pytest.fixture
def gcs_cluster():
    ray_tpu.init(num_cpus=4)
    try:
        yield _api._global_node
    finally:
        ray_tpu.shutdown()


def _kill_gcs_and_wait_restart(node):
    old_pid = next(s.proc.pid for s in node.processes
                   if s.name == "gcs_server")
    node.kill_gcs()
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        gcs = next((s for s in node.processes if s.name == "gcs_server"),
                   None)
        if gcs is not None and gcs.alive() and gcs.proc.pid != old_pid:
            return
        time.sleep(0.1)
    raise TimeoutError("GCS was not restarted by the node monitor")


def test_cluster_survives_gcs_restart(gcs_cluster):
    node = gcs_cluster

    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.v = 0

        def inc(self):
            self.v += 1
            return self.v

    c = Counter.options(name="survivor").remote()
    assert ray_tpu.get(c.inc.remote()) == 1
    internal_kv._kv_put("gcs_ft_key", b"gcs_ft_value")

    _kill_gcs_and_wait_restart(node)

    # Existing actor handle keeps working (actor process never died).
    assert ray_tpu.get(c.inc.remote(), timeout=30) == 2

    # KV survived the restart.
    assert internal_kv._kv_get("gcs_ft_key") == b"gcs_ft_value"

    # Named-actor lookup (GCS-served) works against restored tables.
    again = ray_tpu.get_actor("survivor")
    assert ray_tpu.get(again.inc.remote(), timeout=30) == 3

    # Fresh task submission end-to-end after the restart.
    @ray_tpu.remote
    def f(x):
        return x + 1

    assert ray_tpu.get(f.remote(41), timeout=60) == 42


def test_actor_restart_after_gcs_restart(gcs_cluster):
    """An actor killed AFTER a GCS restart still restarts (the restored
    actor table kept its spec + max_restarts)."""
    node = gcs_cluster

    @ray_tpu.remote(max_restarts=1)
    class Phoenix:
        def pid(self):
            import os

            return os.getpid()

    p = Phoenix.remote()
    pid1 = ray_tpu.get(p.pid.remote())

    _kill_gcs_and_wait_restart(node)

    import os
    import signal

    os.kill(pid1, signal.SIGKILL)
    deadline = time.monotonic() + 30
    pid2 = None
    while time.monotonic() < deadline:
        try:
            pid2 = ray_tpu.get(p.pid.remote(), timeout=10)
            break
        except Exception:
            time.sleep(0.2)
    assert pid2 is not None and pid2 != pid1


def test_the_checkers_own_stall_is_not_a_nodes_silence(monkeypatch):
    """A host that stops as a whole (libtpu initialising four chips
    freezes the machine for seconds) stops the GCS too: when the checker
    wakes late it credits every node the time it overslept, instead of
    declaring dead a raylet whose queued beats it has not read yet. A
    node that really is silent still goes after the normal timeout."""
    import asyncio
    import types

    from ray_tpu._private.config import Config
    from ray_tpu.gcs import server

    cfg = Config()
    timeout = cfg.heartbeat_interval_s * cfg.num_heartbeats_timeout
    clock = {"now": 100.0}
    naps = iter([cfg.heartbeat_interval_s] * 3 + [timeout + 5.0]
                + [cfg.heartbeat_interval_s] * 200)
    removed = []

    async def nap(_):
        try:
            clock["now"] += next(naps)
        except StopIteration:
            raise asyncio.CancelledError

    async def remove(node_id, reason):
        removed.append((node_id, clock["now"], reason))
        del gcs.last_heartbeat[node_id]

    monkeypatch.setattr(server.asyncio, "sleep", nap)
    monkeypatch.setattr(server.time, "monotonic", lambda: clock["now"])
    gcs = types.SimpleNamespace(config=cfg, nodes={}, _remove_node=remove,
                                last_heartbeat={b"n": 100.0})
    with pytest.raises(asyncio.CancelledError):
        asyncio.run(server.GcsServer.heartbeat_checker(gcs))
    (node, when, reason), = removed
    # alive through the stall (which alone outlasted the timeout); dead
    # one timeout of real silence later
    stall_end = 100.0 + 3 * cfg.heartbeat_interval_s + timeout + 5.0
    assert node == b"n" and reason == "heartbeat timeout"
    assert stall_end + timeout - 2.0 < when <= stall_end + timeout + 1.0
