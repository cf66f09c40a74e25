"""The end of a session (ARCHITECTURE.md, "How a session ends"): when
`ray_tpu.shutdown()` returns, nothing the session started is left in the
process table; the one wait behind it (`node.wait_until_left`) asks the
process table and not `cmdline`; a chip-owning worker waits, bounded, for
chips that are still being released."""

import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu._private import accelerator, node
from ray_tpu.train import Trainer, TrainingOperator


def _in_table(pid: int) -> bool:
    """Independent of the code under test: any task of `pid` that is
    there and not a zombie."""
    try:
        tids = os.listdir(f"/proc/{pid}/task")
    except OSError:
        return False
    for tid in tids:
        try:
            with open(f"/proc/{pid}/task/{tid}/stat") as f:
                stat = f.read()
        except OSError:
            continue
        if stat[stat.rindex(")") + 2] not in "ZX":
            return True
    return False


def _cmdline_scan(mark: str) -> list[int]:
    """`benchmark/run.py::wait_for_exit`'s kind of scan: the processes
    whose command line carries `mark`."""
    pids = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                if mark.encode() in f.read():
                    pids.append(int(pid))
        except OSError:
            pass
    return [p for p in pids if p != os.getpid()]


def _state(pid: int) -> str:
    with open(f"/proc/{pid}/stat") as f:
        stat = f.read()
    return stat[stat.rindex(")") + 2]


def _until(cond, what: str, timeout: float = 30.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.01)


@ray_tpu.remote
class Resident:
    def pid(self):
        return os.getpid()

    def spin(self):
        while True:
            sum(range(1000))

    def stop_myself(self):
        # deaf to everything but SIGKILL and SIGCONT from here on
        os.kill(os.getpid(), signal.SIGSTOP)


def _busy_actor():
    actor = Resident.remote()
    pid = ray_tpu.get(actor.pid.remote(), timeout=60)
    actor.spin.remote()
    return [pid]


def _worker_still_starting():
    return []  # shutdown() at once: the pool's workers have not registered


def _actor_deaf_to_a_polite_exit():
    actor = Resident.remote()
    pid = ray_tpu.get(actor.pid.remote(), timeout=60)
    actor.stop_myself.remote()
    _until(lambda: _state(pid) == "T", "the actor to stop itself")
    # forceful, and carried out before it returns: no timer is left in a
    # raylet that the next line kills
    ray_tpu.kill(actor)
    assert not _in_table(pid)
    return [pid]


@pytest.mark.parametrize("prepare", [
    _busy_actor, _worker_still_starting, _actor_deaf_to_a_polite_exit])
def test_nothing_of_the_session_is_alive_when_shutdown_returns(prepare):
    session = ray_tpu.init(num_cpus=2)["session_dir"]
    try:
        pids = prepare()
        # while all of them are alive their command lines can be trusted
        started = _cmdline_scan(session)
        assert len(started) >= 3  # GCS, raylet, at least one worker
    finally:
        ray_tpu.shutdown()
    # no polling: when shutdown() has returned, it is over
    alive = [pid for pid in set(started + pids) if _in_table(pid)]
    assert not alive, f"still in the process table: {alive}"


_LEADER_EXITS = """
import ctypes, threading, time
threading.Thread(target=time.sleep, args=(120,)).start()
print("ready", flush=True)
ctypes.CDLL(None).syscall(60, 0)   # exit(2): this thread alone
"""


def test_the_wait_asks_the_process_table_not_cmdline():
    """A process whose leader has exited reads as a zombie with an empty
    `cmdline` while another of its threads lives on with every file
    descriptor: a `cmdline` scan calls it gone, `has_left` does not."""
    mark = f"--session-dir=/nonexistent/{os.getpid()}"
    proc = subprocess.Popen([sys.executable, "-c", _LEADER_EXITS, mark],
                            stdout=subprocess.PIPE, start_new_session=True)
    try:
        proc.stdout.readline()
        assert _cmdline_scan(mark) == [proc.pid] or _state(proc.pid) == "Z"
        _until(lambda: _state(proc.pid) == "Z", "the leader's exit")
        with open(f"/proc/{proc.pid}/cmdline", "rb") as f:
            assert f.read() == b""
        assert _cmdline_scan(mark) == []
        assert not node.has_left(proc.pid)
        assert node.group_members([proc.pid]) == [proc.pid]
        os.killpg(proc.pid, signal.SIGKILL)
        took = node.wait_until_left(
            lambda: node.group_members([proc.pid]), bound=10.0)
        assert took < 10.0
        # a killed child nobody has reaped: still in /proc, holding
        # nothing, and counted as gone
        assert os.path.exists(f"/proc/{proc.pid}")
        assert node.has_left(proc.pid)
    finally:
        proc.kill()
        proc.wait()
    assert node.has_left(proc.pid)


def test_running_into_the_bound_names_the_processes():
    proc = subprocess.Popen([sys.executable, "-c",
                             "import time; time.sleep(120)"],
                            start_new_session=True)
    try:
        t0 = time.monotonic()
        # signal 0 ends nothing: the group outlives the bound
        with pytest.raises(node.ProcessesStillAlive) as err:
            node.end_process_groups([proc.pid], sig=0, bound=0.3)
        assert 0.3 <= time.monotonic() - t0 < 5.0
        assert str(proc.pid) in str(err.value)
        assert "state" in str(err.value)
    finally:
        assert node.end_process_groups([proc.pid]) < 10.0
        proc.wait()


class _Probe:
    """Device nodes that stay held for the first `busy_polls` asks."""

    def __init__(self, busy_polls):
        self.busy_polls, self.asked = busy_polls, 0

    def __call__(self):
        self.asked += 1
        return ["/dev/vfio/0"] if self.asked <= self.busy_polls else []


@pytest.mark.parametrize("busy_polls, pause, bound, message", [
    (0, 0.01, 60.0, None),                    # free at once
    (3, 0.4, 60.0, "were still held by a process that was ending"),
    (10 ** 9, 0.01, 0.05, "starting all the same"),   # past the bound
])
def test_a_chip_owner_waits_for_a_held_chip(busy_polls, pause, bound,
                                            message, caplog):
    probe = _Probe(busy_polls)
    with caplog.at_level("INFO", logger="ray_tpu.accelerator"):
        waited = accelerator.wait_for_chips(probe, bound=bound, pause=pause)
    if message is None:
        # a free chip costs one ask, no time and no log line
        assert waited < 0.05 and probe.asked == 1 and not caplog.records
        return
    assert [r.getMessage() for r in caplog.records
            if message in r.getMessage()], caplog.text
    if busy_polls == 3:
        assert probe.asked == 4 and 3 * pause <= waited < 3 * pause + 1.0
    else:
        # the worker then starts and fails with libtpu's own error, as
        # it did before there was a wait: nothing is raised here
        assert bound <= waited < bound + 1.0


def test_held_nodes_counts_ebusy_alone(tmp_path, monkeypatch):
    free = tmp_path / "free"
    free.write_bytes(b"")
    real_open = os.open

    def fake_open(path, flags, *a, **kw):
        if str(path).endswith("busy"):
            raise OSError(16, "Device or resource busy")
        return real_open(path, flags, *a, **kw)

    monkeypatch.setattr(accelerator.os, "open", fake_open)
    nodes = [str(free), str(tmp_path / "busy"), str(tmp_path / "missing")]
    assert accelerator.held_nodes(nodes) == [str(tmp_path / "busy")]


def test_the_chip_wait_runs_once_before_the_first_user_code():
    from types import SimpleNamespace

    from ray_tpu._private.core_worker import CoreWorker

    calls = []
    worker = SimpleNamespace(before_user_code=lambda: calls.append("wait"))
    CoreWorker._before_user_code(worker)
    CoreWorker._before_user_code(worker)
    assert calls == ["wait"] and worker.before_user_code is None


class _Tiny(TrainingOperator):
    def setup(self, config):
        import jax.numpy as jnp
        import optax

        self.register(
            model_init=lambda rng: {"w": jnp.zeros(4)},
            loss_fn=lambda p, b: jnp.mean((b[0] @ p["w"] - b[1]) ** 2),
            optimizer=optax.sgd(0.1))
        x = np.ones((8, 4), np.float32)
        self.register_data(train_loader=[(x, x.sum(1))],
                           validation_loader=[(x, x.sum(1))])
        with open(config["pid_file"], "w") as f:
            f.write(str(os.getpid()))


def test_a_new_trainer_follows_a_forced_shutdown_at_once(tmp_path):
    """`Trainer.shutdown(force=True)` has killed its workers and given
    their resources back when it returns: the next Trainer takes the
    same (declared) chips in the same session."""
    ray_tpu.init(num_cpus=2, num_tpus=4)
    try:
        kw = dict(num_workers=1, use_tpu=True,
                  config={"pid_file": str(tmp_path / "pid")},
                  resources_per_worker={"CPU": 1, "TPU": 4})
        first = Trainer(_Tiny, **kw)
        first.train()
        pid = int((tmp_path / "pid").read_text())
        assert _in_table(pid)
        first.shutdown(force=True)
        assert not _in_table(pid)
        assert ray_tpu.available_resources().get("TPU") == 4
        second = Trainer(_Tiny, **kw)
        assert second.train()["num_samples"] == 8
        second.shutdown(force=True)
    finally:
        ray_tpu.shutdown()
