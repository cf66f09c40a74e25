"""Node bootstrap: spawn/stop the GCS and raylet service processes
(reference: python/ray/node.py:52 Node, start_head_processes :854,
start_ray_processes :875; python/ray/_private/services.py spawners)."""

from __future__ import annotations

import atexit
import json
import logging
import os
import shutil
import signal
import subprocess
import sys
import time
import uuid

from ray_tpu._private.accelerator import tpu_worker_jax_platforms
from ray_tpu._private.config import Config
from ray_tpu._private.ids import NodeID
from ray_tpu._private.object_store import default_store_root

logger = logging.getLogger("ray_tpu.node")


def new_session_dir() -> str:
    base = os.environ.get("RAY_TPU_TMPDIR", "/tmp/ray_tpu")
    session = f"session_{time.strftime('%Y%m%d_%H%M%S')}_{uuid.uuid4().hex[:8]}"
    path = os.path.join(base, session)
    os.makedirs(os.path.join(path, "logs"), exist_ok=True)
    return path


def _wait_ready(ready_file: str, proc: subprocess.Popen, what: str,
                timeout: float = 30.0) -> str:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if os.path.exists(ready_file):
            with open(ready_file) as f:
                return f.read().strip()
        if proc.poll() is not None:
            raise RuntimeError(
                f"{what} exited with code {proc.returncode} during startup")
        time.sleep(0.02)
    raise TimeoutError(f"{what} did not become ready in {timeout}s")


# How long the end of a session may take. What takes the time is a
# chip-owning worker: its device nodes close only when the kernel has
# unpinned its staging area and DMA mappings, and until then the dying
# process stays in the process table (PERF.md section 6, PR 46).
SESSION_END_BOUND_S = 60.0


class ProcessesStillAlive(RuntimeError):
    """Processes that were told to end outlived the bound."""


def _stat_fields(path: str) -> list[str] | None:
    """The fields of a /proc ``stat`` file from the state on (state,
    ppid, pgrp, ...); None when the task is gone."""
    try:
        with open(path) as f:
            stat = f.read()
    except OSError:
        return None
    # the command name is in brackets and may hold brackets and spaces
    return stat[stat.rindex(")") + 2:].split()


def has_left(pid: int) -> bool:
    """True when `pid` has left the process table or is a zombie
    awaiting its parent, and so holds no device node, mapping or socket.
    A zombie LEADER is not enough: it reads ``Z``, with an empty
    ``cmdline``, from the moment the process is told to die, while
    another of its threads is still in its exit, and the descriptors
    close with the last one (a worker that held four chips and 9 GB of
    staging: 19-24 s later). The leader's thread count says when that is
    over, on Linux and under gVisor alike; gVisor, which the chip
    machines run, lists no ``/proc/<pid>/task`` for a zombie at all."""
    return _left(_stat_fields(f"/proc/{pid}/stat"))


def _left(fields: list[str] | None) -> bool:
    return fields is None or (fields[0] in "ZX" and int(fields[17]) <= 1)


def group_members(pgids) -> list[int]:
    """The pids of the process groups `pgids` that have not left. A
    service is its group's leader (`_spawn`) and a raylet's workers stay
    in its group, so the group is everything the service started."""
    pgids = {str(g) for g in pgids}
    members = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        fields = _stat_fields(f"/proc/{pid}/stat")
        if not _left(fields) and fields[2] in pgids:
            members.append(int(pid))
    return members


def wait_until_left(alive, bound: float = SESSION_END_BOUND_S) -> float:
    """THE wait for processes that were told to end: returns, with the
    seconds it took, once `alive()` (the pids still in the process
    table) is empty; past `bound` it raises, naming them."""
    t0 = time.monotonic()
    pause = 0.002
    while left := alive():
        waited = time.monotonic() - t0
        if waited > bound:
            named = []
            for pid in left:
                fields = _stat_fields(f"/proc/{pid}/stat")
                named.append(f"{pid} (state {fields[0] if fields else '?'})")
            raise ProcessesStillAlive(
                f"{len(left)} process(es) still in the process table "
                f"{waited:.1f} s after they were told to end: "
                + ", ".join(named))
        time.sleep(pause)
        pause = min(pause * 2, 0.05)
    return time.monotonic() - t0


def end_process_groups(pgids, sig=signal.SIGKILL,
                       bound: float = SESSION_END_BOUND_S) -> float:
    """Signal every process group in `pgids` and wait until all their
    members have left (`wait_until_left`)."""
    pgids = list(pgids)
    for pgid in pgids:
        try:
            os.killpg(pgid, sig)
        except (ProcessLookupError, PermissionError):
            pass
    return wait_until_left(lambda: group_members(pgids), bound)


class ServiceProcess:
    def __init__(self, name: str, proc: subprocess.Popen):
        self.name = name
        self.proc = proc

    def alive(self) -> bool:
        # asked of the process table, not of `proc.poll()`: a service
        # that died stays an unreaped zombie until `end_services`, so
        # its pid, which names its group, cannot be given to another
        return not has_left(self.proc.pid)

    def kill(self, sig=signal.SIGKILL):
        end_services([self], sig)


def end_services(services, sig=signal.SIGKILL) -> float:
    """End services and everything they started; return, with the
    seconds it took, when all of it has left the process table. A group
    outlives a leader that exited by itself (a drained or fail-stopped
    raylet's workers), so it is signalled whether or not the service is
    alive; a service that was reaped here before is skipped."""
    services = [svc for svc in services if svc.proc.returncode is None]
    took = end_process_groups([svc.proc.pid for svc in services], sig)
    for svc in services:
        svc.proc.wait()  # a zombie by now: reaped at once
    return took


def _spawn(cmd: list[str], config: Config, name: str) -> ServiceProcess:
    env = dict(os.environ)
    env.update(config.child_env())
    # control-plane processes never touch an accelerator: the chip
    # belongs to the TPU-flavour worker (see _private/accelerator.py)
    env["JAX_PLATFORMS"] = "cpu"
    # `python -m ray_tpu...` children must import the package regardless
    # of the caller's cwd (the CLI runs from anywhere; without this,
    # `ray-tpu start` only worked inside the repo checkout)
    pkg_root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    existing = env.get("PYTHONPATH")
    if pkg_root not in (existing or "").split(os.pathsep):
        env["PYTHONPATH"] = (pkg_root + os.pathsep + existing
                             if existing else pkg_root)
    proc = subprocess.Popen(
        cmd, env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        start_new_session=True)
    return ServiceProcess(name, proc)


def start_gcs(session_dir: str, config: Config, port: int = 0,
              shard_addresses: list[str] | None = None) -> tuple[ServiceProcess, str]:
    ready = os.path.join(session_dir, f"gcs_ready_{uuid.uuid4().hex[:6]}")
    log_file = os.path.join(session_dir, "logs", "gcs_server.log")
    cmd = [
        sys.executable, "-m", "ray_tpu.gcs.server",
        "--port", str(port),
        "--ready-file", ready,
        "--log-file", log_file,
    ]
    if config.gcs_persistence:
        cmd += ["--store-dir", os.path.join(session_dir, "gcs_store")]
    if shard_addresses:
        cmd += ["--shard-addresses", ",".join(shard_addresses)]
    cmd += ["--uds-dir", os.path.join(session_dir, "sock")]
    svc = _spawn(cmd, config, "gcs_server")
    actual_port = _wait_ready(ready, svc.proc, "gcs_server")
    return svc, f"{config.node_ip_address}:{actual_port}"


def start_gcs_shard(session_dir: str, config: Config, index: int,
                    port: int = 0) -> tuple[ServiceProcess, str]:
    """Spawn one GCS store shard (gcs/shard.py). A restart reuses the
    same port + journal dir, so client-side key routing never remaps."""
    ready = os.path.join(session_dir,
                         f"gcs_shard_ready_{index}_{uuid.uuid4().hex[:6]}")
    log_file = os.path.join(session_dir, "logs", f"gcs_shard_{index}.log")
    cmd = [
        sys.executable, "-m", "ray_tpu.gcs.shard",
        "--index", str(index),
        "--port", str(port),
        "--ready-file", ready,
        "--log-file", log_file,
    ]
    if config.gcs_persistence:
        cmd += ["--store-dir",
                os.path.join(session_dir, f"gcs_shard_{index}")]
    cmd += ["--uds-dir", os.path.join(session_dir, "sock")]
    svc = _spawn(cmd, config, f"gcs_shard_{index}")
    actual_port = _wait_ready(ready, svc.proc, f"gcs_shard_{index}")
    svc.shard_index = index
    svc.shard_port = int(actual_port)
    return svc, f"{config.node_ip_address}:{actual_port}"


def start_gcs_shards(session_dir: str,
                     config: Config) -> tuple[list[ServiceProcess], list[str]]:
    """Spawn the store-shard tier (config.gcs_shards processes; none at
    the default of 1 — single-GCS layout preserved)."""
    if config.gcs_shards <= 1:
        return [], []
    procs, addrs = [], []
    for i in range(config.gcs_shards):
        svc, addr = start_gcs_shard(session_dir, config, i)
        procs.append(svc)
        addrs.append(addr)
    return procs, addrs


def restart_gcs(session_dir: str, config: Config, gcs_address: str,
                shard_addresses: list[str] | None = None) -> ServiceProcess:
    """Bring a (crashed) GCS back on its old port against its persisted
    store, so clients' redial loops land on a server that remembers them
    (reference: test_gcs_fault_tolerance.py restart path)."""
    port = int(gcs_address.rsplit(":", 1)[1])
    svc, _addr = start_gcs(session_dir, config, port,
                           shard_addresses=shard_addresses)
    return svc


def start_raylet(session_dir: str, gcs_address: str, config: Config, *,
                 node_id: NodeID | None = None, num_cpus: float | None = None,
                 num_tpus: float = 0, resources: dict | None = None,
                 labels: dict | None = None, is_head=False,
                 store_root: str | None = None,
                 tpu_slice: dict | None = None,
                 topology: dict | None = None) -> tuple[ServiceProcess, str, NodeID, str]:
    node_id = node_id or NodeID.from_random()
    ready = os.path.join(session_dir, f"raylet_ready_{node_id.hex()[:8]}")
    log_file = os.path.join(session_dir, "logs",
                            f"raylet-{node_id.hex()[:8]}.log")
    if store_root is None:
        store_root = os.path.join(default_store_root(session_dir),
                                  node_id.hex()[:8])
    cmd = [
        sys.executable, "-m", "ray_tpu.raylet.raylet",
        "--gcs-address", gcs_address,
        "--session-dir", session_dir,
        "--store-root", store_root,
        "--node-id", node_id.hex(),
        "--resources", json.dumps(resources or {}),
        "--labels", json.dumps(labels or {}),
        "--ready-file", ready,
        "--log-file", log_file,
        # the raylet itself runs with JAX_PLATFORMS=cpu (_spawn), so the
        # value its TPU-flavour workers get travels as an argument
        "--tpu-worker-platforms", tpu_worker_jax_platforms(),
    ]
    if num_cpus is not None:
        cmd += ["--num-cpus", str(num_cpus)]
    if num_tpus:
        cmd += ["--num-tpus", str(num_tpus)]
    if tpu_slice:
        if hasattr(tpu_slice, "to_dict"):  # TpuSliceDescriptor
            tpu_slice = tpu_slice.to_dict()
        cmd += ["--tpu-slice", json.dumps(tpu_slice)]
    if topology:
        if hasattr(topology, "to_dict"):  # topology.TopologyCoord
            topology = topology.to_dict()
        cmd += ["--topology", json.dumps(topology)]
    if is_head:
        cmd += ["--is-head"]
    svc = _spawn(cmd, config, f"raylet-{node_id.hex()[:8]}")
    address = _wait_ready(ready, svc.proc, "raylet")
    return svc, address, node_id, store_root


class Node:
    """A local cluster head (GCS + one raylet) or an added worker node."""

    def __init__(self, *, config: Config, session_dir: str | None = None,
                 gcs_address: str | None = None, num_cpus=None, num_tpus=0,
                 resources=None, labels=None, tpu_slice=None):
        self.config = config
        self.session_dir = session_dir or new_session_dir()
        self.processes: list[ServiceProcess] = []
        self.is_head = gcs_address is None
        self.shard_addresses: list[str] = []
        if gcs_address is None:
            # Store-shard tier first (the director advertises their
            # addresses via get_shard_map); none at gcs_shards=1.
            shard_procs, self.shard_addresses = start_gcs_shards(
                self.session_dir, config)
            self.processes.extend(shard_procs)
            gcs_proc, gcs_address = start_gcs(
                self.session_dir, config, config.gcs_port,
                shard_addresses=self.shard_addresses)
            self.processes.append(gcs_proc)
        self.gcs_address = gcs_address
        raylet_proc, raylet_addr, node_id, store_root = start_raylet(
            self.session_dir, gcs_address, config,
            num_cpus=num_cpus, num_tpus=num_tpus, resources=resources,
            labels=labels, is_head=self.is_head, tpu_slice=tpu_slice)
        self.processes.append(raylet_proc)
        self.raylet_address = raylet_addr
        self.node_id = node_id
        self.store_root = store_root
        self._stopping = False
        atexit.register(self.kill_all_processes)
        if self.is_head and config.gcs_persistence and config.gcs_auto_restart:
            self._start_gcs_monitor()

    def _start_gcs_monitor(self):
        """Supervise the GCS: a crashed GCS is restarted on its old port
        against its persisted tables (the process-level analog of the
        reference's externally-supervised gcs_server + Redis durability;
        behavior: python/ray/tests/test_gcs_fault_tolerance.py)."""
        import threading

        def _watch():
            while not self._stopping:
                time.sleep(0.5)
                self._respawn_dead_shards()
                gcs = next((s for s in self.processes
                            if s.name == "gcs_server"), None)
                if gcs is None or self._stopping:
                    continue
                if not gcs.alive():
                    if self._stopping:
                        continue
                    logger.warning("GCS exited (rc=%s); restarting on %s",
                                   gcs.proc.poll(), self.gcs_address)
                    try:
                        new = restart_gcs(self.session_dir, self.config,
                                          self.gcs_address,
                                          shard_addresses=self.shard_addresses)
                    except Exception:
                        logger.exception("GCS restart failed")
                        continue
                    # Shutdown may have started while we were spawning
                    # (kill_all sets _stopping before killing): don't leak
                    # an orphan GCS outliving the driver.
                    if self._stopping:
                        new.kill()
                        continue
                    try:
                        self.processes[self.processes.index(gcs)] = new
                    except ValueError:
                        if self._stopping:
                            new.kill()
                        else:
                            self.processes.append(new)

        threading.Thread(target=_watch, name="gcs-monitor",
                         daemon=True).start()

    def _respawn_dead_shards(self):
        """Restart crashed store shards on their FIXED ports against
        their journals (journal replay restores the partition's tables;
        clients' per-shard ReconnectingConnections redial the same
        address, so key routing never remaps)."""
        for i, svc in enumerate(list(self.processes)):
            if (self._stopping or not svc.name.startswith("gcs_shard_")
                    or svc.alive()):
                continue
            index = getattr(svc, "shard_index", None)
            port = getattr(svc, "shard_port", 0)
            if index is None:
                continue
            logger.warning("GCS shard %d exited (rc=%s); restarting on "
                           "port %d", index, svc.proc.poll(), port)
            try:
                new, _addr = start_gcs_shard(self.session_dir, self.config,
                                             index, port=port)
            except Exception:
                logger.exception("GCS shard %d restart failed", index)
                continue
            if self._stopping:
                new.kill()
                continue
            try:
                self.processes[self.processes.index(svc)] = new
            except ValueError:
                if self._stopping:
                    new.kill()
                else:
                    self.processes.append(new)

    def kill_all_processes(self):
        """The one place a session ends. On return no process it started
        (service or worker, registered or still starting) is left in the
        process table other than as a zombie: its chips, arena mappings
        and sockets are free."""
        self._stopping = True
        services, self.processes = self.processes, []
        try:
            end_services(reversed(services))
        finally:
            if self.is_head:
                # the session's shared memory goes with its services:
                # the object store's arena file (2 GiB by default, as
                # resident as the run made it) and the collective
                # segments beside it are nobody's once they are dead
                shutil.rmtree(os.path.dirname(default_store_root(
                    self.session_dir)), ignore_errors=True)

    def kill_gcs(self):
        """Fault injection: kill the GCS process (it will be auto-restarted
        by the monitor when gcs_auto_restart is on)."""
        for svc in self.processes:
            if svc.name == "gcs_server":
                svc.kill()

    def kill_gcs_shard(self, index: int = 0):
        """Fault injection: kill one store shard (auto-restarted by the
        monitor when gcs_auto_restart is on)."""
        for svc in self.processes:
            if getattr(svc, "shard_index", None) == index:
                svc.kill()

    def kill_raylet(self):
        """Fault injection: kill this node's raylet (reference test idiom:
        Node._kill_process_type, node.py:894)."""
        for svc in self.processes:
            if svc.name.startswith("raylet"):
                svc.kill()
