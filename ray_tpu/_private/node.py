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


class ServiceProcess:
    def __init__(self, name: str, proc: subprocess.Popen):
        self.name = name
        self.proc = proc

    def alive(self) -> bool:
        return self.proc.poll() is None

    def kill(self, sig=signal.SIGKILL):
        if self.alive():
            try:
                os.killpg(os.getpgid(self.proc.pid), sig)
            except (ProcessLookupError, PermissionError):
                try:
                    self.proc.kill()
                except ProcessLookupError:
                    pass
        try:
            self.proc.wait(timeout=5)
        except subprocess.TimeoutExpired:
            pass


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
                                   gcs.proc.returncode, self.gcs_address)
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
                           "port %d", index, svc.proc.returncode, port)
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
        self._stopping = True
        for svc in reversed(self.processes):
            svc.kill()
        self.processes.clear()
        if self.is_head:
            # the session's shared memory goes with its services: the
            # object store's arena file (2 GiB by default, as resident
            # as the run made it) and the collective segments beside it
            # are nobody's once the raylet is dead. A process that still
            # maps the arena keeps its mapping until it exits.
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
