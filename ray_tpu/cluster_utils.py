"""Multi-node-on-one-machine test cluster (reference:
python/ray/cluster_utils.py:10 class Cluster, add_node :60) — the
load-bearing test idiom: every "node" is a real raylet process with its own
object store, so distributed logic is exercised process-boundary-faithfully
on a single machine."""

from __future__ import annotations

from ray_tpu._private.config import Config, set_config
from ray_tpu._private.node import (
    Node,
    ServiceProcess,
    end_services,
    new_session_dir,
    start_gcs,
    start_gcs_shard,
    start_gcs_shards,
    start_raylet,
)


class ClusterNode:
    def __init__(self, svc: ServiceProcess, address: str, node_id, store_root):
        self.svc = svc
        self.address = address
        self.node_id = node_id
        self.store_root = store_root

    def kill(self):
        self.svc.kill()


class Cluster:
    def __init__(self, initialize_head: bool = True,
                 head_node_args: dict | None = None,
                 _system_config: dict | None = None):
        self.config = Config.load(_system_config)
        set_config(self.config)
        self.session_dir = new_session_dir()
        self.gcs_svc = None
        self.gcs_address = None
        self.shard_procs: list[ServiceProcess] = []
        self.shard_addresses: list[str] = []
        self.nodes: list[ClusterNode] = []
        if initialize_head:
            self.shard_procs, self.shard_addresses = start_gcs_shards(
                self.session_dir, self.config)
            self.gcs_svc, self.gcs_address = start_gcs(
                self.session_dir, self.config,
                shard_addresses=self.shard_addresses)
            self.add_node(is_head=True, **(head_node_args or {}))

    @property
    def address(self) -> str:
        return self.gcs_address

    @property
    def head_node(self) -> ClusterNode:
        return self.nodes[0]

    def add_node(self, *, num_cpus: float | None = None, num_tpus: float = 0,
                 resources: dict | None = None, labels: dict | None = None,
                 is_head: bool = False,
                 tpu_slice: dict | None = None,
                 topology: dict | None = None) -> ClusterNode:
        svc, address, node_id, store_root = start_raylet(
            self.session_dir, self.gcs_address, self.config,
            num_cpus=num_cpus, num_tpus=num_tpus, resources=resources,
            labels=labels, is_head=is_head, tpu_slice=tpu_slice,
            topology=topology)
        node = ClusterNode(svc, address, node_id, store_root)
        self.nodes.append(node)
        return node

    def remove_node(self, node: ClusterNode):
        node.kill()
        self.nodes.remove(node)

    def connect_driver(self):
        """Connect the current process as a driver to the head node."""
        from ray_tpu._private.core_worker import DRIVER, CoreWorker

        return CoreWorker(
            mode=DRIVER,
            raylet_address=self.head_node.address,
            gcs_address=self.gcs_address,
            session_dir=self.session_dir,
            store_root=self.head_node.store_root,
            config=self.config,
        )

    def kill_shard(self, index: int) -> ServiceProcess:
        """Fault injection: kill one store shard. restart_shard() brings
        it back on the same port against its journal."""
        svc = self.shard_procs[index]
        svc.kill()
        return svc

    def restart_shard(self, index: int) -> ServiceProcess:
        old = self.shard_procs[index]
        svc, _addr = start_gcs_shard(self.session_dir, self.config, index,
                                     port=old.shard_port)
        self.shard_procs[index] = svc
        return svc

    def shutdown(self):
        """Every node, the GCS and its shards, ended together: one wait
        (`node.end_services`), and on return none of them, nor anything
        they started, is left in the process table."""
        services = [node.svc for node in reversed(self.nodes)]
        if self.gcs_svc is not None:
            services.append(self.gcs_svc)
        services += self.shard_procs
        self.nodes.clear()
        self.gcs_svc = None
        self.shard_procs = []
        end_services(services)
