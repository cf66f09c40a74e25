"""Declarative collective groups across actors/tasks (API parity with the
reference: python/ray/util/collective/collective.py — GroupManager :29,
init_collective_group :93, create_collective_group :126, allreduce :226,
barrier :266, reduce :279, broadcast :340, allgather :391, reducescatter,
send :496, recv :550)."""

from __future__ import annotations

import threading
from typing import Any

import numpy as np

from ray_tpu.collective.types import (Backend, ReduceOp, Transport,
                                      is_jax_array, normalize_quantize)


class GroupManager:
    """Per-process registry of collective groups (reference:
    collective.py:29)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._groups: dict[str, Any] = {}

    def create_group(self, group_name: str, world_size: int, rank: int,
                     backend: Backend, timeout: float = 60.0,
                     transport: str = "auto", quantize=None,
                     placement_plan: dict | None = None):
        backend = Backend(backend)
        quantize = normalize_quantize(quantize)
        if backend == Backend.AUTO:
            backend = Backend.XLA if world_size == 1 else Backend.HOST
        with self._lock:
            if group_name in self._groups:
                raise RuntimeError(f"group {group_name!r} already exists")
        if backend == Backend.HOST:
            from ray_tpu.collective.backends.host_backend import HostGroup

            group = HostGroup(group_name, world_size, rank, timeout=timeout,
                              transport=Transport(transport).value,
                              quantize=quantize,
                              placement_plan=placement_plan)
        else:
            from ray_tpu.parallel import multihost

            def _spans_processes() -> bool:
                if world_size <= 1 or not multihost.is_initialized():
                    return False
                import jax

                # only a one-rank-per-process group rides the global
                # mesh; other sizes are single-controller device groups
                return world_size == jax.process_count()

            # both device-group flavors live in xla_backend.py (the
            # former xla_global.GlobalMeshGroup is unified there)
            from ray_tpu.collective.backends.xla_backend import (
                ProcessMeshGroup, XlaGroup)

            if _spans_processes():
                # N actor processes joined one jax.distributed runtime:
                # group ops ride XLA collectives over the global mesh
                # (the NCCL-across-actors capability)
                group = ProcessMeshGroup(group_name, world_size, rank,
                                         quantize=quantize)
            else:
                group = XlaGroup(group_name, quantize=quantize)
        with self._lock:
            self._groups[group_name] = group
        return group

    def get_group(self, group_name: str):
        with self._lock:
            group = self._groups.get(group_name)
        if group is None:
            raise ValueError(
                f"collective group {group_name!r} is not initialized in "
                f"this process; call init_collective_group first")
        return group

    def destroy_group(self, group_name: str):
        with self._lock:
            group = self._groups.pop(group_name, None)
        if group is not None:
            group.destroy()

    def debug_state(self) -> list[dict]:
        """Live rows for every group in this process (debug_state.py /
        `ray-tpu state collectives`): backends exposing their own
        debug_state (HostGroup: current op + phase + age) use it; the
        rest report membership only."""
        with self._lock:
            groups = list(self._groups.items())
        out = []
        for name, group in groups:
            fn = getattr(group, "debug_state", None)
            if callable(fn):
                try:
                    out.append(fn())
                    continue
                except Exception:
                    pass
            out.append({"group": name,
                        "rank": int(getattr(group, "rank", 0)),
                        "world_size": int(getattr(group, "world_size", 1)),
                        "backend": type(group).__name__,
                        "op": "", "phase": "idle", "age_s": 0.0})
        return out


_manager = GroupManager()


def init_collective_group(world_size: int, rank: int,
                          backend: str = "host",
                          group_name: str = "default",
                          timeout: float = 60.0,
                          transport: str = "auto",
                          quantize=None,
                          placement_plan: dict | None = None):
    """Initialize this process's membership in a collective group
    (reference: collective.py:93). Call from inside each participating
    actor/task with its rank. `transport` pins the HOST data plane to
    one tier (hub/ring/shm/device); "auto" routes per
    op. `quantize="int8"` makes this group's default allreduce wire
    format block-scaled int8 (EQuARX-style, lossy) on the tiers that
    have a wire (ring/device); per-op `allreduce(..., quantize=...)`
    overrides it. `placement_plan` (topology.transport_plan output)
    pins the tier FROM the gang's placement record instead of the
    probe round — see create_collective_group(placement_group=...)."""
    return _manager.create_group(group_name, world_size, rank,
                                 Backend(backend), timeout=timeout,
                                 transport=transport, quantize=quantize,
                                 placement_plan=placement_plan)


def placement_transport_plan(pg) -> dict | None:
    """Resolve a PlacementGroup (or its id bytes) to the topology
    transport plan its record carries, or None for ad-hoc/fallback
    groups (which keep the probe round)."""
    from ray_tpu._private import global_state
    from ray_tpu._private import topology as _topo

    cw = global_state.get_core_worker()
    if pg is None or cw is None:
        return None
    pg_id = pg if isinstance(pg, bytes) else pg.id.binary()
    try:
        record = cw.get_placement_group(pg_id)
    except Exception:
        return None
    return _topo.transport_plan(record)


def create_collective_group(actors, world_size: int, ranks: list[int],
                            backend: str = "host",
                            group_name: str = "default",
                            timeout: float = 60.0,
                            quantize=None,
                            transport: str = "auto",
                            placement_group=None):
    """Driver-side declarative setup (reference: collective.py:126): tells
    every actor in `actors` to init the group with its rank.

    `placement_group`: the gang's reservation. When its record carries
    an ICI_RING topology plan and `transport` is "auto", every rank's
    tier is DERIVED from the placement (shm when the ring landed on one
    host, device/ring/hub otherwise) and the per-op probe rounds are
    skipped — counted by `collective.transport_derived_total`. Records
    without a plan (PACK fallback, ad-hoc groups) keep probing."""
    import ray_tpu

    if len(actors) != len(ranks) or len(actors) != world_size:
        raise ValueError("actors/ranks/world_size mismatch")
    plan = None
    if placement_group is not None and transport == "auto":
        plan = placement_transport_plan(placement_group)
    refs = [
        actor.__ray_collective_init__.remote(world_size, rank, backend,
                                             group_name, timeout, quantize,
                                             transport, plan)
        for actor, rank in zip(actors, ranks)
    ]
    return ray_tpu.get(refs, timeout=120)


def declare_collective_group(actors, world_size: int, ranks: list[int],
                             backend: str = "host",
                             group_name: str = "default"):
    return create_collective_group(actors, world_size, ranks, backend,
                                   group_name)


def is_group_initialized(group_name: str = "default") -> bool:
    try:
        _manager.get_group(group_name)
        return True
    except ValueError:
        return False


def destroy_collective_group(group_name: str = "default"):
    _manager.destroy_group(group_name)


def get_rank(group_name: str = "default") -> int:
    group = _manager.get_group(group_name)
    return getattr(group, "rank", 0)


def get_collective_group_size(group_name: str = "default") -> int:
    return _manager.get_group(group_name).world_size


def _as_numpy(tensor) -> np.ndarray:
    if isinstance(tensor, np.ndarray):
        return tensor
    return np.asarray(tensor)


def _prep(tensor):
    """Normalize an op payload WITHOUT forcing device arrays to host:
    jax.Arrays pass through untouched (the DEVICE tier and the XLA
    backend consume them in place — pulling them to numpy here would
    defeat the whole ICI plane), everything else becomes numpy."""
    if isinstance(tensor, np.ndarray) or is_jax_array(tensor):
        return tensor
    return np.asarray(tensor)


def _traced_op(name: str, group_name: str, fn, nbytes: int | None = None):
    """Collective trace entry point (tracing.py): continues an ambient
    trace (op inside a traced task/replica call) or head-samples a fresh
    root, recording one `collective.<op>` span over the op. The
    `collective.op_s` histogram observes EVERY call (sampled or not),
    with the sampled caller's trace id as its exemplar."""
    import time as _time

    from ray_tpu._private import tracing
    from ray_tpu.collective import metrics as _metrics

    ctx = tracing.maybe_trace()
    t0 = _time.time()
    if ctx is None:
        try:
            return fn()
        finally:
            _metrics.OP_S.observe(_time.time() - t0)
    extra = {"group": group_name}
    if nbytes is not None:
        extra["bytes"] = nbytes
    try:
        with tracing.span(name, ctx, extra, ambient=True):
            return fn()
    finally:
        _metrics.OP_S.observe(_time.time() - t0,
                              exemplar=tracing.exemplar_of(ctx))


def allreduce(tensor, group_name: str = "default",
              op: ReduceOp = ReduceOp.SUM, quantize=None):
    """`quantize` (None = the group's default; "int8" = block-scaled
    int8 wire format; False = force exact) applies on the tiers that
    have a wire to compress — the DEVICE ppermute ring and the host
    TCP ring. hub/shm always carry exact payloads."""
    group = _manager.get_group(group_name)
    t = _prep(tensor)
    return _traced_op("collective.allreduce", group_name,
                      lambda: group.allreduce(t, op, quantize=quantize),
                      t.nbytes)


def reduce(tensor, dst_rank: int = 0, group_name: str = "default",
           op: ReduceOp = ReduceOp.SUM):
    group = _manager.get_group(group_name)
    t = _prep(tensor)
    return _traced_op("collective.reduce", group_name,
                      lambda: group.reduce(t, dst_rank, op), t.nbytes)


def broadcast(tensor, src_rank: int = 0, group_name: str = "default"):
    group = _manager.get_group(group_name)
    t = _prep(tensor)
    return _traced_op("collective.broadcast", group_name,
                      lambda: group.broadcast(t, src_rank), t.nbytes)


def allgather(tensor, group_name: str = "default"):
    group = _manager.get_group(group_name)
    t = _prep(tensor)
    return _traced_op("collective.allgather", group_name,
                      lambda: group.allgather(t), t.nbytes)


def reducescatter(tensor, group_name: str = "default",
                  op: ReduceOp = ReduceOp.SUM, quantize=None):
    """quantize: per-op wire codec override ("int8" / None), same
    semantics as the group-construction default — the sharded trainer's
    grad bucket rides this knob."""
    group = _manager.get_group(group_name)
    t = _prep(tensor)
    return _traced_op("collective.reducescatter", group_name,
                      lambda: group.reducescatter(t, op, quantize=quantize),
                      t.nbytes)


def barrier(group_name: str = "default"):
    group = _manager.get_group(group_name)
    _traced_op("collective.barrier", group_name, group.barrier)


def send(tensor, dst_rank: int, group_name: str = "default", tag: int = 0):
    _manager.get_group(group_name).send(_as_numpy(tensor), dst_rank, tag)


def recv(src_rank: int, group_name: str = "default", tag: int = 0):
    return _manager.get_group(group_name).recv(src_rank, tag)


class CollectiveActorMixin:
    """Mixin giving an actor class the __ray_collective_init__ hook used by
    create_collective_group."""

    def __ray_collective_init__(self, world_size, rank, backend, group_name,
                                timeout=60.0, quantize=None,
                                transport="auto", placement_plan=None):
        init_collective_group(world_size, rank, backend, group_name,
                              timeout=timeout, quantize=quantize,
                              transport=transport,
                              placement_plan=placement_plan)
        return rank
