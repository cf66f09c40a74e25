"""Trainer — distributed data-parallel training over worker actors
(reference: python/ray/util/sgd/torch/torch_trainer.py:39 TorchTrainer —
train :365, fault-tolerant _resize_worker_group :328, save/load :543/:552;
worker group: worker_group.py:107 RemoteWorkerGroup, _setup_process_group
:153).

TPU-first differences: each worker is one actor per host running a jax
runtime; gradient allreduce goes through ray_tpu.collective (HOST TCP
backend across processes; within a host the jitted step shards over the
local device mesh, so ICI collectives come from XLA, not this layer)."""

from __future__ import annotations

import collections
import os
import pickle
import resource
import threading
import time
import traceback
import uuid
from typing import NamedTuple

import cloudpickle

import ray_tpu
from ray_tpu import exceptions as exc
from ray_tpu._private import failpoints as _fp
from ray_tpu._private import global_state
from ray_tpu._private import tracing
from ray_tpu.collective.collective import CollectiveActorMixin

# Sharded checkpoint manifest marker (Trainer.save/load): `path` holds a
# small index dict with this format tag; params + per-rank optimizer
# shards live in sibling files it names.
_SHARDED_CKPT_FORMAT = "ray_tpu.sharded_ckpt"

# The span trees of this process's last Trainer.train() calls, oldest
# first (call_log()). Raw rows; turned into dicts when read.
CALL_LOG_MAX = 256
_call_log: collections.deque = collections.deque(maxlen=CALL_LOG_MAX)


# ... and of its last worker-group starts (start_log()): a log of its
# own, because readers find a call in `_call_log` by its position.
START_LOG_MAX = 32
_start_log: collections.deque = collections.deque(maxlen=START_LOG_MAX)


def call_log() -> list[dict]:
    """The finished span trees of this process's last `Trainer.train()`
    calls (at most 256, oldest first). One entry a call: `trace_id` and
    `spans`, each span a dict of `name`, `start`, `end` (seconds on
    `time.time()`, comparable within one host), `span`, `parent` (ids;
    the root's parent is None) and `attrs` (its counts). Root:
    `train.call`; the worker's spans arrive in the task replies, so an
    entry is whole when `train()` returns (ARCHITECTURE.md, "Span
    catalogue")."""
    return _entries(_call_log)


def start_log() -> list[dict]:
    """The finished span trees of this process's last worker-group
    starts (at most 32, oldest first; entries as `call_log()`'s): one a
    `Trainer(...)` and one more a restart of its group. Root:
    `train.start`; what a new worker did before it had a trace context
    (`worker.spawn`, `worker.boot`, `worker.chip_wait`,
    `worker.actor_init`) and its `train.setup` arrive in the reply of
    the first call it runs."""
    return _entries(_start_log)


def _entries(log) -> list[dict]:
    out = []
    for trace_id, rows in list(log):
        spans = []
        for name, start, end, fields in list(rows):
            attrs = {k: v for k, v in fields.items()
                     if k not in ("tid", "sid", "psid")}
            spans.append({"name": name, "start": start, "end": end,
                          "span": fields["sid"],
                          "parent": fields.get("psid"), "attrs": attrs})
        out.append({"trace_id": trace_id, "spans": spans})
    return out


class _Pending(NamedTuple):
    """A `train()` call whose state the worker holds and nobody has
    pulled yet: the call's number, the worker's epoch after it (which
    copy `state_piece(of_epoch=)` reads), the steps it ran (what the
    elastic restore runs again if the copy dies with its worker) and,
    where the worker holds a PART of the state, the pull that has
    brought the rest home already (a `_Pull`; None: the whole is held,
    and nothing has crossed)."""

    call: int
    epoch: int
    num_steps: int | None
    pull: "_Pull | None" = None


class _Pull:
    """One state on its way from a worker into the buffer set written
    longest ago, in one part or in two (`Trainer._pull_state`): the
    plan piece 0 brought, the leaves that have landed, and how much of
    the set's reservation was taken before the first did."""

    def __init__(self, spare: "_BufferSet"):
        self.spare, self.reserve = spare, spare.reserve
        self.taken = self.reserve.taken if self.reserve is not None else 0
        self.treedef, self.ranges, self.sizes = None, (), ()
        self.spares, self.leaves = [], []

    def plan(self, piece: dict) -> None:
        """Piece 0's reply: the state's tree and its cut."""
        import jax

        self.treedef, self.ranges = piece["treedef"], piece["ranges"]
        self.sizes = piece["bytes"]
        self.spares, spare_def = jax.tree.flatten(self.spare.state)
        if spare_def != self.treedef:
            self.spares = []    # a changed tree: every leaf is new

    @property
    def pieces(self) -> int:
        """Pieces that have landed, in order from 0."""
        return sum(1 for _, stop in self.ranges if stop <= len(self.leaves))

    @property
    def whole(self) -> bool:
        return self.treedef is not None and self.pieces == len(self.ranges)

    def stop(self, leaf: int | None) -> int:
        """The piece that begins at `leaf` (None, or no piece does: one
        past the last)."""
        return next((i for i, (first, _) in enumerate(self.ranges)
                     if first == leaf), len(self.ranges))

    def state(self) -> dict:
        import jax

        return jax.tree.unflatten(self.treedef, self.leaves)

    def drop(self) -> None:
        """Nothing of this pull is installed: what it took of the
        reservation is nobody's."""
        if self.reserve is not None:
            self.reserve.taken = self.taken


# A buffer set's bytes are made resident a chunk at a time, each chunk
# written this often by one thread before a copy may land in it: what a
# page needs on the chip machines before it is written at the steady
# rate (`_Reserve`).
RESERVE_CHUNK = 32 << 20
RESERVE_WRITES = 2
_PAGE = 4096
_LEAF_AT = 16       # a reserved leaf's offset within its page (`take`)


def _dense_strides(x) -> tuple:
    """The strides `np.array(x)` gives its copy: `x`'s own where `x` is
    dense in some order of its axes (a leaf the device keeps transposed
    stays so), else those of a dense array whose axes follow the order
    of `x`'s strides."""
    order = sorted(range(x.ndim), key=lambda i: (-abs(x.strides[i]), i))
    strides, step = [0] * x.ndim, x.itemsize
    for axis in reversed(order):
        strides[axis] = step
        step *= x.shape[axis]
    dense = all(s == t for s, t, n in zip(strides, x.strides, x.shape)
                if n > 1)
    return x.strides if dense else tuple(strides)


class _BufferSet(NamedTuple):
    """One of the two sets of host buffers a Trainer's snapshots land in
    by turns: the trees `_own` built last in it (None: none yet), how
    many snapshots have been copied into it, and the bytes reserved for
    it (None where the workers could not say how large a state is)."""

    state: dict | None = None
    shards: list | None = None
    writes: int = 0
    reserve: "_Reserve | None" = None


class _Reserve:
    """The bytes of ONE of the Trainer's two buffer sets, reserved when
    the workers have said how large a state is and before one lands in
    them: `_own` takes a leaf's destination from here when the leaf
    arrives (`take`), and the Trainer's own threads (`_Reserver`) make
    the bytes resident meanwhile, front to back.

    Why: on the chip machines (gVisor) a page costs on its first TWO
    writes, however it was allocated — `np.empty`, numpy's huge-page
    advice on or off, `np.zeros`, a plain anonymous mapping: 1.0 GB/s,
    then 2.3 GB/s, then 19 GB/s from the third write on (ISSUE 54's
    probe, `PERF.md` §5) — and one thread pays that in full, while four
    threads writing a GiB between them make it resident in 0.31 s and
    leave the next write at 16 GB/s, a second round (0.03 s) at 19.
    A snapshot copied by the driver's one thread into arrays of its
    own making paid both prices inside `train()` calls 1 to 4."""

    def __init__(self, nbytes: int):
        import numpy as np

        self.bytes = np.empty(nbytes, np.uint8)
        # bytes handed out as destinations: a mark `Trainer._snapshot`
        # puts back when a pull installs nothing
        self.taken = 0
        # bytes from the front that no thread has still to write: all
        # of them until a `_Reserver` begins on the reservation, whole
        # chunks while its threads run, all again once they have ended
        self.ready = self.bytes.nbytes
        self._chunks_done: set = set()
        self._changed = threading.Condition()

    def take(self, x):
        """A destination for the leaf `x` in bytes nobody has taken —
        its shape and dtype, the layout `np.array(x)` would give it —
        or None where `x` does not fit what is left. It starts 16 bytes
        into a page, where `np.array` puts a large leaf (glibc's header
        in front of a mapping of its own): on these hosts a copy whose
        destination lies 16 to some 600 bytes past its source, pages
        apart, runs at 4–5 GB/s where every other runs at 19 (loads
        that wait for the stores just before them: `PERF.md` §5), the
        arena hands leaves out 300 to 2800 bytes into their pages, and
        a destination at a page's head is never just past one of
        those — placed by the source's own offset, 2–10 % of a steady
        copy's bytes drew that lot."""
        import numpy as np

        at = self.bytes.ctypes.data + self.taken
        first = self.taken + (_LEAF_AT - at) % _PAGE
        if not x.nbytes or first + x.nbytes > self.bytes.nbytes:
            return None
        self.taken = first + x.nbytes
        return np.ndarray(x.shape, x.dtype, buffer=self.bytes, offset=first,
                          strides=_dense_strides(x))

    def seal(self) -> None:
        """The set's first snapshot is installed: what it left is never
        handed out (a later tree's leaves are allocated, as ever)."""
        self.taken = self.bytes.nbytes

    def holds(self, leaf) -> bool:
        """Whether `leaf` is an array `take` made."""
        return leaf.base is self.bytes

    def wait_for(self, leaf=None) -> float:
        """Return once `leaf`'s bytes (None: all of them) are resident
        and no thread writes them any more; the seconds that took (0.0:
        they were ahead)."""
        stop = self.bytes.nbytes if leaf is None else (
            leaf.ctypes.data - self.bytes.ctypes.data + leaf.nbytes)
        if self.ready >= stop:
            return 0.0
        start = time.perf_counter()
        with self._changed:
            self._changed.wait_for(lambda: self.ready >= stop)
        return time.perf_counter() - start

    def chunks(self) -> int:
        return -(-self.bytes.nbytes // RESERVE_CHUNK)

    def write(self, chunk: int) -> bool:
        """Make one chunk resident (a reserver thread; leaves the GIL);
        True when that was the reservation's last."""
        part = self.bytes[chunk * RESERVE_CHUNK:(chunk + 1) * RESERVE_CHUNK]
        for _ in range(RESERVE_WRITES):
            part[:] = 0
        with self._changed:
            self._chunks_done.add(chunk)
            while self.ready < self.bytes.nbytes and (
                    self.ready // RESERVE_CHUNK in self._chunks_done):
                self.ready = min(self.ready + RESERVE_CHUNK,
                                 self.bytes.nbytes)
            self._changed.notify_all()
            return len(self._chunks_done) == self.chunks()

    def release(self) -> None:
        """No thread writes here any more: everything may be copied
        into, resident or not."""
        with self._changed:
            self.ready = self.bytes.nbytes
            self._changed.notify_all()


class _Reserver:
    """The threads that make a Trainer's reservations resident, beside
    whatever the caller's thread does: the first set's from the moment
    the workers have started (`begin(0)`: the first pull needs it, and
    until then the driver mostly waits for the first step to be traced
    and loaded), the second's from the Trainer's second `train()` call
    (`begin(1)`: its pull is the first to need it, and pages made
    resident any earlier would be memory the parent of this code never
    held between the two calls; that call waits for the set to be
    whole before it pulls, or before it returns where it pulls
    nothing). Each reservation is one span
    `train.snapshot.reserve` (`set`, `bytes`, `writes` a page,
    `threads`, `minor_faults` of the process meanwhile), a child of the
    `train.start` that reserved it and kept in that tree's `rows`
    though it ends after the tree has closed."""

    def __init__(self, reserves: list, ctx, rows: list):
        self._reserves = reserves
        self._ctx, self._rows = ctx, rows
        self._work: collections.deque = collections.deque()
        self._began: dict = {}
        self._lock = threading.Lock()
        self._stopped = False
        self._threads: list = []

    def begin(self, k: int) -> None:
        """Start making set `k` resident (once; a no-op afterwards)."""
        with self._lock:
            if self._stopped or k in self._began:
                return
            chunks = self._reserves[k].chunks()
            count = min(_reserve_threads(), chunks)
            self._began[k] = (time.time(), _minor_faults(), count)
            self._reserves[k].ready = 0     # a copy waits from here on
            self._work.extend((k, chunk) for chunk in range(chunks))
        threads = [threading.Thread(target=self._run, daemon=True,
                                    name=f"train-reserve-{k}-{n}")
                   for n in range(count)]
        self._threads += threads
        for t in threads:
            t.start()

    def _run(self):
        while True:
            with self._lock:
                if self._stopped or not self._work:
                    return
                k, chunk = self._work.popleft()
            if self._reserves[k].write(chunk):
                self._record(k)

    def _record(self, k: int):
        start, faults, count = self._began[k]
        self._began[k] = None
        tracing.record_late(
            self._rows, "train.snapshot.reserve", start, time.time(),
            tracing.child(self._ctx),
            {"set": k, "bytes": self._reserves[k].ready,
             "writes": RESERVE_WRITES, "threads": count,
             "minor_faults": _minor_faults() - faults})

    def stop(self) -> None:
        """End the threads (each after the chunk it is writing) and let
        go of what they had not reached or begun: idempotent."""
        with self._lock:
            self._stopped = True
        for t in self._threads:
            t.join()
        self._threads = []
        for k, began in list(self._began.items()):
            if began is not None:   # begun and not whole: what there is
                self._record(k)
        for reserve in self._reserves:
            reserve.release()


def _reserve_threads() -> int:
    """Half the cores this process may run on, eight at most: six on
    the one-chip machines' 13, where 8 GiB are written twice in 1.8 s
    by six threads or eight, 2.5 s by four, 5.8 s by two and 13.4 s by
    one (ISSUE 54's probe, `PERF.md` §5)."""
    return max(1, min(8, len(os.sched_getaffinity(0)) // 2))


def _minor_faults() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_minflt


class TrainWorker(CollectiveActorMixin):
    """Actor wrapping a TrainingOperator (reference:
    distributed_torch_runner.py DistributedTorchRunner)."""

    def __init__(self, operator_cls_pickled: bytes, config: dict,
                 world_rank: int, world_size: int, group_name: str):
        self._operator_cls = pickle.loads(operator_cls_pickled)
        self._config = config
        self._rank = world_rank
        self._world_size = world_size
        self._group_name = group_name
        self.operator = None

    def setup_operator(self):
        """`train.setup`: the first traced call a new worker runs, so
        its reply also takes the worker's own start home
        (`tracing.pending`)."""
        with tracing.span("train.setup", tracing.child_of_current(),
                          ambient=True):
            if self._config.get("multihost"):
                # Join the group's global jax runtime BEFORE the
                # operator's first backend use; the operator then sees
                # jax.devices() = the whole group and builds a global
                # mesh.
                from ray_tpu.parallel import multihost

                multihost.initialize(self._group_name, self._world_size,
                                     self._rank)
            from ray_tpu.train.operator import TrainingOperator

            if (isinstance(self._operator_cls, type)
                    and issubclass(self._operator_cls, TrainingOperator)):
                # the first backend use, made here so that libtpu's
                # initialisation is a span and not the head of the
                # user's `model_init`
                facts = {}
                with tracing.span("train.setup.backend",
                                  tracing.child_of_current(), facts):
                    import jax

                    devices = jax.devices()
                    facts.update(platform=devices[0].platform,
                                 devices=len(devices))
            self.operator = self._operator_cls(
                self._config, self._rank, self._world_size,
                group_name=self._group_name)
        # what one snapshot of this worker's state takes in the driver
        # (`_BufferSet`), where the operator can say
        sizes = getattr(self.operator, "snapshot_bytes", None)
        return sizes() if sizes is not None else True

    def train_epoch(self, num_steps=None, profile_dir=None, pull_of=None):
        """`pull_of`: the driver pulls the copy held of that epoch while
        this one runs; said here, before the first step, so that however
        short the epoch its end finds the pull open (`expect_pull`)."""
        if pull_of is not None:
            self.operator.expect_pull(pull_of)
        return self.operator.train_epoch(num_steps, profile_dir=profile_dir)

    def task_lane(self, method_name):
        """Where the runtime runs a call (`core_worker._task_lane`: a
        name is a thread of its own, None the actor's one lane). While
        the operator holds a copy of its state the `state_piece` calls
        run on the lane `pull`, one at a time and in order: the driver
        pulls the held copy while the NEXT epoch runs where every epoch
        runs, on the actor's one lane, and no dispatch of it queues
        behind a piece. An operator that holds nothing — no room, the
        CPU — never has a second lane."""
        if method_name in ("state_piece", "end_pull") and getattr(
                self.operator, "holds_state", False):
            return "pull"
        return None

    def start_profile(self, profile_dir):
        """Trainer.train(profile_dir=) brackets the worker's side of the
        call with these two (an operator without a profiler: no-ops)."""
        start = getattr(self.operator, "start_profile", None)
        return bool(start and start(profile_dir))

    def stop_profile(self):
        stop = getattr(self.operator, "stop_profile", None)
        return bool(stop and stop())

    def validate(self, num_steps=None):
        return self.operator.validate(num_steps)

    def state_dict(self):
        return self.operator.state_dict()

    def state_piece(self, index, usable, drop=(), of_epoch=None):
        """One piece of the operator's state (`train/snapshot.py`); with
        `of_epoch`, of the copy the operator holds since that epoch's
        end (only an operator that said it holds one is asked so). An
        operator that only has `state_dict` gives its host tree, cut the
        same way."""
        from ray_tpu._private import global_state
        from ray_tpu.train import snapshot

        # no more than THIS node's store holds either
        usable = min(usable, snapshot.usable_bytes(
            global_state.require_core_worker()))
        own = getattr(self.operator, "state_piece", None)
        if of_epoch is not None:
            return own(index, usable, drop, of_epoch)
        if own is not None:
            return own(index, usable, drop)
        state = self.operator.state_dict()
        return snapshot.piece({k: v for k, v in state.items()
                               if k not in drop}, index, usable)

    def end_pull(self):
        """The driver's pull of the held copy failed half-way."""
        return self.operator.end_pull()

    def load_state_piece(self, first, leaves, treedef=None):
        """The other direction: leaves [first, ...) of a state, in
        order from 0; True once the state is whole and installed."""
        own = getattr(self.operator, "load_state_piece", None)
        if own is not None:
            return own(first, leaves, treedef)
        from ray_tpu.train import snapshot

        if first == 0:
            self._assembler = snapshot.Assembler()
        state = self._assembler.add(first, leaves, treedef)
        if state is None:
            return False
        self.operator.load_state_dict(state)
        return True

    def read_counter(self, name: str) -> float:
        """Worker-process metric readback (wire A/B verification)."""
        from ray_tpu._private import stats

        snap = stats.snapshot().get(name)
        return float(snap["value"]) if snap else 0.0

    def read_metric(self, name: str):
        """Full metric snapshot (histograms/gauges, not just counter
        values) — bench + ingest-wait gate readback."""
        from ray_tpu._private import stats

        return stats.snapshot().get(name)

    def peak_rss(self) -> int:
        """Peak RSS of this worker process in bytes (bench readback)."""
        import resource
        import sys

        ru = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        return int(ru if sys.platform == "darwin" else ru * 1024)

    def attach_ingest(self, dataset_actor, depth: int):
        """Register a streaming loader over this rank's DatasetShard
        actor: batches prefetch `depth` deep through the object plane
        while the step computes (validation loader untouched)."""
        from ray_tpu.train.ingest import IngestStream

        op = self.operator
        op.register_data(
            train_loader=IngestStream(dataset_actor, depth,
                                      lambda: op.epoch),
            validation_loader=op._val_loader)
        return True

    def opt_shard_state(self):
        return self.operator.opt_shard_state()

    def load_opt_shard(self, shard):
        self.operator.load_opt_shard(shard)
        return True

    def sync_state(self, src_rank: int = 0):
        """Collectively broadcast the full training state from src_rank
        over the group's data plane (shm segment / pipelined ring for
        large payloads) instead of the driver pushing world_size pickled
        copies. Every rank must call this."""
        import numpy as np

        from ray_tpu.collective import collective as col

        group = col._manager.get_group(self._group_name)
        if self._rank == src_rank:
            blob = np.frombuffer(
                pickle.dumps(self.operator.state_dict()), np.uint8)
            size = np.array([blob.size], np.int64)
        else:
            blob = None
            size = np.zeros(1, np.int64)
        size = group.broadcast(size, src_rank)  # geometry first: all
        if self._rank != src_rank:              # ranks pass equal shapes
            blob = np.empty(int(size[0]), np.uint8)
        out = group.broadcast(blob, src_rank)
        if self._rank != src_rank:
            self.operator.load_state_dict(pickle.loads(out.tobytes()))
        return True

    def shutdown(self):
        ray_tpu.exit_actor()


class Trainer:
    """Data-parallel trainer with elastic fault tolerance (reference:
    torch_trainer.py:39).

    Every `train()` call ends with a whole snapshot of the training
    state installed in the driver, which the elastic restore consumes.
    Where the worker's devices have no room for a second copy of the
    state (`TrainingOperator._room_to_hold`; the CPU; a group of
    several workers) it is this call's: pulled after the epoch, as
    ever. Where they have, the worker HOLDS a copy at the epoch's end,
    `train()` returns without pulling, and the NEXT call pulls it
    beside its own epoch: while call k + 1 runs the installed snapshot
    is call k - 1's, from the moment the pull lands call k's, and call
    k + 1 returns with call k's installed — one call older than
    without. A worker lost before the pull has landed takes the held
    copy with it: the restore goes back to the installed snapshot and
    runs up to TWO calls again (the one whose copy was lost, with its
    own `num_steps`, then the one under way) where it runs one without.
    What a caller asks for is never stale: `state_dict()`, `save()` and
    `shutdown()` (not `force=True`, which kills) pull a pending copy
    first, `load_state_dict()` / `load()` drop it. A pull that raises
    installs nothing, deferred or not. The very first call, and any
    call that finds the installed snapshot more than a call behind
    (after a restore), pulls at once.

    Where the devices have room for a PART of the state (the rule
    answers in bytes: `TrainingOperator._held_part`), the worker holds
    the pieces at the tail of the state's cut that fit; `train()` pulls
    the pieces before them from the live state at once, into the buffer
    set written longest ago, installs nothing, and returns; the next
    call pulls the held pieces beside its epoch and installs the state
    when its last piece has landed. The guarantee is the holding
    worker's, no weaker: the installed snapshot is always whole and at
    most one call older than without; a worker lost before the held
    pieces have landed takes them with it, what had crossed at once is
    dropped, and the restore runs up to two calls again."""

    def __init__(self, training_operator_cls, *, num_workers: int = 1,
                 config: dict | None = None,
                 resources_per_worker: dict | None = None,
                 use_tpu: bool = False,
                 backend: str = "host",
                 max_retries: int = 3,
                 collective_timeout: float = 30.0,
                 setup_timeout: float = 600.0,
                 quantize: str | None = None,
                 collective_transport: str = "auto",
                 placement_strategy: str | None = "ICI_RING",
                 sharded: bool = False,
                 mesh_mode: str | None = None,
                 ingest=None):
        """quantize="int8" makes the gradient-sync collective ride the
        block-scaled int8 wire format (EQuARX-style) on the tiers that
        have a wire — the collective DEVICE plane and the host TCP ring
        — cutting gradient bytes ~4x; state sync (broadcast) and
        node-local tiers stay exact. collective_transport pins the
        group's data plane to one tier (tests / wire A/Bs).

        placement_strategy (default "ICI_RING"): gang-reserve the
        workers through a placement group per generation so consecutive
        ranks land on ICI-neighboring nodes and the collective tier is
        DERIVED from the reservation (probe-free); clusters without
        topology coords degrade it to PACK at the GCS. None disables
        the reservation entirely (pre-topology scheduling).

        sharded=True turns on the ZeRO weight-update schedule
        (arXiv:2004.13336): reducescatter(grads) → optimizer update on
        the local 1/N shard of (params, opt state) → allgather(params).
        Optimizer memory per worker drops N×; with quantize="int8" the
        grad wire drops ~4× on top. Checkpoints become per-rank shard
        files behind an index manifest (save/load), and elastic resizes
        re-partition the optimizer shards to the new world size instead
        of re-broadcasting a replicated blob.

        mesh_mode="fsdp" builds the topology-derived ('data','fsdp')
        mesh (parallel.mesh.fsdp_mesh) inside each worker and shards
        params over the fsdp axis — single-worker or multihost groups
        only (host-backend data parallelism would not sync mesh-local
        shards). One worker (or a multihost group) whose
        resources_per_worker grant it more than one TPU chip takes this
        mode by itself, over the chips it holds: on a v5e host's four,
        state and batch sharded four ways (operator.register).

        ingest: an ingest.IngestSpec — one DatasetShard actor per rank
        streaming prefetched batches through the object plane
        (train/ingest.py); replaces the operator's train_loader."""
        self._operator_cls = training_operator_cls
        self._config = dict(config or {})
        self._sharded = bool(sharded)
        if sharded:
            if mesh_mode is not None:
                raise ValueError(
                    "sharded=True (host-collective ZeRO) and mesh_mode "
                    "(XLA SPMD) are mutually exclusive update plans")
            if self._config.get("multihost"):
                raise ValueError(
                    "sharded=True uses the HOST collective plane; "
                    "multihost groups sync through XLA psum instead")
            self._config["sharded_update"] = True
        if mesh_mode is not None:
            if mesh_mode != "fsdp":
                raise ValueError(f"unknown mesh_mode {mesh_mode!r} "
                                 "(expected 'fsdp' or None)")
            if num_workers > 1 and not self._config.get("multihost"):
                raise ValueError(
                    "mesh_mode='fsdp' with multiple workers requires "
                    "config={'multihost': True} (a GLOBAL mesh); "
                    "host-backend workers would each build a private "
                    "mesh and never sync")
            self._config["mesh_mode"] = mesh_mode
        self._ingest = ingest
        self._ingest_actors: list = []
        self._quantize = quantize
        self._collective_transport = collective_transport
        self._placement_strategy = placement_strategy
        self._pg = None
        self._num_workers = num_workers
        self._resources = dict(resources_per_worker or {"CPU": 1})
        if use_tpu:
            self._resources.setdefault("TPU", 1)
        self._backend = backend
        self._max_retries = max_retries
        self._collective_timeout = collective_timeout
        # A cold first compile can take minutes (ResNet-50's whole step:
        # about one on a v5e); operator setup waits this long before
        # declaring the worker wedged.
        self._setup_timeout = setup_timeout
        self._generation = 0
        self._uid = uuid.uuid4().hex[:8]
        self.workers: list = []
        self._last_state: dict | None = None
        self._last_shards: list | None = None
        # The two buffer sets (`_BufferSet`), the one written last
        # first: the other is the next copy's destination (train). The
        # first group's start reserves their bytes and has threads of
        # the Trainer's own make them resident (`_reserve_sets`).
        self._owned = (_BufferSet(), _BufferSet())
        self._reserver: _Reserver | None = None
        # train() calls made; the call whose state is installed
        # (`_last_state`; None: none yet); the call whose state the
        # worker holds, not pulled yet (a _Pending, see the class)
        self._calls = 0
        self._snapshot_of: int | None = None
        self._pending: _Pending | None = None
        self._start_workers(num_workers)

    # ------------------------------------------------------------------
    # worker group lifecycle (reference: worker_group.py:107/:208)
    # ------------------------------------------------------------------

    def _gang_reserve(self, num_workers: int):
        """Reserve one bundle per worker under the trainer's placement
        strategy. Best-effort: a reservation that cannot be placed
        promptly (resources still draining from the previous
        generation, single saturated node) is dropped and the workers
        schedule exactly as before — the reservation is an
        optimization, never a new failure mode."""
        if self._placement_strategy is None or num_workers <= 1:
            return None
        from ray_tpu.util.placement_group import (placement_group,
                                                  remove_placement_group)

        try:
            pg = placement_group(
                [dict(self._resources) for _ in range(num_workers)],
                strategy=self._placement_strategy,
                name=f"sgd-{self._uid}-g{self._generation}")
        except Exception:
            return None
        try:
            # short bound: a placeable gang resolves in well under a
            # second; anything longer means the fleet is saturated and
            # the pre-topology queue-and-wait path is strictly better
            # than stalling __init__ here
            if pg.ready(timeout=3.0):
                return pg
        except Exception:
            pass
        # not placeable (or ready() errored): the registered group must
        # not linger — a later GCS retry would reserve a full worker-set
        # of resources nobody ever uses
        try:
            remove_placement_group(pg)
        except Exception:
            pass
        return None

    def _release_gang(self):
        if self._pg is None:
            return
        from ray_tpu.util.placement_group import remove_placement_group

        try:
            remove_placement_group(self._pg)
        except Exception:
            pass
        self._pg = None

    def _start_workers(self, num_workers: int):
        """One generation of the group, as ONE trace rooted at
        `train.start` (always recorded; kept in `start_log()`). A tree
        of its own even where a `train()` call restarts the group:
        `in_call` then names that call's trace."""
        self._generation += 1
        counts = {"generation": self._generation, "workers": num_workers,
                  "restored": 0}
        in_call = tracing.current_id()
        if in_call is not None:
            counts["in_call"] = in_call
        with tracing.use(None):
            root = tracing.always_trace()
        with tracing.open_tree(root) as rows:
            try:
                with tracing.span("train.start", root, counts,
                                  ambient=True):
                    self._start_traced(num_workers, counts, rows)
            finally:
                _start_log.append((root.trace_id.hex(), rows))

    def _start_traced(self, num_workers: int, counts: dict, rows: list):
        group_name = f"sgd_{self._uid}_g{self._generation}"
        # cloudpickle: operator classes defined in __main__ or notebooks
        # serialize by value (stdlib pickle would import-by-reference and
        # fail on the worker).
        pickled = cloudpickle.dumps(self._operator_cls)
        self._pg = self._gang_reserve(num_workers)
        worker_cls = ray_tpu.remote(
            resources=dict(self._resources))(TrainWorker)
        self.workers = [
            worker_cls.options(
                placement_group=self._pg,
                placement_group_bundle_index=rank,
            ).remote(pickled, self._config, rank, num_workers, group_name)
            if self._pg is not None else
            worker_cls.remote(pickled, self._config, rank, num_workers,
                              group_name)
            for rank in range(num_workers)
        ]
        if num_workers > 1 and not self._config.get("multihost"):
            # multihost groups sync gradients through XLA collectives
            # inside the jitted step — no HOST group needed.
            from ray_tpu.collective import collective as col

            col.create_collective_group(
                self.workers, num_workers, list(range(num_workers)),
                backend=self._backend, group_name=group_name,
                timeout=self._collective_timeout,
                quantize=self._quantize,
                transport=self._collective_transport,
                # ICI_RING reservations carry the derived transport tier
                placement_group=self._pg)
        sizes = ray_tpu.get([w.setup_operator.remote()
                             for w in self.workers],
                            timeout=self._setup_timeout)
        if self._reserver is None:  # a restarted group: the sets exist
            self._reserve_sets(sizes, rows)
        self._active_workers = num_workers
        self._start_ingest(num_workers)
        if (self._last_state is not None or self._pending is not None
                or (self._sharded and self._last_shards)):
            counts["restored"] = 1
            restored = {}
            with tracing.span("train.start.restore",
                              tracing.child_of_current(), restored,
                              ambient=True):
                restored["bytes"] = self._restore_state()

    def _reserve_sets(self, sizes: list, rows: list):
        """Reserve the bytes of both buffer sets from what the workers
        said of their state (`TrainingOperator.snapshot_bytes`; an
        operator of another kind says nothing, and the sets are
        allocated leaf by leaf as states arrive) and start the threads
        that make the first resident, the one the first pull lands in
        (the second's start with the second call: `_Reserver`). The
        sharded schedule's snapshot is rank 0's state less its
        optimizer shard, and every rank's shard."""
        if not all(isinstance(s, dict) for s in sizes):
            return
        nbytes = sizes[0]["state_bytes"] - sizes[0]["opt_shard_bytes"] + sum(
            s["opt_shard_bytes"] for s in sizes)
        # a leaf starts at a fixed offset within a page (`_Reserve.take`)
        nbytes += _PAGE * (sizes[0]["leaves"] + sum(
            s["leaves"] for s in sizes[1:] if s["opt_shard_bytes"]))
        try:
            first, second = _Reserve(nbytes), _Reserve(nbytes)
        except MemoryError:
            return
        self._reserver = _Reserver([first, second], tracing.current(), rows)
        self._reserver.begin(0)
        newer, older = self._owned
        self._owned = (newer._replace(reserve=second),
                       older._replace(reserve=first))

    def _start_ingest(self, num_workers: int):
        """One DatasetShard actor per rank; every generation re-shards
        the dataset over the CURRENT world size (elastic resize included
        — the survivors' shards re-cover the whole dataset)."""
        if self._ingest is None:
            return
        from ray_tpu._private.config import get_config
        from ray_tpu.train.ingest import DatasetShard

        spec = self._ingest
        depth = (spec.prefetch_depth if spec.prefetch_depth is not None
                 else get_config().train_ingest_prefetch_depth)
        shard_cls = ray_tpu.remote(
            resources=dict(spec.resources or {"CPU": 1}))(DatasetShard)
        fn_pickled = cloudpickle.dumps(spec.dataset_fn)
        self._ingest_actors = [
            shard_cls.remote(fn_pickled, rank, num_workers, self._config)
            for rank in range(num_workers)]
        ray_tpu.get([a.ping.remote() for a in self._ingest_actors],
                    timeout=self._setup_timeout)
        ray_tpu.get([w.attach_ingest.remote(a, depth)
                     for w, a in zip(self.workers, self._ingest_actors)],
                    timeout=self._setup_timeout)

    def _restore_state(self):
        """Re-install training state into a freshly started generation:
        params/progress broadcast once over the data plane, then (in
        sharded mode) per-rank optimizer shards — re-partitioned to the
        new world size when it changed, never a replicated blob. Returns
        the bytes of the state it pushed (`train.start.restore`)."""
        num_workers = len(self.workers)
        pushed = 0
        if self._last_state is not None:
            if (num_workers > 1 and self._backend == "host"
                    and not self._config.get("multihost")):
                # Weight broadcast rides the collective data plane: the
                # driver ships ONE copy to rank 0; the group's shm/ring
                # transport fans it out node-locally (the elastic-resize
                # restore used to pickle the state num_workers times).
                pushed = self._push_state(self.workers[:1],
                                          self._last_state)
                ray_tpu.get([w.sync_state.remote(0) for w in self.workers],
                            timeout=self._setup_timeout)
            else:
                pushed = self._push_state(self.workers, self._last_state)
        if self._sharded and self._last_shards:
            shards = self._last_shards
            if len(shards) != num_workers:
                if _fp.ARMED:
                    _fp.fire_strict("train.reshard")
                from ray_tpu.train import sharding as _shardlib

                shards = _shardlib.reshard_opt_shards(shards, num_workers)
            ray_tpu.get([w.load_opt_shard.remote(s)
                         for w, s in zip(self.workers, shards)],
                        timeout=self._setup_timeout)
        if self._pending is not None:
            # The state of the last call was held on a worker that is
            # gone, and never pulled: the snapshot just installed is a
            # call older. That call again, as it was made (cleared only
            # once it has run: a worker lost in it is restored again).
            ray_tpu.get([w.train_epoch.remote(self._pending.num_steps)
                         for w in self.workers], timeout=600)
            self._forget_pending()
        return pushed

    def _kill_workers(self):
        for w in self.workers + self._ingest_actors:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass
        self.workers = []
        self._ingest_actors = []
        # release the gang's bundles BEFORE the next generation reserves
        # its own — a lingering hold would starve the new reservation
        self._release_gang()

    def _resize_worker_group(self):
        """Reference: torch_trainer.py:328 — shut the group down, restart
        at whatever size is currently schedulable, restore state."""
        broken, _ = self._gang_interrupted()
        if not broken and len(self.workers) == self._num_workers:
            # No-op resize: the gang is intact at full strength — keep
            # it. Restarting here would pay a redundant state broadcast
            # and drop every compiled step for nothing (the old
            # path did exactly that). Wedged-but-alive groups still
            # terminate: the caller's retry budget bounds us.
            return
        self._kill_workers()
        # Prefer the full size; shrink to what every resource type can hold.
        target = self._num_workers
        avail = ray_tpu.available_resources()
        for res, need in self._resources.items():
            if need > 0:
                target = min(target, int(avail.get(res, 0) // need))
        try:
            self._start_workers(max(1, target))
        except Exception:
            self._kill_workers()
            raise

    # ------------------------------------------------------------------
    # train/validate (reference: torch_trainer.py:365 train)
    # ------------------------------------------------------------------

    def _any_worker_dead(self) -> bool:
        return self._gang_interrupted()[0]

    def _gang_interrupted(self) -> tuple[bool, bool]:
        """-> (broken, planned). Broken: a worker is DEAD or parked on a
        DRAINING node (the node is leaving; its bundle can't follow).
        Planned: every interruption found is a drain — the next
        generation re-gangs from a fresh ICI_RING reservation placed
        around the hole (the GCS placement record carries the masked
        coords), with the collective tier re-derived from that record
        rather than probe rounds."""
        cw = global_state.require_core_worker()
        try:
            draining = {n["node_id"] for n in cw.cluster_info()["nodes"]
                        if n.get("state") not in (None, "ALIVE")}
        except Exception:
            draining = set()
        broken = False
        planned = True
        # ingest actors are part of the gang: a dead DatasetShard means
        # its rank's stream is gone, so the generation restarts (and
        # re-shards the dataset) exactly like a dead worker
        for w in self.workers + self._ingest_actors:
            info = cw.get_actor_info(w._actor_id.binary())
            if info is None or info.get("state") == "DEAD":
                broken = True
                if "drained" not in (info or {}).get("death_cause", ""):
                    planned = False
            elif info.get("node_id") in draining:
                broken = True
        return broken, broken and planned

    # planned departures re-gang for free, but boundedly so — a fleet
    # draining in a loop must not keep a train() call alive forever
    _MAX_PLANNED_REGANGS = 8

    def _run_with_retries(self, fn_name: str, num_steps,
                          counts: dict | None = None, refs=None, **kw):
        """`refs`: the first attempt's calls, if the caller has
        submitted them already (`_epoch_beside_pull`)."""
        attempt = 0
        planned_regangs = 0
        while True:
            if counts is not None:   # the caller's span carries them
                counts["attempts"] = attempt + planned_regangs + 1
            try:
                if not self.workers:
                    raise exc.WorkerCrashedError("worker group is empty")
                first, refs = refs, None
                return ray_tpu.get(
                    first or [getattr(w, fn_name).remote(num_steps, **kw)
                              for w in self.workers],
                    timeout=600)
            except (exc.ActorDiedError, exc.WorkerCrashedError,
                    exc.GetTimeoutError):
                _, planned = self._gang_interrupted()
                if planned and planned_regangs < self._MAX_PLANNED_REGANGS:
                    # a drain took a worker: planned departure costs no
                    # retry budget (crash recovery stays bounded as before)
                    planned_regangs += 1
                elif attempt >= self._max_retries:
                    raise
                else:
                    attempt += 1
            except exc.TaskError:
                # A collective timing out inside a surviving worker usually
                # means a peer died mid-epoch; anything else is a user error.
                broken, planned = self._gang_interrupted()
                if not broken:
                    raise
                if planned and planned_regangs < self._MAX_PLANNED_REGANGS:
                    planned_regangs += 1
                elif attempt >= self._max_retries:
                    raise
                else:
                    attempt += 1
            time.sleep(0.5)
            try:
                self._resize_worker_group()
            except Exception:
                if attempt >= self._max_retries:
                    raise
                # group left empty; next attempt resizes again

    def train(self, num_steps: int | None = None,
              reduce_results: bool = True, profile_dir: str | None = None):
        """One epoch (or `num_steps`) on every worker, then the
        epoch-boundary snapshot the elastic restore consumes. The call
        is ONE trace rooted at `train.call` (always recorded; kept in
        `call_log()`; `call` counts this Trainer's calls from 1, and
        `train.snapshot` says which call's state it pulled, `of_call`,
        and whether beside this call's epoch, `deferred`: the class
        docstring); `profile_dir` brackets the workers' whole side of
        the call — epoch, snapshot, return put — with a jax profiler
        session, and turns the per-leaf spans of the snapshot on."""
        root = tracing.always_trace(fine=bool(profile_dir))
        self._calls += 1
        counts = {"num_steps": num_steps, "workers": len(self.workers),
                  "call": self._calls}
        with tracing.open_tree(root) as rows:
            try:
                with tracing.span("train.call", root, counts, ambient=True):
                    return self._train_traced(num_steps, reduce_results,
                                              profile_dir)
            finally:
                _call_log.append((root.trace_id.hex(), rows))

    def _train_traced(self, num_steps, reduce_results, profile_dir):
        if self._calls == 2 and self._reserver is not None:
            # the first pull that lands in the second set is this
            # call's or, where this call's state is held, the next's,
            # beside its epoch: then this call ends when the set is
            # whole (below), so that no later call finds it half made
            self._reserver.begin(1)
        if profile_dir:
            ray_tpu.get([w.start_profile.remote(profile_dir)
                         for w in self.workers], timeout=120)
        try:
            if self._pending is None:
                counts = {}
                with tracing.span("train.epoch", tracing.child_of_current(),
                                  counts, ambient=True):
                    results = self._run_with_retries(
                        "train_epoch", num_steps, counts)
            else:
                results = self._epoch_beside_pull(num_steps)
            # a worker that holds a copy of this call's state says so,
            # and from which leaf on (one worker that owns its whole
            # state: operator._hold; leaf 0: all of it)
            held = [r.pop("held_epoch", None) for r in results]
            held_from = [r.pop("held_from", 0) for r in results]
            if (len(held) == 1 and held[0] is not None
                    and self._snapshot_of == self._calls - 1):
                # ... and the installed snapshot is the last call's:
                # what is held of this call's is pulled beside the next
                # epoch, the pieces before it (a part is held) now
                part = held_from[0]
                pull = self._snapshot(self._calls,
                                      held_from=part) if part else None
                if pull is not None or not part:    # else: it is whole
                    self._pending = _Pending(self._calls, held[0],
                                             num_steps, pull)
            else:
                self._snapshot(self._calls)
            if self._calls == 2:
                for reserve in (s.reserve for s in self._owned):
                    if reserve is not None:
                        reserve.wait_for()
        finally:
            if profile_dir:
                # a worker restarted mid-call has no session (a no-op);
                # one that died must not hide the call's own error
                try:
                    ray_tpu.get([w.stop_profile.remote()
                                 for w in self.workers], timeout=300)
                except exc.RayTpuError:
                    pass
        return _reduce(results) if reduce_results else results

    def _snapshot(self, of_call: int, of_epoch: int | None = None,
                  pull: "_Pull | None" = None,
                  held_from: int | None = None) -> "_Pull | None":
        """Pull the state `of_call` left and install it: the worker's
        live state, or with `of_epoch` the copy it holds since that
        epoch's end (`deferred` on the span: beside this call's epoch,
        or a drain). Where the worker holds a PART of the state (from
        the leaf `held_from` on) the call pulls the pieces before it
        from the live state and returns the `_Pull`, nothing installed;
        the next hands it back with `of_epoch` for the held pieces.
        None once the state is whole and installed."""
        counts = {"deferred": int(of_epoch is not None), "of_call": of_call}
        with tracing.span("train.snapshot", tracing.child_of_current(),
                          counts, ambient=True):
            # Two sets of buffers take turns: this call's views are
            # copied into the set `_own` built two calls ago. Only
            # trees `_own` built are in `_owned`, so a caller's
            # arrays (load_state_dict, load) are never written to;
            # neither is the newer set, which is the installed
            # snapshot unless the caller's took its place. Both
            # parts are installed, and the sets turned, only once
            # both are whole: a copy that raises changes nothing.
            newer, older = self._owned
            waited = 0.0
            if pull is None:
                pull = _Pull(older)
                # A pull does not start before its set is whole: the
                # threads that write it and the worker's chain into
                # pages of its own slow each other by more than either
                # takes (set 1 beside the second call's pull: 6.4-7.0 s
                # for the 1.8 s of writing, 5.0-5.5 s for the 2.7 s of
                # pull).
                if pull.reserve is not None:
                    waited = pull.reserve.wait_for()
            reserve = pull.reserve
            try:
                # sharded: the epoch-boundary snapshot is params (rank
                # 0; identical everywhere) + ALL optimizer shards — the
                # reshardable unit the elastic restore path consumes.
                # Rank 0's own shard is never kept, so it stays where
                # it is and the tree matches the spare's.
                self._pull_state(
                    self.workers[0], pull, counts,
                    drop=("opt_shard",) if self._sharded else (),
                    of_epoch=of_epoch, waited=waited, held_from=held_from)
                if not pull.whole:
                    return pull
                state, shards = pull.state(), None
                if self._sharded:
                    shards = _own(ray_tpu.get(
                        [w.opt_shard_state.remote() for w in self.workers],
                        timeout=120), older.shards, older.writes,
                        reserve=reserve)
            except BaseException:
                pull.drop()
                raise
            self._last_state, self._last_shards = state, shards
            self._snapshot_of = of_call
            # a set no leaf of which went into the spare's buffers
            # (a first call, a changed tree) is new: written once —
            # and done with a reservation none of it lies in
            reused = _written_into((state, shards),
                                   (older.state, older.shards))
            if reserve is not None:
                reserve.seal()
                if not _lies_in((state, shards), reserve):
                    reserve = None
            self._owned = (_BufferSet(
                state, shards, older.writes + 1 if reused else 1, reserve),
                newer)
        return None

    def _epoch_beside_pull(self, num_steps) -> list:
        """A call that finds the last call's state held: the epoch is
        submitted first, the held copy (the whole state, or the pieces
        the last call did not pull at once) pulled and installed while
        it runs (the pieces on a lane of their own:
        `TrainWorker.task_lane`), then the epoch waited for. A
        worker lost under the pull takes the copy with it: the epoch's
        own retries restore the group, and the restore runs the lost
        call again (`_restore_state`). Any other failure of the pull
        is the caller's, as ever; the epoch under way is left to end
        and the worker told to let go of the copy."""
        pending = self._pending
        ctx, counts, start = tracing.child_of_current(), {}, time.time()
        with tracing.use(ctx):      # its tasks hang under `train.epoch`
            refs = [w.train_epoch.remote(num_steps, pull_of=pending.epoch)
                    for w in self.workers]
        try:
            self._pull_pending()
        except BaseException:
            if self._pending is None:   # not the worker's loss
                raise
        with tracing.span("train.epoch", ctx, counts, ambient=True,
                          start=start):
            return self._run_with_retries("train_epoch", num_steps, counts,
                                          refs=refs)

    def _pull_pending(self):
        """Pull what the worker holds of the last call's state and
        install the state: afterwards nothing is pending. A pull that
        raises installs nothing and the worker is told to let go of the
        copy (an epoch's end waits for the pull); what the call had
        pulled at once went with it. If it raised because the worker is
        lost, the call stays pending for the restore to run again
        (`_restore_state`); after any other failure the next call pulls
        its own state at once."""
        pending = self._pending
        try:
            self._snapshot(pending.call, pending.epoch, pending.pull)
            self._pending = None
        except BaseException as e:
            for w in self.workers:
                try:
                    w.end_pull.remote()
                except exc.RayTpuError:
                    pass
            self._pending = (pending._replace(pull=None)
                             if self._worker_lost(e) else None)
            raise

    def _worker_lost(self, e: BaseException) -> bool:
        """Whether a pull raised `e` because its worker is gone."""
        return isinstance(e, (exc.ActorDiedError, exc.WorkerCrashedError)
                          ) or (isinstance(e, exc.RayTpuError)
                                and self._gang_interrupted()[0])

    def _forget_pending(self):
        """Nobody pulls the pending state any more (another state took
        its place, or its worker is gone and the call has run again)."""
        if self._pending is not None and self._pending.pull is not None:
            self._pending.pull.drop()
        self._pending = None

    def _drain(self):
        """Pull what the worker still holds of the last call, now:
        afterwards the installed snapshot is that call's."""
        if self._pending is not None:
            self._pull_pending()

    def validate(self, num_steps: int | None = None,
                 reduce_results: bool = True):
        results = self._run_with_retries("validate", num_steps)
        return _reduce(results) if reduce_results else results

    # ------------------------------------------------------------------
    # checkpointing
    # ------------------------------------------------------------------

    def _pull_state(self, worker, pull: "_Pull | None" = None,
                    counts: dict | None = None, drop=(),
                    of_epoch: int | None = None, waited: float = 0.0,
                    held_from: int | None = None) -> "_Pull":
        """`worker`'s training state (with `of_epoch`: the copy it holds
        since that epoch's end) into memory the driver owns: the pieces
        `pull` has not got yet — all of them, or with `held_from` those
        before the piece that begins at that leaf (the rest the worker
        holds, for a later call with `of_epoch`).
        It crosses the object plane as the pieces `train/snapshot.py`
        cuts (also a state the store would hold whole): each goes
        device→host and into the arena on the worker, out through `_own`
        into the leaves of `pull.spare` here (a tree an earlier pull
        built, or bytes of its reservation where it built none: see
        `_own`), and is released. The driver asks ahead — the actor runs
        the `state_piece` calls in order, one at a time (on a lane of
        their own where a held copy is pulled beside an epoch:
        `TrainWorker.task_lane`), so the worker brings the next piece to
        the host while this side copies the last — as long as what is in
        the store or on its way there never exceeds what it holds.
        Nothing of the spare or of the result is installed here: a piece
        that raises leaves the caller's snapshot as it was. Inside a
        trace the driver thread's time is tiled, a piece, by
        `train.snapshot.wait` (blocked until the worker has put the
        piece; `object.get` hangs under it) and `train.snapshot.copy`
        (`_own`; `writes` is how often the spare's buffers were written
        before)."""
        from ray_tpu.train import snapshot

        usable = snapshot.usable_bytes(global_state.require_core_worker())
        which = () if of_epoch is None else (of_epoch,)
        pull = pull or _Pull(_BufferSet())
        first = pull.pieces
        # bytes asked for and not copied out yet; one past this part's
        # last piece (a pull that begins at piece 0 learns both from it)
        held = pull.sizes[first] if pull.ranges else 0
        stop = pull.stop(held_from) if pull.ranges else 0
        pending = collections.deque()

        def ask(index):
            pending.append((index, worker.state_piece.remote(
                index, usable, drop, *which)))
            return index + 1

        def ask_ahead():
            nonlocal asked, held
            while asked < stop and held + pull.sizes[asked] <= usable:
                held += pull.sizes[asked]
                asked = ask(asked)

        asked = ask(first)
        try:
            while pending:
                index, ref = pending.popleft()
                with tracing.span("train.snapshot.wait",
                                  tracing.child_of_current(),
                                  {"piece": index}, ambient=True):
                    piece = ray_tpu.get(ref, timeout=120)
                del ref
                if index == 0:
                    pull.plan(piece)
                    held, stop = pull.sizes[0], pull.stop(held_from)
                ask_ahead()                 # what fits beside this piece
                a, b = pull.ranges[index]
                pull.leaves.extend(_own(
                    piece["leaves"], pull.spares[a:b], pull.spare.writes,
                    piece=index, reserve=pull.reserve,
                    waited=waited if index == first else 0.0))
                piece = None                # the views die here
                held -= pull.sizes[index]
                ask_ahead()                 # ... and what fits without it
        except BaseException as e:
            # whoever keeps the exception keeps its frames: let go of
            # what they pin in the arena (views, pieces asked ahead)
            pending.clear()
            piece = None
            traceback.clear_frames(e.__traceback__)
            raise
        if counts is not None:
            counts.update(pieces=stop - first,
                          bytes=sum(pull.sizes[first:stop]))
        return pull

    def _push_state(self, workers: list, state: dict):
        """The other direction (`load_state_dict`, the elastic restore):
        `state` to every worker in `workers`, cut the same way, one put
        a piece whatever the number of workers; the next piece goes when
        every worker has placed the last. Returns the state's bytes."""
        import jax

        from ray_tpu.train import snapshot

        leaves, treedef = jax.tree.flatten(state)
        usable = snapshot.usable_bytes(global_state.require_core_worker())
        sizes = [snapshot.leaf_bytes(x) for x in leaves]
        for first, stop in snapshot.plan(sizes, usable):
            part = ray_tpu.put(leaves[first:stop])
            ray_tpu.get(
                [w.load_state_piece.remote(
                    first, part, treedef if first == 0 else None)
                 for w in workers], timeout=self._setup_timeout)
            del part    # out of the arena before the next piece goes in
        return sum(sizes)

    def state_dict(self) -> dict:
        self._drain()
        return self._pull_state(self.workers[0]).state()

    def load_state_dict(self, state: dict):
        self._last_state = state
        # the workers drop a copy they hold of the state this replaces
        self._forget_pending()
        self._snapshot_of = self._calls
        self._push_state(self.workers, state)

    def save(self, path: str) -> str:
        """Unsharded: one pickle, as before. Sharded: each worker's
        optimizer shard returns through the object plane (plasma +, for
        cross-node workers, the bulk transfer channel) and the driver
        writes one file per shard plus a small index manifest at `path`
        — no full replicated optimizer blob ever assembles anywhere."""
        if not self._sharded:   # state_dict() drains a pending pull
            with open(path, "wb") as f:
                pickle.dump(self.state_dict(), f)
            return path
        import os

        state = ray_tpu.get(self.workers[0].state_dict.remote(),
                            timeout=120)
        state.pop("opt_shard", None)
        shard_refs = [w.opt_shard_state.remote() for w in self.workers]
        params_file = os.path.basename(path) + ".params"
        with open(path + ".params", "wb") as f:
            pickle.dump(state, f)
        spans, shard_files = [], []
        for i, ref in enumerate(shard_refs):
            sh = ray_tpu.get(ref, timeout=120)
            spans.append(tuple(sh["span"]))
            shard_files.append(os.path.basename(path) + f".shard{i}")
            with open(f"{path}.shard{i}", "wb") as f:
                pickle.dump(sh, f)
            numel, pad_numel = sh["numel"], sh["pad_numel"]
        manifest = {
            "format": _SHARDED_CKPT_FORMAT, "version": 1,
            "world_size": len(shard_files),
            "numel": numel, "pad_numel": pad_numel, "spans": spans,
            "epoch": state["epoch"], "global_step": state["global_step"],
            "params_file": params_file, "shard_files": shard_files,
        }
        with open(path, "wb") as f:
            pickle.dump(manifest, f)
        return path

    def load(self, path: str):
        """Loads either format; a sharded manifest reshards to the
        CURRENT world size on the way in (any saved N → any running N)."""
        with open(path, "rb") as f:
            blob = pickle.load(f)
        if not (isinstance(blob, dict)
                and blob.get("format") == _SHARDED_CKPT_FORMAT):
            self.load_state_dict(blob)
            return
        if not self._sharded:
            raise ValueError(
                f"{path} is a sharded checkpoint manifest; load it with "
                "Trainer(sharded=True)")
        import os

        base = os.path.dirname(os.path.abspath(path))
        with open(os.path.join(base, blob["params_file"]), "rb") as f:
            self._last_state = pickle.load(f)
        self._last_shards = []
        for sf in blob["shard_files"]:
            with open(os.path.join(base, sf), "rb") as f:
                self._last_shards.append(pickle.load(f))
        self._restore_state()

    @property
    def num_workers(self) -> int:
        return len(self.workers)

    def shutdown(self, force: bool = False):
        if force:
            self._kill_workers()
            self._end_reserver()
            return
        try:    # what the workers hold of the last call comes home first
            self._drain()
        except exc.RayTpuError:
            pass
        self._end_reserver()
        for w in self.workers:
            try:
                w.shutdown.remote()
            except Exception:
                pass
        self.workers = []
        for a in self._ingest_actors:
            try:
                ray_tpu.kill(a)
            except Exception:
                pass
        self._ingest_actors = []
        self._release_gang()


    def _end_reserver(self):
        if self._reserver is not None:
            self._reserver.stop()


def _own(snapshot, spare=None, writes: int = 0, piece: int | None = None,
         reserve=None, waited: float = 0.0):
    """A whole copy of `snapshot` in memory the driver owns, with no
    view into the object store left in it. What `get` returns are
    zero-copy views PINNED in the node's shared arena; a snapshot the
    trainer keeps for the next elastic restore would hold those bytes
    for ever (a GPT-2-small + AdamW state, 1.5 GB in the default 2 GiB
    arena, made the second `train()` fail in the worker's put).

    `spare` is a tree an earlier `_own` built and nothing uses any more
    (`Trainer._snapshot`: the snapshot retired an install ago — a call
    ago where every call pulls its own state, and just the same where
    the pull is deferred to the next call's epoch: the sets turn when a
    snapshot is installed, whichever call's it is, so the installed one
    is never a destination while a deferred pull writes beside an epoch
    either). A leaf is copied
    INTO the spare's leaf at the same path when that is a writable
    array of the same shape, dtype and strides that is the driver's own
    — it owns its data, or `reserve` holds it. (Strides, not C-order:
    on a TPU some leaves arrive transposed, ResNet's `fc_w` for one,
    and the copy keeps their layout.) A leaf the spare has no place for
    takes its destination from `reserve`, the bytes the Trainer
    reserved for this set before any state arrived (`_Reserve`), and
    waits there for the Trainer's threads to have made them resident
    and written them as often as a page needs before it takes a write
    at the steady rate: on the chip machines a page's first write runs
    at 1.0 GB/s and its second at 2.3 where the third runs at 19,
    under `np.empty`, `np.zeros` and a plain mapping alike, and
    `np.array(x)` on the driver's one thread paid both inside a pull.
    Any other leaf — no reservation, a changed tree, optimizer shards
    re-partitioned to another world size — is allocated with
    `np.array(x)`, so the copy is bit-identical either way. `spare` and
    `reserve` are only ever written to, never returned as a whole: a
    copy that raises half-way leaves a half-written spare and the
    installed snapshot untouched. In steady state the driver holds
    two sets of buffers (2 x the snapshot's bytes: 2.8 GiB for
    GPT-2-small), which was the peak before — the old snapshot was
    alive while the new one was built. The span's `reused_bytes` says
    how much went into the spare or the reservation (= `bytes` from a
    Trainer's first call on; 0 where every array had to be allocated),
    `reserve_wait_s` how long the copy stood waiting for bytes the
    reserving threads had not reached (0.0: they were ahead; a pull's
    first copy carries what the pull waited for its set to be whole
    before it asked for a piece: `waited`), its
    `dest_writes` how many SNAPSHOTS had been copied into the set
    before: `writes`, the count the Trainer keeps for it, or 0 where
    every array had to be allocated (0, 0, 1, 1, 2 ... over a
    Trainer's calls). `piece` is the snapshot piece's index, if it is
    one."""
    import jax
    import numpy as np

    counts = {"bytes": 0, "reused_bytes": 0, "dest_writes": writes,
              "reserve_wait_s": waited}
    if piece is not None:
        counts["piece"] = piece
    spares = dict(jax.tree_util.tree_flatten_with_path(spare)[0])

    def reserved(dst) -> bool:
        return reserve is not None and reserve.holds(dst)

    def own(path, x):
        if not isinstance(x, np.ndarray):
            return x
        counts["bytes"] += x.nbytes
        dst = spares.get(path)
        if dst is None and reserve is not None:
            dst = reserve.take(x)       # the set's first snapshot
        elif not (isinstance(dst, np.ndarray)
                  and (dst.flags.owndata or reserved(dst))
                  and dst.flags.writeable and dst.shape == x.shape
                  and dst.dtype == x.dtype and dst.strides == x.strides):
            dst = None
        if dst is None:
            return np.array(x)
        if reserved(dst):
            counts["reserve_wait_s"] += reserve.wait_for(dst)
        np.copyto(dst, x)
        counts["reused_bytes"] += x.nbytes
        return dst

    with tracing.span("train.snapshot.copy", tracing.child_of_current(),
                      counts):
        out = jax.tree_util.tree_map_with_path(own, snapshot)
        if counts["bytes"] and not counts["reused_bytes"]:
            counts["dest_writes"] = 0
        return out


def _lies_in(tree, reserve) -> bool:
    """Whether a leaf of `tree` is one `reserve` handed out."""
    import jax
    import numpy as np

    return any(isinstance(x, np.ndarray) and reserve.holds(x)
               for x in jax.tree.leaves(tree))


def _written_into(new, spare) -> bool:
    """Whether `_own` copied a leaf of `new` into a buffer of `spare`
    (it returns the spare's own array where it did)."""
    import jax
    import numpy as np

    buffers = {id(x) for x in jax.tree.leaves(spare)
               if isinstance(x, np.ndarray)}
    return any(id(x) in buffers for x in jax.tree.leaves(new))


def _reduce(results: list[dict]) -> dict:
    """Average worker metrics; sum sample counts/throughput."""
    if not results:
        return {}
    out = {}
    for k in results[0]:
        vals = [r[k] for r in results if k in r]
        if k in ("num_samples", "samples_per_s", "batch_count"):
            out[k] = type(vals[0])(sum(vals))
        elif isinstance(vals[0], (int, float)):
            out[k] = sum(vals) / len(vals)
        else:
            out[k] = vals[0]
    return out
