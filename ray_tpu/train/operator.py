"""TrainingOperator — user-defined training logic run on each worker
(reference: python/ray/util/sgd/torch/training_operator.py:50 — setup :175,
register :187, train_epoch :437), redesigned jax-first:

- the user registers a functional model (init_fn + loss_fn) and an optax
  optimizer instead of nn.Module/torch.optim objects;
- the framework jits one fused step: value_and_grad → (cross-worker grad
  allreduce) → optimizer update with donated buffers;
- when the model has mutable state (batchnorm stats), register with
  stateful=True and model_init returning (params, state), loss_fn
  (params, state, batch) -> (loss, new_state);
- single-worker (or XLA-backend) groups run ONE fused jit per batch with
  all buffers donated and the loss left on device — no host syncs inside
  the epoch loop, so the framework path matches a bare jit loop;
- multi-worker host groups move gradients as ONE flat bucket
  (ravel_pytree), the DDP bucketing idea without the bookkeeping.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor, wait
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from ray_tpu._private import profiling as _profiling
from ray_tpu._private import tracing as _tracing
from ray_tpu.train import sharding as _shard
from ray_tpu.train import snapshot as _snapshot

# snapshot leaves above this get a span of their own at the fine level
_LEAF_SPAN_BYTES = 1 << 20
# joined leaves start on multiples of this in the staging area
_STAGE_ALIGN = 64
# A copy of the state is held beside the step (`_room_to_hold`) only if
# this share of every device's memory stays free of both: the allocator
# needs slack, and the step's peak is one reading, not a bound.
_HOLD_MARGIN = 1 / 16
# how long an epoch's end waits for the pull of the last held copy
_HOLD_WAIT_S = 600.0


class TrainingOperator:
    """Subclass and implement setup(); call self.register(...) there."""

    def __init__(self, config: dict, world_rank: int, world_size: int,
                 group_name: str | None = None):
        self.config = config or {}
        self.world_rank = world_rank
        self.world_size = world_size
        self._group_name = group_name
        self._registered = False
        self._train_loader = None
        self._val_loader = None
        self.epoch = 0
        self.global_step = 0
        # `train.setup.user`: setup()'s own seconds, as the stretches of
        # it that are not register()'s (which closes one and opens the
        # next: `_user_span`)
        self._user_since = time.time()
        self.setup(self.config)
        self._user_span()
        if not self._registered:
            raise RuntimeError(
                "TrainingOperator.setup() must call self.register(...)")

    def _user_span(self) -> float:
        """Record the user's stretch of `setup` that ends now; returns
        now, on the spans' clock."""
        now = time.time()
        _tracing.record_span("train.setup.user", self._user_since, now,
                             _tracing.child_of_current())
        return now

    # ------------------------------------------------------------------
    # user surface
    # ------------------------------------------------------------------

    def setup(self, config: dict):
        raise NotImplementedError

    def register(self, *, model_init: Callable[[jax.Array], Any],
                 loss_fn: Callable[..., jax.Array],
                 optimizer, seed: int = 0, stateful: bool = False,
                 eval_fn: Callable[..., dict] | None = None,
                 mesh=None, param_spec=None, batch_spec=None):
        """Register the functional model.

        stateful=False: model_init(rng) -> params;
            loss_fn(params, batch) -> scalar loss.
        stateful=True (models with mutable state, e.g. batchnorm):
            loss_fn(params, state, batch) -> (loss, new_state).
        optimizer: optax GradientTransformation.
        eval_fn(params[, state], batch) -> metrics dict (defaults to
            loss_fn in eval position).
            A state dict with the key "epoch_counters" — a flat dict
            of SCALAR arrays the loss_fn updates every step on the
            device (adds to, takes a maximum into, sets: tokens an
            expert got, ...) — gets them zeroed when an epoch starts and
            read ONCE, in `train.sync` with the losses: no sync inside
            the epoch. Each goes on the `train.sync` span under its key
            and into the epoch's result ("counters").
            A `loss_fn` with an attribute `step_facts` — a function of
            one batch that returns a flat dict of numbers known without
            running the step (the layers of a kind, the chunks a scan
            walks) — gets them on every `train.dispatch` span, reckoned
            from the epoch's last batch.

        mesh: a jax Mesh (possibly GLOBAL, spanning worker processes via
            parallel.multihost) — the step runs SPMD over it and gradient
            combination is XLA's psum over the batch axes, NOT the HOST
            collective backend. param_spec: PartitionSpec or pytree of
            them for the params (default replicated); batch_spec:
            PartitionSpec for batches (default P('dp'): rows over the
            data axis).
        """
        since = self._user_span()
        self._registered = True
        self._facts = None      # _layout_facts, reckoned at the first epoch
        self._loading = None    # a state arriving in pieces (_LoadPlan)
        self._stage = None      # the staging area (_staging), once needed
        self._joiners = None    # the join's threads (_join_pool), likewise
        self._join_width = 0    # ... and how many they are
        self._room = None       # the room rule's answer (_room_to_hold)
        self._usable = None     # the budget the state was last cut by
        self._held_from = {}    # ... and where the held part begins
        self._held = None       # the copy taken at the last epoch's end
        self._pull_open = False     # ... is being pulled (state_piece)
        self._held_cv = threading.Condition()
        self._loss_fn = loss_fn
        self._eval_fn = eval_fn
        self._optimizer = optimizer
        self._stateful = stateful
        # `train.setup.init` and `train.setup.place` each end when the
        # device has done what they dispatched: the two waits move the
        # rest of initialisation from the first step, which waited for
        # the same arrays, into set-up, where it has a name
        # (`wait_s`: how long each wait took, so how much was moved)
        made = {}
        with _tracing.span("train.setup.init", _tracing.child_of_current(),
                           made, start=since):
            if stateful:
                self.params, self.model_state = model_init(
                    jax.random.key(seed))
            else:
                self.params = model_init(jax.random.key(seed))
                self.model_state = None
            made.update(_waited_for((self.params, self.model_state)),
                        bytes=_shard.opt_nbytes(
                            (self.params, self.model_state)))
        placed = {}
        with _tracing.span("train.setup.place", _tracing.child_of_current(),
                           placed):
            self._place(optimizer, mesh, param_spec, batch_spec)
            state = (self.params, self.model_state, self.opt_state)
            placed.update(_waited_for(state),
                          state_bytes=_shard.opt_nbytes(state))
        self._user_since = time.time()

    def _place(self, optimizer, mesh, param_spec, batch_spec):
        """register()'s second half: the state onto its devices (a mesh,
        asked for or derived from the lease), the optimizer's state made
        beside it, the jitted steps built."""
        self._epoch_counters = (isinstance(self.model_state, dict)
                                and "epoch_counters" in self.model_state)
        if self._epoch_counters and any(
                jnp.ndim(x) for x in
                self.model_state["epoch_counters"].values()):
            raise ValueError("epoch_counters is a flat dict of scalars")
        chips = _leased_chips()
        if mesh is None and (self.config.get("mesh_mode") == "fsdp" or (
                chips > 1 and not self.config.get("sharded_update")
                and (self.world_size == 1 or self.config.get("multihost")))):
            # FSDP mesh mode, asked for or DERIVED FROM THE LEASE: one
            # worker that was granted several chips trains on all of
            # them. The ('data','fsdp') mesh spans the chips this worker
            # holds (a multihost group: the group's), params — so every
            # optimizer buffer — split over the fsdp axis, the batch
            # over both. The fused step stays ONE jit:
            # with_sharding_constraint pins the updated params so XLA
            # keeps every optimizer buffer on its shard.
            from jax.sharding import PartitionSpec as P

            from ray_tpu.parallel import mesh as _meshlib

            if self.config.get("multihost") or chips <= 1:
                devices = jax.devices()
            else:   # no more than leased; no more than there are
                devices = jax.local_devices()[:chips]
            mesh = _meshlib.fsdp_mesh(devices)
            if param_spec is None:
                param_spec = _meshlib.fsdp_param_specs(self.params, mesh)
            if batch_spec is None:
                batch_spec = P(("data", "fsdp"))
        self._mesh = mesh
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            def to_sharding(spec):
                return NamedSharding(mesh, spec if spec is not None else P())

            if param_spec is None or isinstance(param_spec, P):
                p_shard = to_sharding(param_spec)
                self.params = jax.device_put(self.params, p_shard)
            else:  # pytree of PartitionSpecs matching params
                self.params = jax.tree.map(
                    lambda p, s: jax.device_put(p, to_sharding(s)),
                    self.params, param_spec,
                    is_leaf=lambda x: isinstance(x, P))
            if self.model_state is not None:
                self.model_state = jax.device_put(self.model_state,
                                                  to_sharding(None))
            self._batch_sharding = to_sharding(
                batch_spec if batch_spec is not None else P("dp"))
            self._param_shardings = jax.tree.map(
                lambda p: p.sharding, self.params)
        else:
            self._param_shardings = None
        # sharded stays on at world_size == 1 (collectives degenerate to
        # identity) so an elastic resize N→1→N keeps ONE state layout —
        # optimizer shards merge/split instead of changing format.
        self._sharded = (bool(self.config.get("sharded_update"))
                         and mesh is None)
        _, self._unravel = ravel_pytree(self.params)
        if self._sharded:
            self._init_sharded_state()
        else:
            # After placement: optax init inherits the params' shardings
            # (zeros_like preserves sharding), so optimizer state is laid
            # out like the params without extra plumbing.
            self.opt_state = optimizer.init(self.params)
            if mesh is not None:
                # ...except the scalars optax makes itself (adam's
                # count): on the default device, so whole on the mesh
                self.opt_state = jax.tree.map(
                    lambda x: x if isinstance(x.sharding, NamedSharding)
                    else jax.device_put(x, to_sharding(None)),
                    self.opt_state)
        from ray_tpu.train import metrics as _tm

        _tm.OPT_SHARD_BYTES.set(_shard.opt_nbytes(self.opt_state))
        self._build_steps()

    def _init_sharded_state(self):
        """ZeRO weight-update sharding (arXiv:2004.13336): this rank
        keeps the FULL params (needed for the forward) but only 1/N of
        the optimizer state — optax initialized on the rank's uniform
        span of the padded flat param bucket (layout: train/sharding.py).
        The step becomes reducescatter(grads) → local shard update →
        allgather(params)."""
        flat, _ = ravel_pytree(self.params)
        self._numel = int(flat.size)
        self._pad_numel = _shard.padded_numel(self._numel, self.world_size)
        self._shard_lo, self._shard_hi = _shard.shard_span(
            self._numel, self.world_size, self.world_rank)
        self._param_shard = jnp.pad(
            flat, (0, self._pad_numel - self._numel)
        )[self._shard_lo:self._shard_hi]
        self.opt_state = self._optimizer.init(self._param_shard)
        self._opt_treedef = jax.tree.structure(self.opt_state)

    def register_data(self, *, train_loader: Iterable | None = None,
                      validation_loader: Iterable | None = None):
        self._train_loader = train_loader
        self._val_loader = validation_loader

    # ------------------------------------------------------------------
    # jitted steps
    # ------------------------------------------------------------------

    def _build_steps(self):
        loss_fn, optimizer = self._loss_fn, self._optimizer
        unravel = self._unravel
        stateful = self._stateful
        shardings = self._param_shardings

        def pin(params):
            # FSDP/mesh mode: constrain the UPDATED params back onto
            # their named shardings so the whole fused step — grads,
            # optimizer buffers, update — stays sharded inside one jit
            # instead of XLA replicating intermediates.
            return (params if shardings is None
                    else jax.lax.with_sharding_constraint(params, shardings))

        def traced_on_mesh(fn):
            # mesh mode: tell the Pallas kernels in the model how THIS
            # operator shards its batches, so each runs per device on
            # its rows (ops/partition.py) — the SPMD partitioner cannot
            # split a Mosaic call itself
            if self._mesh is None:
                return fn
            from ray_tpu.ops import partition as _partition

            def on_mesh(*args):
                with _partition.batch_sharded(self._mesh,
                                              self._batch_sharding.spec):
                    return fn(*args)

            return on_mesh
        # mesh mode: the step hands its state back laid out EXACTLY as
        # it took it (the same sharding objects), so step N+1 dispatches
        # step N's program; left to XLA, an equivalent layout spelled
        # another way costs a second lowering and compile
        self._fused_out = None
        if self._mesh is not None:
            def layout(tree):
                return jax.tree.map(lambda x: x.sharding, tree)

            self._fused_out = (
                layout(self.params), layout(self.model_state),
                layout(self.opt_state),
                jax.sharding.NamedSharding(self._mesh,
                                           jax.sharding.PartitionSpec()))
        # The step's two halves. The scopes are names in the device
        # trace ("forward_backward", "optimizer"), nothing else.
        def update(grads, opt_state, params):
            with jax.named_scope("optimizer"):
                updates, opt_state = optimizer.update(grads, opt_state,
                                                      params)
                return (jax.tree.map(lambda p, u: p + u, params, updates),
                        opt_state)

        if stateful:
            def value_and_grads(params, mstate, batch):
                with jax.named_scope("forward_backward"):
                    (loss, mstate), grads = jax.value_and_grad(
                        loss_fn, has_aux=True)(params, mstate, batch)
                return loss, mstate, grads
        else:
            def value_and_grads(params, mstate, batch):
                with jax.named_scope("forward_backward"):
                    loss, grads = jax.value_and_grad(loss_fn)(params, batch)
                return loss, mstate, grads

        # Fused path (single worker): grads + update in one jit, buffers
        # donated so XLA updates params/opt_state in place; loss stays on
        # device — the epoch loop issues pure async dispatches.
        def fused(params, mstate, opt_state, batch):
            loss, mstate, grads = value_and_grads(params, mstate, batch)
            params, opt_state = update(grads, opt_state, params)
            return pin(params), mstate, opt_state, loss

        def grad_step(params, mstate, batch):
            loss, mstate, grads = value_and_grads(params, mstate, batch)
            return loss, mstate, ravel_pytree(grads)[0]

        self._fused_donate = (0, 1, 2) if stateful else (0, 2)
        self._fused_step = jax.jit(traced_on_mesh(fused),
                                   donate_argnums=self._fused_donate,
                                   out_shardings=self._fused_out)
        self._grad_step = jax.jit(grad_step)

        def apply_step(params, opt_state, flat_grads):
            return update(unravel(flat_grads), opt_state, params)

        self._apply_step = jax.jit(apply_step, donate_argnums=(0, 1))

        # The held copy (`_hold`): the whole state once more on the
        # devices, laid out as it is; nothing donated. A part of it
        # goes through the same function as (leaves, None, None).
        def copy_state(params, mstate, opt_state):
            return jax.tree.map(jnp.copy, (params, mstate, opt_state))

        self._copy_fn = copy_state
        self._copy_out = self._fused_out and self._fused_out[:3]
        self._copy_state = jax.jit(copy_state, out_shardings=self._copy_out)
        if self._sharded:
            ws = self.world_size
            pad = self._pad_numel - self._numel

            # The ZeRO step's local half: average the reduce-scattered
            # grad shard, update THIS rank's 1/N of (params, opt state).
            # Elementwise over the flat bucket, so it is bitwise the
            # same arithmetic the replicated apply_step would do on
            # these elements — the bit-exactness bar rests on this.
            def shard_apply(pshard, opt_state, gshard):
                g = gshard / ws
                updates, opt_state = optimizer.update(g, opt_state, pshard)
                return pshard + updates, opt_state

            self._shard_apply = jax.jit(shard_apply, donate_argnums=(0, 1))
            self._pad_grads = jax.jit(lambda g: jnp.pad(g, (0, pad)))
        # one CompileProbe per (step name, batch shape class): the first
        # dispatch of a NEW shape class traces, lowers and compiles (or
        # loads from JAX's persistent cache) — recorded once
        # (jax.compiles_total / jax.compile_s / a `jax.compile` span
        # with jax's own timings), so a shape-churning loader reads as a
        # recompile storm, not a mystery slowdown
        self._step_cache = {}

        if self._eval_fn is not None:
            eval_fn = self._eval_fn
        elif stateful:
            def eval_fn(params, mstate, batch):
                return {"val_loss": loss_fn(params, mstate, batch)[0]}
        else:
            def eval_fn(params, batch):
                return {"val_loss": loss_fn(params, batch)}
        self._jit_eval = jax.jit(traced_on_mesh(eval_fn))

    def _allreduce_grads(self, flat_grads: jax.Array):
        from ray_tpu.collective import collective as col

        # the gradient bucket stays a device array: a device-capable
        # group (Transport.DEVICE) reduces it over ICI with zero host
        # copies; host groups convert internally. The group's quantize
        # default (Trainer(quantize="int8")) applies to the wire here.
        avg = col.allreduce(flat_grads, group_name=self._group_name)
        return avg / self.world_size

    def _reducescatter_grads(self, flat_grads: jax.Array):
        """Sharded step, wire half 1: pad the flat grad bucket to the
        shard layout and reduce-scatter it — each rank receives only the
        summed span it will update, (w-1)/w * bucket bytes on the wire
        instead of ~2x bucket for allreduce. The group's quantize
        default (Trainer(quantize="int8")) drops it ~4x further."""
        from ray_tpu._private import failpoints as _fp
        from ray_tpu.collective import collective as col

        if _fp.ARMED:
            _fp.fire_strict("train.reducescatter")
        padded = self._pad_grads(flat_grads)
        if self.world_size == 1:
            return padded  # whole (padded) bucket IS the rank's span
        return col.reducescatter(padded, group_name=self._group_name)

    def _allgather_params(self):
        """Sharded step, wire half 2: every rank contributes its updated
        param shard; concatenation (uniform spans, rank order) rebuilds
        the padded flat bucket, trimmed + unraveled into self.params.
        The gather relays exact bytes, so params stay bit-identical
        across ranks even under a quantized (lossy) grad wire."""
        if self.world_size == 1:
            self.params = self._unravel(self._param_shard[:self._numel])
            return
        from ray_tpu.collective import collective as col

        shards = col.allgather(np.asarray(self._param_shard),
                               group_name=self._group_name)
        flat = np.concatenate(shards)[:self._numel]
        self.params = self._unravel(jnp.asarray(flat))

    # ------------------------------------------------------------------
    # train/validate loops (reference: training_operator.py:437 train_epoch)
    # ------------------------------------------------------------------

    def train_batch(self, batch) -> dict:
        """Sync path for step-at-a-time callers; returns a host float."""
        loss = self._dispatch_batch(batch)
        self.global_step += 1
        return {"train_loss": float(loss)}

    def _place_batch(self, batch):
        """Mesh path: lift a host-local batch onto the (global) mesh —
        each process contributes its local rows; XLA's compiled
        collectives combine across processes."""
        if jax.process_count() > 1:
            from ray_tpu.parallel import multihost

            return multihost.shard_host_batch(batch, self._batch_sharding)
        return jax.device_put(batch, self._batch_sharding)

    def _cached_step(self, name: str, shape_key: str, jitted, donate=()):
        """The per-(step, shape-class) CompileProbe around `jitted`:
        its first dispatch is recorded as
        `train.step:<name>:<shape_key>`."""
        key = (name, shape_key)
        fn = self._step_cache.get(key)
        if fn is None:
            fn = self._step_cache[key] = _profiling.CompileProbe(
                f"train.step:{name}:{shape_key}", jitted,
                donate_argnums=donate)
        return fn

    def compiled_step_text(self, batch) -> str:
        """Optimised HLO of the fused step as dispatched for `batch`'s
        shape class (single-worker and mesh groups: the paths with ONE
        jit per step). Runs nothing and donates nothing."""
        name, shape_key = "fused", _profiling.shape_class(batch)
        if self._mesh is not None:
            name, batch = "fused-mesh", self._place_batch(batch)
        step = self._cached_step(name, shape_key, self._fused_step,
                                 self._fused_donate)
        return step.compiled_text(self.params, self.model_state,
                                  self.opt_state, batch)

    def _dispatch_batch(self, batch):
        """Run one step, returning the (possibly device-resident) loss."""
        shape_key = _profiling.shape_class(batch)
        if self._mesh is not None:
            # SPMD over the (global) mesh — no HOST allreduce.
            batch = self._place_batch(batch)
            step = self._cached_step("fused-mesh", shape_key,
                                     self._fused_step, self._fused_donate)
            self.params, self.model_state, self.opt_state, loss = step(
                self.params, self.model_state, self.opt_state, batch)
            return loss
        if self.world_size == 1 and not self._sharded:
            step = self._cached_step("fused", shape_key,
                                     self._fused_step, self._fused_donate)
            self.params, self.model_state, self.opt_state, loss = step(
                self.params, self.model_state, self.opt_state, batch)
            return loss
        grad = self._cached_step("grad", shape_key, self._grad_step)
        loss, self.model_state, flat_grads = grad(
            self.params, self.model_state, batch)
        if self._sharded:
            # ZeRO schedule: reducescatter(grads) -> update local 1/N
            # shard of (params, opt state) -> allgather(params).
            gshard = self._reducescatter_grads(flat_grads)
            apply = self._cached_step("shard-apply", "flat",
                                      self._shard_apply, (0, 1))
            self._param_shard, self.opt_state = apply(
                self._param_shard, self.opt_state, jnp.asarray(gshard))
            self._allgather_params()
            return loss
        flat_grads = self._allreduce_grads(flat_grads)
        apply = self._cached_step("apply", "flat", self._apply_step,
                                  (0, 1))
        self.params, self.opt_state = apply(
            self.params, self.opt_state, flat_grads)
        return loss

    def _layout_facts(self) -> dict:
        """What `train.dispatch` says of where the state lives: the
        devices the step runs on (`chips`), the mesh's shape (mesh mode
        only), the bytes of parameters, model and optimizer state as a
        whole (`state_bytes`) and the addressable shard bytes of them on
        the fullest device (`state_bytes_fullest_chip`), and the bytes
        of leaves of two or more dimensions that are split along their
        LEADING one (`state_bytes_split_leading`: the axis a layer scan
        walks, so the step gathers such a stack whole; 0 under
        `fsdp_param_specs` when a later dimension divides). Layouts are
        pinned at register(), so this is reckoned once."""
        facts = self._facts
        if facts is None:
            held: dict = {}
            whole = split_leading = 0
            for x in jax.tree.leaves((self.params, self.model_state,
                                      self.opt_state)):
                if not isinstance(x, jax.Array):
                    continue
                whole += x.nbytes
                shard_shape = x.sharding.shard_shape(x.shape)
                shard = _shard_bytes(x)
                for d in x.sharding.addressable_devices:
                    held[d] = held.get(d, 0) + shard
                if x.ndim >= 2 and shard_shape[0] < x.shape[0]:
                    split_leading += x.nbytes
            facts = self._facts = {
                "chips": max(len(held), 1), "state_bytes": whole,
                "state_bytes_fullest_chip": max(held.values(), default=0),
                "state_bytes_split_leading": split_leading}
            if self._mesh is not None:
                facts["mesh"] = [int(n) for n in self._mesh.shape.values()]
        return facts

    def snapshot_bytes(self) -> dict:
        """What one snapshot of this worker's state takes on the host:
        `state_bytes` as `_layout_facts` has them, how many of them are
        this rank's optimizer shard (`opt_shard_bytes`: the sharded
        schedule, where the driver keeps every rank's; else 0) and the
        number of `leaves` they come in. `TrainWorker.setup_operator`
        replies with it, so the driver can reserve its buffer sets
        before the first state arrives."""
        state = (self.params, self.model_state, self.opt_state)
        return {"state_bytes": self._layout_facts()["state_bytes"],
                "opt_shard_bytes": (_shard.opt_nbytes(self.opt_state)
                                    if self._sharded else 0),
                "leaves": len(jax.tree.leaves(state))}

    def start_profile(self, profile_dir: str) -> bool:
        """Start a jax profiler session in this process; while it runs,
        the tracing spans recorded here are also host annotations in
        the trace (`_private/tracing.py`)."""
        jax.profiler.start_trace(profile_dir)
        _tracing.set_annotating(True)
        return True

    def stop_profile(self) -> bool:
        """Stop the session `start_profile` began (none running: False)."""
        if not _tracing.annotating():
            return False
        _tracing.set_annotating(False)
        jax.profiler.stop_trace()
        return True

    def train_epoch(self, num_steps: int | None = None,
                    profile_dir: str | None = None) -> dict:
        if self._train_loader is None:
            raise RuntimeError("no train_loader registered")
        from ray_tpu.train import metrics as _tm

        if profile_dir:   # a direct caller; Trainer.train brackets the call
            self.start_profile(profile_dir)
        try:
            losses, samples = [], 0
            step = 0
            counts = {}
            # `train.dispatch`: loop entry (the first batch's fetch) to
            # the last dispatch returned; the device works from its
            # first dispatch until `train.sync`'s drain returns
            with _tracing.span("train.dispatch",
                               _tracing.child_of_current(), counts):
                t_step = t0 = time.perf_counter()
                if self._epoch_counters:
                    self.model_state = {
                        **self.model_state, "epoch_counters": jax.tree.map(
                            jnp.zeros_like,
                            self.model_state["epoch_counters"])}
                for batch in self._train_loader:
                    losses.append(self._dispatch_batch(batch))
                    self.global_step += 1
                    bs = _batch_size(batch)
                    samples += bs
                    if bs:
                        _tm.SAMPLES_TOTAL.inc(bs)
                    now = time.perf_counter()
                    # with ingest_wait_s (observed inside IngestStream's
                    # get) this answers "is training input-bound?"
                    _tm.STEP_DISPATCH_S.observe(now - t_step)
                    if not step:    # where jax's deferred garbage lands
                        counts["first_dispatch_s"] = now - t_step
                    t_step = now
                    step += 1
                    if num_steps is not None and step >= num_steps:
                        break
                counts.update(steps=step, samples=samples,
                              **self._layout_facts())
                facts = getattr(self._loss_fn, "step_facts", None)
                if facts is not None and step:
                    counts.update(facts(batch))
            # One sync for the whole epoch: the loop was async dispatch.
            counters = {}
            with _tracing.span("train.sync", _tracing.child_of_current(),
                               counters):
                losses = [float(x) for x in losses]
                dt = time.perf_counter() - t0
                if self._epoch_counters:
                    counters.update(
                        (k, v.item()) for k, v in jax.device_get(
                            self.model_state["epoch_counters"]).items())
        finally:
            if profile_dir:
                self.stop_profile()
        self.epoch += 1
        held = self._hold()
        out = {
            "epoch": self.epoch,
            "batch_count": len(losses),
            "num_samples": samples,
            "train_loss": float(np.mean(losses)) if losses else float("nan"),
            "last_train_loss": losses[-1] if losses else float("nan"),
            "samples_per_s": samples / dt if dt > 0 else 0.0,
        }
        if self._epoch_counters:
            out["counters"] = counters
        if held:    # ... and from which leaf of the state on (0: whole)
            out["held_epoch"] = self.epoch
            out["held_from"] = self._held["first"]
        return out

    # ------------------------------------------------------------------
    # the held copy: the state pulled beside the NEXT epoch
    # ------------------------------------------------------------------

    def _device_memory(self) -> list:
        """`memory_stats()` of every device the state lives on (None
        where the backend keeps no count: the CPU)."""
        devices = {d for x in jax.tree.leaves(
            (self.params, self.model_state, self.opt_state))
            if isinstance(x, jax.Array)
            for d in x.sharding.addressable_devices}
        return [d.memory_stats() for d in devices]

    def _room_to_hold(self) -> int:
        """THE RULE, read once, after the first epoch: the bytes of a
        second copy of the state that fit on every device the state
        lives on, beside the most the device has held so far — the
        step's peak: live buffers, or what is live now plus the
        runtime's reservation for programs' temporaries, whichever is
        more — with `_HOLD_MARGIN` of its memory to spare; the least
        over the devices. Nothing else decides it: no name, no size
        chosen for a cell, no option. A backend that keeps no count
        (the CPU) has no room; neither has an operator that does not
        own its whole state (a host-collective group's rank)."""
        if self.world_size != 1 or self._sharded:
            return 0
        room = None
        for s in self._device_memory():
            if (not s or s.get("bytes_limit") is None
                    or s.get("peak_bytes_in_use") is None):
                return 0
            peak = max(s["peak_bytes_in_use"], s.get("bytes_in_use", 0)
                       + s.get("bytes_reserved", 0))
            free = int(s["bytes_limit"] - peak
                       - _HOLD_MARGIN * s["bytes_limit"])
            room = free if room is None else min(room, free)
        return max(room or 0, 0)

    def _held_part(self) -> tuple | None:
        """What of the state is held in that room: (its first leaf in
        tree order, its bytes), or None where nothing is. The whole
        (leaf 0) where the state's bytes on the fullest device fit;
        else the run of WHOLE pieces at the tail of the cut the state
        last crossed by (`snapshot.plan` under `state_piece`'s
        `usable`) whose bytes on the fullest device fit: the driver
        pulls those beside the next epoch and only the pieces before
        them at once. Before the state has crossed once nobody knows
        the cut, and a part is not held: a Trainer's first call pulls
        everything at once anyway."""
        room = self._room
        if not room:
            return None
        facts = self._layout_facts()
        if room >= facts["state_bytes_fullest_chip"]:
            return 0, facts["state_bytes"]
        usable = self._usable
        if usable is None:
            return None
        if usable not in self._held_from:
            leaves = jax.tree.leaves(self._state_tree())
            sizes = [_snapshot.leaf_bytes(x) for x in leaves]
            on_chip = [_shard_bytes(x) for x in leaves]
            first, fit = len(leaves), 0
            for a, b in reversed(_snapshot.plan(sizes, usable)):
                if fit + sum(on_chip[a:b]) > room:
                    break
                first, fit = a, fit + sum(on_chip[a:b])
            self._held_from[usable] = (
                (first, sum(sizes[first:])) if first < len(leaves) else None)
        return self._held_from[usable]

    @property
    def holds_state(self) -> bool:
        """A copy of the state (or of its tail), as an epoch left it,
        is on the devices."""
        return self._held is not None

    def _hold(self) -> bool:
        """At an epoch's end, where the devices have the room
        (`_room_to_hold`, `_held_part`): one jitted copy of the state,
        or of the pieces at its tail that fit, beside the live one,
        which `state_piece(of_epoch=)` reads while the NEXT epoch's
        steps donate and overwrite the live buffers. The copy is
        dispatched, not waited for: the device runs it before the next
        step. A copy that was pulled went when its last piece was read
        (`end_pull`); one that still is being pulled (the pull may
        outlast its epoch) is waited for, one nobody asked for is let
        go first: never two copies. With room for no piece this is one
        attribute read and a comparison: no span, no program."""
        if self._room is None:
            self._room = self._room_to_hold()
        part = self._held_part()
        if part is None:
            return False
        first, held_bytes = part
        counts = {"epoch": self.epoch, "held_bytes": held_bytes,
                  "bytes": self._layout_facts()["state_bytes"]}
        with _tracing.span("train.hold", _tracing.child_of_current(),
                           counts):
            t0 = time.perf_counter()
            with self._held_cv:
                if not self._held_cv.wait_for(
                        lambda: not self._pull_open, _HOLD_WAIT_S):
                    raise RuntimeError(
                        "the pull of the held state of epoch "
                        f"{self._held['epoch']} has not ended after "
                        f"{_HOLD_WAIT_S:.0f} s (Trainer: end_pull)")
                self._held = None
            t1 = time.perf_counter()
            tree = self._state_tree()
            leaves, treedef = jax.tree.flatten(tree)
            if first:
                arrays = [x for x in leaves[first:]
                          if isinstance(x, jax.Array)]
                copied = iter(self._copy_program(first, arrays)(
                    arrays, None, None)[0])
                tail = [next(copied) if isinstance(x, jax.Array) else x
                        for x in leaves[first:]]
            else:
                params, mstate, opt_state = self._copy_program(0, ())(
                    self.params, self.model_state, self.opt_state)
                tail = jax.tree.leaves(dict(
                    tree, params=params, model_state=mstate,
                    opt_state=opt_state))
            # of the span: waiting for a pull to end (and letting an
            # unread copy go), dispatching the copy (the first: building)
            counts.update(wait_s=t1 - t0, copy_s=time.perf_counter() - t1)
            self._held = {
                "epoch": self.epoch, "first": first, "leaves": tail,
                "treedef": treedef,
                "sizes": [_snapshot.leaf_bytes(x) for x in leaves]}
        return True

    def _copy_program(self, first: int, arrays):
        """The copy of the held part that begins at leaf `first`: the
        whole state's (`first` 0) is one program over (params, model
        state, optimizer state), a tail's the same function over
        (`arrays`, None, None), each laid out as it lies."""
        if not first:
            return self._cached_step("hold", "state", self._copy_state)
        fn = self._step_cache.get(("hold", f"from{first}"))
        if fn is None:
            out = self._copy_out and ([x.sharding for x in arrays],
                                      None, None)
            fn = self._cached_step(
                "hold", f"from{first}",
                jax.jit(self._copy_fn, out_shardings=out))
        return fn

    def expect_pull(self, of_epoch: int) -> bool:
        """The copy held of `of_epoch` is about to be pulled (the driver
        says so with the epoch it submits before the first piece): the
        pull is open from now, so an epoch that ends before the first
        piece has even arrived keeps the copy for it."""
        with self._held_cv:
            if self._held is not None and self._held["epoch"] == of_epoch:
                self._pull_open = True
        return self._pull_open

    def end_pull(self) -> bool:
        """The pull of the held copy is over (its last piece closes it
        by itself; a driver whose pull failed half-way says so here):
        nobody reads the copy again, so it goes now — its device buffers
        and the host copies jax keeps of arrays it has converted, off
        the epoch's thread, which finds little to free at its end (the
        last piece's leaves, still on their way into the store)."""
        with self._held_cv:
            self._held = None
            self._pull_open = False
            self._held_cv.notify_all()
        # jax frees what its own threads let go of (the host buffers of
        # finished transfers) in the NEXT jitted call of the process,
        # whichever thread makes it: 68 ms for GPT-2 small's 1.4 GB
        # inside the epoch's own `_hold`, read in a kept profile. Here
        # it is the pull's thread that pays.
        from jax._src.lib import xla_client

        xla_client._xla.collect_garbage()
        return True

    def validate(self, num_steps: int | None = None) -> dict:
        if self._val_loader is None:
            raise RuntimeError("no validation_loader registered")
        all_metrics: list[dict] = []
        samples = 0
        for step, batch in enumerate(self._val_loader):
            if self._mesh is not None:
                batch = self._place_batch(batch)
            evaluate = self._cached_step(
                "eval", _profiling.shape_class(batch), self._jit_eval)
            m = (evaluate(self.params, self.model_state, batch)
                 if self._stateful else evaluate(self.params, batch))
            all_metrics.append({k: float(v) for k, v in m.items()})
            samples += _batch_size(batch)
            if num_steps is not None and step + 1 >= num_steps:
                break
        out = {k: float(np.mean([m[k] for m in all_metrics]))
               for k in (all_metrics[0] if all_metrics else {})}
        out["num_samples"] = samples
        return out

    # ------------------------------------------------------------------
    # checkpointing (reference: torch_trainer.py:543 save / :552 load)
    # ------------------------------------------------------------------

    def _to_host(self, tree, counts: dict, ctx, room: int = 0, ahead=()):
        """`tree` with its arrays on the host; adds to `counts`
        (`bytes`, `leaves`, `staged_bytes`, `shards`, `join_threads`,
        and the WALL seconds of the caller: `start_s` issuing transfers,
        `wait_s` waiting for a device array to arrive — the link — and
        `join_s` in which a shard was being written into the staging
        area). Every leaf's
        transfer is started before the first is waited for, and behind
        them those of the arrays in `ahead`, which are NOT waited for:
        the link takes transfers in the order issued. With `room`
        (bytes, `_stage_room`) a leaf that has to be joined from shards
        is written into the staging area — pages this operator has
        written before, where `np.asarray` joins into freshly mapped
        ones — each shard waited for and written by a thread of its own
        (`_join_pool`), beside one another, and comes back as a VIEW of
        it, good until the next call with `room`: every shard's write
        has ended, or its exception is raised here, before the next
        leaf is looked at. Of such a leaf's wall seconds `join_s` takes
        those in which at least one shard was being written (the union
        of the writes) and `wait_s` the rest, every thread still waiting
        for its shard; `join_threads` is the most shards one leaf had
        written off this thread. Leaves with nothing to
        join go through `np.asarray` either way. At a trace's fine level
        every leaf above 1 MiB gets a `train.snapshot.d2h.leaf` span."""
        leaves, treedef = jax.tree.flatten(tree)
        _start_transfers(leaves + list(ahead), counts)
        at = 0
        clock = time.perf_counter

        def to_np(x):
            nonlocal at
            if not isinstance(x, (jnp.ndarray, np.ndarray)):
                return x
            # Cross-process (multihost) shards aren't addressable locally:
            # gather them before converting (replicated arrays pass
            # np.asarray directly).
            if (isinstance(x, jax.Array) and not x.is_fully_addressable
                    and not x.is_fully_replicated):
                from jax.experimental import multihost_utils

                x = multihost_utils.process_allgather(x)
            counts["bytes"] += x.nbytes
            counts["leaves"] += 1
            t0 = clock()
            if not (room and _is_joined(x)):
                out = np.asarray(x)
                counts["wait_s"] += clock() - t0
                return out
            out = self._staging(room)[at:at + x.nbytes].view(
                x.dtype).reshape(x.shape)
            at += _padded(x.nbytes)
            shards = [s for s in x.addressable_shards
                      if s.replica_id == 0]     # each index once
            pool = self._join_pool(len(shards))
            writes = [pool.submit(_write_shard, out, s) for s in shards]
            wait(writes)        # all of them: none writes after a raise
            joined = _union_s([w.result() for w in writes])
            counts["join_s"] += joined
            counts["wait_s"] += clock() - t0 - joined
            counts["shards"] += len(shards)
            counts["join_threads"] = max(counts["join_threads"], len(shards))
            counts["staged_bytes"] += x.nbytes
            return out

        def traced(x):
            if (ctx is None or not ctx.fine
                    or getattr(x, "nbytes", 0) < _LEAF_SPAN_BYTES):
                return to_np(x)
            with _tracing.span(
                    "train.snapshot.d2h.leaf", _tracing.child(ctx),
                    {"bytes": x.nbytes, "dtype": str(x.dtype),
                     "shape": list(x.shape)}):
                return to_np(x)

        return jax.tree.unflatten(treedef, [traced(x) for x in leaves])

    def _staging(self, room: int) -> np.ndarray:
        """The staging area: at least `room` bytes of host memory this
        operator keeps, piece after piece and call after call, so the
        pages a join writes are resident from the second use on. The
        join's threads (`_join_pool`) write into it, each its own
        shard's slice; between two calls of `_to_host` nobody does."""
        if self._stage is None or self._stage.nbytes < room:
            self._stage = np.empty(room, np.uint8)
        return self._stage

    def _join_pool(self, width: int) -> ThreadPoolExecutor:
        """The join's threads: as many as the widest leaf joined so far
        had shards, one to a shard. They are this operator's, kept
        beside the staging area from the first joined leaf on (a state
        has hundreds of leaves: not a start and a join each), idle
        between leaves, and never made where nothing is joined."""
        if width > self._join_width:
            if self._joiners is not None:
                self._joiners.shutdown()
            self._joiners = ThreadPoolExecutor(
                width, thread_name_prefix="train-join")
            self._join_width = width
        return self._joiners

    def _state_tree(self, drop=()) -> dict:
        """The training state with its arrays where they are (on the
        devices), less the top-level keys in `drop`."""
        out = {
            "params": self.params,
            "model_state": self.model_state,
            "epoch": self.epoch,
            "global_step": self.global_step,
        }
        if self._sharded:
            # no replicated opt blob exists in sharded mode — the
            # state carries THIS rank's shard (train/sharding.py
            # dict format)
            out["sharded_update"] = True
            if "opt_shard" not in drop:
                out["opt_shard"] = self._opt_shard_tree()
        else:
            out["opt_state"] = self.opt_state
        return {k: v for k, v in out.items() if k not in drop}

    def state_dict(self) -> dict:
        counts = _d2h_counts()
        ctx = _tracing.child_of_current()
        with _tracing.span("train.snapshot.d2h", ctx, counts):
            return self._to_host(self._state_tree(), counts, ctx)

    def state_piece(self, index: int, usable: int, drop=(),
                    of_epoch: int | None = None) -> dict:
        """Piece `index` of the state as `train/snapshot.py` cuts it for
        a store that holds `usable` bytes: only this piece's leaves are
        brought to the host (one `train.snapshot.d2h` span a piece).
        With `of_epoch`, of the copy HELD since that epoch's end
        (`_hold`) and not of the live state, which the next epoch may be
        stepping on meanwhile; the held copy stays until its last piece
        has been read (or `end_pull`), and a copy of another epoch is
        an error, never another epoch's bytes.
        Before this piece is waited for, the transfers of the TWO pieces
        after it are started behind its own: the link moves more with a
        second piece queued than with one alone, and they run under the
        caller's put of this piece.

        Leaves joined from shards are views of the operator's staging
        area, written there by the operator's own threads, a shard each
        (`_join_pool`), all of which have ended when this returns: they
        are good until the NEXT `state_piece` call and no
        longer. That is what the actor's lane gives: `TrainWorker` runs
        one `state_piece` at a time and the runtime has serialised and
        copied a reply into the store before it starts the next (an
        epoch that runs beside them, on the actor's other lane, touches
        neither the area nor the held copy). Who keeps what he gets
        calls `state_dict` (or copies)."""
        self._usable = usable   # the cut `_held_part` holds pieces of
        if of_epoch is not None:
            if drop:
                raise ValueError("a held copy is of the whole state")
            return self._held_piece(index, usable, of_epoch)
        held = self._held
        return self._piece(_snapshot.cut(self._state_tree(drop), usable),
                           index, held["first"] if held else 0)[0]

    def _piece(self, whole, index: int, held_from: int = 0) -> tuple:
        """Piece `index` of the cut `whole` (the live state's, or the
        held copy's), brought to the host; and whether it is the last.
        A piece that begins at leaf `held_from` or after (0: none is
        held apart) is read from the held copy, not from this one: its
        transfers are not started ahead."""
        def ahead(i):
            if i >= len(whole.ranges) or (
                    held_from and whole.ranges[i][0] >= held_from):
                return []
            return whole.part(i)

        counts = dict(_d2h_counts(), piece=index)
        ctx = _tracing.child_of_current()
        with _tracing.span("train.snapshot.d2h", ctx, counts):
            part = self._to_host(
                whole.part(index), counts, ctx, room=_stage_room(whole),
                ahead=ahead(index + 1) + ahead(index + 2))
        return whole.reply(index, part), index >= len(whole.ranges) - 1

    def _held_piece(self, index, usable, of_epoch) -> dict:
        """`state_piece` of the held copy: the same cut, the same
        transfers, the same span; the pull is open from its first piece
        to its last (or to one that raises), and `_hold` does not touch
        the copy meanwhile."""
        with self._held_cv:
            held = self._held
            if held is None or held["epoch"] != of_epoch:
                raise ValueError(
                    f"no state is held of epoch {of_epoch} (held: "
                    f"{held and held['epoch']})")
            self._pull_open = True
        last = True
        try:
            reply, last = self._piece(_held_cut(held, index, usable), index)
            return reply
        finally:
            if last:
                held = None     # the arrays die with the copy
                self.end_pull()

    def load_state_dict(self, state: dict):
        leaves, treedef = jax.tree.flatten(state)
        self.load_state_piece(0, leaves, treedef)

    def load_state_piece(self, first: int, leaves: list, treedef=None):
        """Install leaves [first, first + len(leaves)) of a state dict
        whose structure is `treedef` (given with leaf 0). Every array
        goes straight to where the registered layout keeps it — on a
        mesh each device gets its shard from the host array, nothing is
        made whole on one chip first — and takes the old leaf's place at
        once, so a restore never holds two states. True when whole."""
        if first == 0:
            if treedef is None:
                raise ValueError("leaf 0 of a state comes with its treedef")
            self._loading = _LoadPlan(self, treedef)
        plan = self._loading
        if plan is None or first != plan.next:
            raise ValueError(
                f"state piece starting at leaf {first} arrived out of "
                "order: pieces come in order, from leaf 0")
        plan.place(leaves)
        if plan.next < plan.total:
            return False
        self._loading = None
        plan.install()
        return True

    def opt_shard_state(self) -> dict:
        """This rank's optimizer-state shard in the train/sharding.py
        dict format (numpy leaves) — the unit of sharded checkpoints and
        elastic resharding."""
        counts = _d2h_counts()
        ctx = _tracing.child_of_current()
        with _tracing.span("train.snapshot.d2h", ctx, counts):
            return self._to_host(self._opt_shard_tree(), counts, ctx)

    def _opt_shard_tree(self) -> dict:
        return {"rank": self.world_rank, "world_size": self.world_size,
                "span": (self._shard_lo, self._shard_hi),
                "numel": self._numel, "pad_numel": self._pad_numel,
                "leaves": jax.tree.leaves(self.opt_state)}

    def load_opt_shard(self, shard: dict):
        """Install a shard produced by opt_shard_state (or
        sharding.reshard_opt_shards) — geometry must match this rank."""
        if (int(shard["world_size"]) != self.world_size
                or tuple(shard["span"]) != (self._shard_lo, self._shard_hi)
                or int(shard["numel"]) != self._numel):
            raise ValueError(
                f"optimizer shard geometry {shard['world_size']}x"
                f"{tuple(shard['span'])} (numel {shard['numel']}) does "
                f"not match rank {self.world_rank}: expected "
                f"{self.world_size}x({self._shard_lo}, {self._shard_hi}) "
                f"numel {self._numel}; reshard with "
                "train.sharding.reshard_opt_shards first")
        leaves = [jnp.asarray(x) if isinstance(x, np.ndarray) else x
                  for x in shard["leaves"]]
        self.opt_state = jax.tree.unflatten(self._opt_treedef, leaves)


def _d2h_counts() -> dict:
    """What a `train.snapshot.d2h` span counts: `staged_bytes` of
    `bytes` were joined from `shards` shards in the staging area, at
    most `join_threads` of them at once, each on a thread of the
    operator's (0: nothing was joined); the span's WALL seconds by what
    the worker's chain did in them (`_to_host`), never thread-seconds:
    `start_s` + `wait_s` + `join_s` stay inside the span."""
    return {"bytes": 0, "leaves": 0, "staged_bytes": 0, "shards": 0,
            "join_threads": 0, "start_s": 0.0, "wait_s": 0.0, "join_s": 0.0}


def _write_shard(out: np.ndarray, shard) -> tuple:
    """Wait for `shard` (the link) and write it into its slice of the
    joined leaf `out`; when the write started and ended. One of the
    join's threads runs this (`_join_pool`), and both halves leave the
    GIL: the wait inside jax, the write in numpy's raw copy — for the
    extension dtypes too (`bfloat16`: no `NPY_NEEDS_PYAPI` flag)."""
    arrived = np.asarray(shard.data)
    t0 = time.perf_counter()
    out[shard.index] = arrived
    return t0, time.perf_counter()


def _union_s(intervals: list) -> float:
    """Seconds covered by at least one of the (start, end) `intervals`."""
    covered, done = 0.0, float("-inf")
    for start, end in sorted(intervals):
        covered += max(0.0, end - max(start, done))
        done = max(done, end)
    return covered


def _shard_bytes(x) -> int:
    """The bytes of the leaf `x` on one of the devices that hold it."""
    if not isinstance(x, jax.Array):
        return 0
    return int(np.prod(x.sharding.shard_shape(x.shape))) * x.dtype.itemsize


def _is_joined(x) -> bool:
    """A leaf whose host copy is put together from several shards (one
    device, or every device holding it whole: nothing to join)."""
    return (isinstance(x, jax.Array) and x.is_fully_addressable
            and not x.is_fully_replicated)


def _start_transfers(leaves: list, counts: dict):
    """Start the device→host copy of every array in `leaves` (of a
    sharded one: of each of its shards) without waiting for any; the
    seconds that took are added to `counts["start_s"]`."""
    t0 = time.perf_counter()
    for x in leaves:
        if isinstance(x, jax.Array) and (x.is_fully_addressable
                                         or x.is_fully_replicated):
            x.copy_to_host_async()
    counts["start_s"] += time.perf_counter() - t0


def _padded(nbytes: int) -> int:
    return -(-nbytes // _STAGE_ALIGN) * _STAGE_ALIGN


def _held_cut(held: dict, index: int, usable: int):
    """The whole state's cut with the held copy's leaves in their places
    (`TrainingOperator._hold`: the leaves before `first` are not held,
    and a piece of them is an error, never the live state's bytes)."""
    first, sizes = held["first"], held["sizes"]
    ranges = _snapshot.plan(sizes, usable)
    if index < len(ranges) and ranges[index][0] < first:
        raise ValueError(
            f"piece {index} of the state of epoch {held['epoch']} is not "
            f"held: the copy begins at leaf {first}")
    return _snapshot.Cut([None] * first + held["leaves"], held["treedef"],
                         sizes, ranges)


def _stage_room(whole) -> int:
    """Bytes the staging area needs for any piece of `whole` (a
    `snapshot.Cut`): the joined leaves of its fullest piece, each on an
    aligned offset. 0 when no leaf has anything to join."""
    joined = [_padded(size) if _is_joined(x) else 0
              for x, size in zip(whole.leaves, whole.sizes)]
    return max(sum(joined[a:b]) for a, b in whole.ranges)


def _leased_chips() -> int:
    """TPU chips the runtime leased to the actor this process hosts; 0
    in a driver, a task worker or with no runtime at all."""
    from ray_tpu._private import global_state

    cw = global_state.get_core_worker()
    return int(getattr(cw, "actor_resources", {}).get("TPU", 0))


class _LoadPlan:
    """Where each leaf of an incoming state dict goes in an operator:
    (field, position among the field's own leaves), by tree order of the
    incoming `treedef`. Leaves of other keys (`sharded_update`, a
    geometry-matching `opt_shard`) are kept as they come."""

    ARRAYS = ("params", "model_state", "opt_state")

    def __init__(self, op: "TrainingOperator", treedef):
        self.op, self.treedef = op, treedef
        self.total, self.next = treedef.num_leaves, 0
        index = jax.tree.unflatten(treedef, range(self.total))
        if index.get("sharded_update") and not op._sharded:
            raise ValueError(
                "sharded checkpoint cannot load into an unsharded "
                "trainer; construct Trainer(sharded=True) or load "
                "the sharded manifest via Trainer.load()")
        if op._sharded and "opt_state" in index:
            raise ValueError(
                "replicated checkpoint (full opt_state) cannot load "
                "into a sharded-update trainer; re-save it sharded "
                "or construct Trainer(sharded=False)")
        self.where, self.fields, self.kept = {}, {}, {}
        for key in self.ARRAYS:
            if index.get(key) is None or (key == "opt_state"
                                          and op._sharded):
                continue
            own, own_def = jax.tree.flatten(getattr(op, key))
            at = jax.tree.leaves(index[key])
            if len(at) != len(own):
                raise ValueError(
                    f"the state's {key} has {len(at)} leaves, this "
                    f"operator's {len(own)}")
            self.fields[key] = (own, own_def)
            self.where.update((i, (key, pos)) for pos, i in enumerate(at))
        for key in self.fields:
            # the lists are the only holders now: a leaf's old buffers
            # go when the new leaf takes its place, so a restore never
            # holds two states on the devices
            setattr(op, key, None)

    def place(self, leaves: list):
        placed = []
        for i, x in enumerate(leaves, self.next):
            key, pos = self.where.get(i, (None, None))
            if key is None:
                # a view into the arena dies with the call's arguments
                self.kept[i] = (np.array(x) if isinstance(x, np.ndarray)
                                else x)
                continue
            own = self.fields[key][0]
            if isinstance(x, (np.ndarray, jax.Array)):
                old = own[pos]
                if isinstance(x, np.ndarray) and not x.flags.owndata \
                        and jax.default_backend() == "cpu":
                    # the CPU backend may alias a host buffer it is
                    # given; a view into the arena must not live on
                    x = np.array(x)
                # mesh mode: laid out as registered (the step's program
                # was built for exactly that layout)
                x = (jax.device_put(x, old.sharding)
                     if self.op._fused_out is not None
                     and isinstance(old, jax.Array) else jnp.asarray(x))
            own[pos] = x
            placed.append(x)
        self.next += len(leaves)
        # a transfer in flight holds its host source — a view into the
        # arena — so the piece is done only when its arrays are there
        jax.block_until_ready(placed)

    def install(self):
        op = self.op
        for key, (own, own_def) in self.fields.items():
            setattr(op, key, jax.tree.unflatten(own_def, own))
        rest = jax.tree.unflatten(self.treedef, [
            self.kept.get(i) for i in range(self.total)])
        if op._sharded:
            # rebuild the local param shard from the restored params;
            # the optimizer shard arrives separately (load_opt_shard,
            # possibly resharded) unless this state happens to carry a
            # geometry-matching shard (same-rank broadcast restore).
            flat, _ = ravel_pytree(op.params)
            op._param_shard = jnp.pad(
                flat, (0, op._pad_numel - op._numel)
            )[op._shard_lo:op._shard_hi]
            sh = rest.get("opt_shard")
            if (sh is not None and sh["world_size"] == op.world_size
                    and sh["rank"] == op.world_rank):
                op.load_opt_shard(sh)
        op.epoch = rest["epoch"]
        op.global_step = rest["global_step"]
        op._held = None     # a copy of the state this one replaced


def _waited_for(tree) -> dict:
    """Block until the device has made every array of `tree`; the
    seconds that took, as a span's `wait_s`."""
    t0 = time.perf_counter()
    jax.block_until_ready(tree)
    return {"wait_s": round(time.perf_counter() - t0, 4)}


def _batch_size(batch) -> int:
    leaves = jax.tree.leaves(batch)
    return int(leaves[0].shape[0]) if leaves and hasattr(
        leaves[0], "shape") and leaves[0].ndim else 0
