"""One worker that leases several chips shards by itself, and a training
state crosses the object plane in pieces the arena can hold
(train/operator.py `register`, train/snapshot.py, `Trainer._pull_state`
/ `_push_state`). CPU: four of the virtual devices stand for a v5e
host's four chips, the TPU resource is declared, the model is the tiny
GPT with seeded weights, and the arena is 8 MiB so that a 20 MB state is
several times what it holds."""

import statistics
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import ray_tpu
from benchmark import boundary_path, span_log
from ray_tpu._private import global_state
from ray_tpu.train import (Trainer, TrainingOperator, call_log,
                           start_log)
from ray_tpu.train import operator as operator_mod
from ray_tpu.train import snapshot
from ray_tpu.train import trainer as trainer_mod
from tests.conftest import scale_timeout

P = jax.sharding.PartitionSpec

ARENA = 8 << 20
TINY = {"batch": 8, "seq": 128, "seed": 3}


STACK = 12      # layers that divide by four chips, and no other extent


def _tiny_pieces(optimizer="adamw", layers=None):
    import dataclasses

    import optax

    from ray_tpu.models import transformer

    # TINY's two layers do not divide by four chips, so no rule splits
    # its stacks along them; `layers=STACK` makes stacks a rule could
    cfg = transformer.TINY
    if layers:
        cfg = dataclasses.replace(cfg, n_layers=layers)
    tokens = jax.random.randint(jax.random.key(TINY["seed"] + 1),
                                (TINY["batch"], TINY["seq"]), 0,
                                cfg.vocab_size)
    opt = optax.adamw(3e-4) if optimizer == "adamw" else optax.sgd(1.0)
    return (lambda key: transformer.init(key, cfg),
            lambda p, b: transformer.loss_fn(p, b, cfg), opt, tokens)


class TinyGPT(TrainingOperator):
    """The tiny GPT on one repeated seeded batch; no mesh argument."""

    def setup(self, config):
        init, loss_fn, opt, tokens = _tiny_pieces(
            config.get("optimizer", "adamw"), config.get("layers"))
        self.register(model_init=init, loss_fn=loss_fn, optimizer=opt,
                      seed=TINY["seed"])
        self.register_data(train_loader=[tokens] * 3)


class Wide(TrainingOperator):
    """Six (512, 512) weights, one (1024, 1024) and a bias under adam:
    10 MiB of parameters, 30 MiB of state — four arenas' worth. The
    4 MiB leaf is a piece of its own, and no two such pieces fit the
    6.4 MiB the store holds: the next is asked when the last is let go."""

    def setup(self, config):
        import optax

        def model_init(rng):
            keys = jax.random.split(rng, 7)
            p = {f"w{i}": jax.random.normal(keys[i], (512, 512)) / 512
                 for i in range(6)}
            p["big"] = jax.random.normal(keys[6], (1024, 1024)) / 512
            p["b"] = jnp.zeros((512,))
            return p

        def loss_fn(params, batch):
            x = batch
            for i in range(6):
                x = jnp.tanh(x @ params[f"w{i}"])
            return jnp.mean((x @ params["big"][:512])[:, :512] ** 2
                            + params["b"])

        self.register(model_init=model_init, loss_fn=loss_fn,
                      optimizer=optax.adam(1e-2),
                      seed=config.get("seed", 0))
        self.register_data(
            train_loader=[np.ones((4, 512), np.float32)] * 2)


class Small(TrainingOperator):
    """`weights` (one unless configured) (256, 256) matrices under adam,
    0.75 MiB of state each: one is under the 1.6 MiB budget of the 8 MiB
    store and a single piece, four lie between one budget and the
    6.4 MiB the store holds whole."""

    def setup(self, config):
        import optax

        count = config.get("weights", 1)

        def model_init(rng):
            return {f"w{i}": jax.random.normal(key, (256, 256)) / 16
                    for i, key in enumerate(jax.random.split(rng, count))}

        def loss_fn(params, batch):
            x = batch
            for i in range(count):
                x = jnp.tanh(x @ params[f"w{i}"])
            return jnp.mean(x ** 2)

        self.register(model_init=model_init, loss_fn=loss_fn,
                      optimizer=optax.adam(1e-2))
        self.register_data(
            train_loader=[np.ones((4, 256), np.float32)] * 2)


@pytest.fixture(scope="module")
def host():
    """A 'host' with four declared chips and an 8 MiB object store."""
    ray_tpu.init(num_cpus=4, num_tpus=4, object_store_memory=ARENA)
    try:
        yield
    finally:
        ray_tpu.shutdown()


def _span(entry, name):
    return [s["attrs"] for s in entry["spans"] if s["name"] == name]


def _wait_released(used, seconds=10):
    """The store's `used` back at `used` (bounded wait): nothing pinned."""
    store = global_state.require_core_worker().store
    deadline = time.monotonic() + seconds
    while store.stats()["used"] > used and time.monotonic() < deadline:
        time.sleep(0.05)
    assert store.stats()["used"] <= used


def _bits(tree):
    return [(x.dtype.str, x.shape, x.tobytes()) if isinstance(x, np.ndarray)
            else x for x in jax.tree.leaves(tree)]


# ---------------------------------------------------------------------
# the mesh comes from the lease
# ---------------------------------------------------------------------

@pytest.mark.parametrize("chips, mesh", [(4, [1, 4]), (1, None)])
def test_a_lease_of_several_chips_shards_without_mesh_mode(host, chips,
                                                           mesh):
    tr = Trainer(TinyGPT, num_workers=1, use_tpu=True,
                 resources_per_worker={"CPU": 1, "TPU": chips})
    try:
        tr.train(num_steps=1)
        (facts,) = _span(call_log()[-1], "train.dispatch")
    finally:
        tr.shutdown(force=True)
    assert facts.get("mesh") == mesh and facts["chips"] == chips
    whole, fullest = facts["state_bytes"], facts["state_bytes_fullest_chip"]
    if chips == 1:      # untouched: the state whole on the one device
        assert fullest == whole
    else:               # every leaf of the tiny GPT divides by four
        assert fullest <= 0.3 * whole


def _operator(monkeypatch, chips, **config):
    monkeypatch.setattr(operator_mod, "_leased_chips", lambda: chips)
    return TinyGPT(config, 0, 1)


def test_every_leaf_that_divides_is_split_past_its_leading_dim_if_it_can(
        monkeypatch):
    op = _operator(monkeypatch, 4, layers=STACK)
    assert dict(op._mesh.shape) == {"data": 1, "fsdp": 4}
    assert len(op._mesh.devices.flat) == 4      # of the 8 there are
    state = jax.tree.leaves((op.params, op.opt_state))
    assert len(state) > 20
    for x in state:
        dims = [d for d, n in enumerate(x.shape) if n % 4 == 0]
        spec = tuple(x.sharding.spec) + (None,) * x.ndim
        if dims:    # the first that divides after the leading one
            dim = ([d for d in dims if d] or dims)[0]
            assert spec[dim] == "fsdp", (x.shape, x.sharding.spec)
            assert spec.count("fsdp") == 1
            assert x.addressable_shards[0].data.size * 4 == x.size
        else:
            assert x.is_fully_replicated
    # the twelve layers divide by four and are left whole all the same
    assert op.params["blocks"]["wqkv"].sharding.spec == P(None, "fsdp", None)
    assert op.params["blocks"]["b_in"].sharding.spec == P(None, "fsdp")
    # at GPT-2 large's shapes: no stack along its layers, the embedding
    # along its width (50257 rows do not divide), the positions too now
    from ray_tpu.parallel import mesh as meshlib

    specs = meshlib.fsdp_param_specs(
        {"wte": jax.ShapeDtypeStruct((50257, 1280), jnp.float32),
         "wpe": jax.ShapeDtypeStruct((1024, 1280), jnp.float32),
         "wqkv": jax.ShapeDtypeStruct((36, 1280, 3840), jnp.float32),
         "w_out": jax.ShapeDtypeStruct((36, 5120, 1280), jnp.float32),
         "ln1_w": jax.ShapeDtypeStruct((36, 1280), jnp.float32),
         "lnf_w": jax.ShapeDtypeStruct((1280,), jnp.float32),
         "odd": jax.ShapeDtypeStruct((3, 5), jnp.float32)}, op._mesh)
    assert specs == {"wte": P(None, "fsdp"), "wpe": P(None, "fsdp"),
                     "wqkv": P(None, "fsdp", None),
                     "w_out": P(None, "fsdp", None),
                     "ln1_w": P(None, "fsdp"), "lnf_w": P("fsdp"),
                     "odd": P()}
    facts = op._layout_facts()
    assert facts["state_bytes_fullest_chip"] <= 0.3 * facts["state_bytes"]
    assert facts["state_bytes_split_leading"] == 0
    assert facts["mesh"] == [1, 4] and facts["chips"] == 4


def test_split_leading_counts_the_stacks_a_layout_splits_along_layers(
        monkeypatch):
    """`state_bytes_split_leading` under the layout the rule used to
    give: every stacked leaf and its two moments, and nothing of one
    dimension."""
    facts = _stack_operator(monkeypatch, "leading")._layout_facts()
    shapes = jax.tree.leaves(jax.eval_shape(
        _tiny_pieces(layers=STACK)[0], jax.random.key(0)))
    stacked = sum(4 * int(np.prod(x.shape)) for x in shapes
                  if x.ndim >= 2 and x.shape[0] % 4 == 0)
    assert facts["state_bytes_split_leading"] == 3 * stacked > 0
    assert stacked < sum(4 * int(np.prod(x.shape)) for x in shapes)


def _leading_spec(x):
    """The first dimension that divides by four: the rule before PR 30."""
    for dim, n in enumerate(x.shape):
        if n % 4 == 0:
            return P(*[None] * dim, "fsdp")
    return P()


def _stack_operator(monkeypatch, layout):
    """The twelve-layer tiny GPT on four chips, laid out by the rule
    (`layout="rule"`) or as the rule did before (`"leading"`: handed to
    `register` as any `param_spec` is)."""
    init, loss_fn, opt, _ = _tiny_pieces(layers=STACK)
    monkeypatch.setattr(operator_mod, "_leased_chips", lambda: 4)

    class Op(TrainingOperator):
        def setup(self, config):
            spec = None if layout == "rule" else jax.tree.map(
                _leading_spec, jax.eval_shape(init, jax.random.key(0)))
            self.register(model_init=init, loss_fn=loss_fn, optimizer=opt,
                          param_spec=spec)

    return Op({}, 0, 1)


# ---------------------------------------------------------------------
# a layer's weights are gathered inside the layer scan
# ---------------------------------------------------------------------

def _collectives(text):
    """(operation, result shapes, inside a while body?) of every
    collective in a compiled module's text. A computation is inside when
    a while names it as its body or one that is inside calls it."""
    import re

    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head and not line.startswith(" "):
            name = head.group(1)
            comps[name] = []
        elif name and line.startswith(" "):
            comps[name].append(line)
    inside = set()
    grow = {m for lines in comps.values() for line in lines
            for m in re.findall(r"body=%?([\w.\-]+)", line)}
    while grow:
        inside |= grow
        grow = {m for c in grow for line in comps.get(c, ())
                for m in re.findall(
                    r"(?:calls|to_apply|body|condition|branch_computations)"
                    r"=\{?%?([\w.\-]+)", line)} - inside
    found = []
    for comp, lines in comps.items():
        for line in lines:
            m = re.search(r"= (.*?) (all-gather|all-reduce|reduce-scatter|"
                          r"all-to-all|collective-permute)(?:-start)?\(",
                          line)
            if m:
                shapes = [tuple(int(n) for n in dims.split(",") if n)
                          for dims in re.findall(r"\w+\[([\d,]*)\]",
                                                 m.group(1))]
                found.append((m.group(2), shapes, comp in inside))
    return found


@pytest.mark.parametrize("layout", ["rule", "leading"])
def test_the_mesh_step_gathers_a_layer_inside_the_scan_not_stacks_before(
        monkeypatch, layout):
    """The operator's fused mesh step for a twelve-layer tiny GPT, as
    the partitioner compiles it: under the rule no all-gather outside a
    while body has the stack's extent, and the scans' bodies hold the
    per-layer ones. `leading` is the control that the reading can fail:
    the layout the rule gave before, and its gathers of whole stacks."""
    tokens = _tiny_pieces()[-1]
    found = _collectives(
        _stack_operator(monkeypatch, layout).compiled_step_text(tokens))
    gathers = [(shapes, inside) for op, shapes, inside in found
               if op == "all-gather"]
    assert len(gathers) >= 8, found
    stacks_outside = [s for shapes, inside in gathers if not inside
                      for s in shapes if len(s) >= 2 and STACK in s[:1]]
    # one layer of a stacked matrix: (64, 192), or (1, 64, 192)
    a_layer = [s for shapes, inside in gathers if inside for s in shapes
               if STACK not in s and len([n for n in s if n > 1]) == 2]
    if layout == "rule":
        assert not stacks_outside
        assert not [s for shapes, _ in gathers for s in shapes
                    if STACK in s]          # nor anywhere else
        assert len(a_layer) >= 8, gathers   # four a pass, at the least
    else:
        assert len(stacks_outside) >= 4, gathers
        assert not a_layer


def test_three_steps_with_the_stacks_split_past_their_layers_match_one_device(
        monkeypatch):
    tokens = _tiny_pieces()[-1]
    mesh_op = _operator(monkeypatch, 4, layers=STACK)
    one = _operator(monkeypatch, 1, layers=STACK)
    assert one._mesh is None
    assert one._layout_facts()["state_bytes_split_leading"] == 0
    w_in = mesh_op.params["blocks"]["w_in"]
    assert w_in.sharding.spec == P(None, "fsdp", None)
    assert w_in.sharding.shard_shape(w_in.shape) == (STACK, 16, 256)
    mesh_losses = [mesh_op.train_batch(tokens)["train_loss"]
                   for _ in range(3)]
    one_losses = [one.train_batch(tokens)["train_loss"] for _ in range(3)]
    # the tolerance of the two-layer test above, and its reasons
    assert mesh_losses == pytest.approx(one_losses, rel=2e-4)
    assert mesh_losses[2] < mesh_losses[0]
    # a leaf split along dimension 1 comes back from `state_piece` as
    # `np.asarray` gives it, joined from four shards of twelve runs each
    want = _bits(jax.tree.map(
        lambda x: np.asarray(x) if isinstance(x, jax.Array) else x,
        mesh_op._state_tree()))
    copies, _ = _pull(mesh_op, usable=1 << 21)      # in several pieces
    assert _bits(copies) == want
    _, (counts,) = _traced_d2h(lambda: mesh_op.state_piece(0, 1 << 30))
    assert counts["staged_bytes"] > 0.9 * counts["bytes"]


def _reference(init, tokens):
    """Loss and gradients of the plain float32 reference."""
    from benchmark.families import gpt_reference

    rows, t = tokens.shape

    def mean_nll(params):
        return gpt_reference._nll_sum(params, tokens, n_head=4,
                                      eps=1e-5) / (rows * (t - 1))

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(mean_nll)(init)


def test_three_steps_on_the_derived_mesh_match_one_device_and_reference(
        monkeypatch):
    init_fn, _, _, tokens = _tiny_pieces()
    mesh_op = _operator(monkeypatch, 4)
    one = _operator(monkeypatch, 1)
    assert one._mesh is None
    mesh_losses = [mesh_op.train_batch(tokens)["train_loss"]
                   for _ in range(3)]
    one_losses = [one.train_batch(tokens)["train_loss"] for _ in range(3)]
    # same program, same seed, same global batch: only the order in
    # which bf16 partial sums are added differs between one device and
    # four (measured here up to 2e-5); a gradient taken from one shard
    # of the batch, or a reduction left out, moves steps 1 and 2 by
    # 1e-3 or more
    assert mesh_losses == pytest.approx(one_losses, rel=2e-4)
    assert mesh_losses[2] < mesh_losses[0]
    # step 0 against the float32 reference: bf16 compute, averaged over
    # 8 x 127 targets (the benchmark's own check; 5e-5 on the chip at
    # 32 x 1023 targets)
    init = init_fn(jax.random.key(TINY["seed"]))
    ref_loss, ref_grads = _reference(init, tokens)
    assert mesh_losses[0] == pytest.approx(float(ref_loss), rel=5e-4)

    # the gradients the FUSED sharded step applied: under SGD with a
    # learning rate of 1 they are what one step took off the parameters
    sgd = _operator(monkeypatch, 4, optimizer="sgd")
    before = jax.tree.map(np.asarray, sgd.params)
    sgd.train_batch(tokens)
    applied = jax.tree.map(lambda a, b: a - np.asarray(b), before,
                           sgd.params)
    for (path, g), ref in zip(
            jax.tree_util.tree_flatten_with_path(applied)[0],
            jax.tree.leaves(ref_grads)):
        ref = np.asarray(ref)
        # bf16 forward and backward against float32: 2**-8 an operation,
        # a few operations deep, relative to the leaf's norm (measured
        # up to 1.3e-2); a sum where a mean belongs is off by 3.0, a
        # shard's own rows alone by about 1
        err = np.linalg.norm(g - ref) / np.linalg.norm(ref)
        assert err < 4e-2, (jax.tree_util.keystr(path), err)


def test_load_state_dict_puts_each_leaf_straight_onto_its_sharding(
        monkeypatch):
    op = _operator(monkeypatch, 4)
    tokens = _tiny_pieces()[-1]
    op.train_batch(tokens)
    saved = op.state_dict()
    layout = jax.tree.map(lambda x: x.sharding, (op.params, op.opt_state))
    op.train_batch(tokens)
    placed = []
    real = jax.device_put
    monkeypatch.setattr(
        jax, "device_put",
        lambda x, s=None, **kw: placed.append(s) or real(x, s, **kw))
    op.load_state_dict(saved)
    monkeypatch.undo()
    # every array went from the host to a NamedSharding of the mesh —
    # none whole onto one device first
    assert len(placed) == len(jax.tree.leaves(layout))
    assert all(isinstance(s, jax.sharding.NamedSharding) for s in placed)
    assert jax.tree.map(lambda x: x.sharding,
                        (op.params, op.opt_state)) == layout
    assert _bits(op.state_dict()) == _bits(saved)
    built = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _s, **kw: built.append(event)
        if event.endswith("backend_compile_duration") else None)
    op.train_batch(tokens)      # the step's program still fits the layout
    assert not built
    # pieces out of order, or a stranger's tree, are refused whole
    held = _bits(op.state_dict())
    with pytest.raises(ValueError, match="out of order"):
        op.load_state_piece(5, [np.zeros(3)])
    with pytest.raises(ValueError, match="leaves"):
        op.load_state_dict(dict(saved, params={"w": np.zeros(3)}))
    assert _bits(op.state_dict()) == held


def test_a_killed_worker_restores_onto_the_mesh_with_equal_losses(host):
    def run(kill):
        tr = Trainer(TinyGPT, num_workers=1, use_tpu=True, max_retries=2,
                     resources_per_worker={"CPU": 1, "TPU": 4})
        try:
            losses = []
            for call in range(4):
                if kill and call == 2:
                    ray_tpu.kill(tr.workers[0])
                losses.append(tr.train(num_steps=2)["last_train_loss"])
            attempts = _span(call_log()[-2], "train.epoch")[0]["attempts"]
            facts = _span(call_log()[-1], "train.dispatch")[0]
            return losses, attempts, facts
        finally:
            tr.shutdown(force=True)

    straight, _, _ = run(kill=False)
    resumed, attempts, facts = run(kill=True)
    assert attempts == 2            # the third call met a dead worker
    assert facts["mesh"] == [1, 4]  # ... and its successor shards too
    assert resumed == straight      # bit for bit


# ---------------------------------------------------------------------
# a snapshot crosses in pieces
# ---------------------------------------------------------------------

MIB = 1 << 20


@pytest.mark.parametrize("sizes, ranges", [
    # under one budget (a quarter of the 8 MiB that fit): one piece
    ([MIB, 4, MIB // 2], [(0, 3)]),
    ([2 * MIB], [(0, 1)]),
    # between one budget and what fits: cut all the same
    ([3 * MIB, 4 * MIB, MIB], [(0, 1), (1, 2), (2, 3)]),
    ([MIB, MIB, MIB, 4, MIB, MIB], [(0, 2), (2, 4), (4, 6)]),
    # more than fits: the ranges the rule gave while what fits was one
    # piece (the parent's own output, as literals): a leaf over the
    # budget is a piece of its own
    ([MIB, MIB, MIB, 3 * MIB, 4, MIB, 2 * MIB, MIB],
     [(0, 2), (2, 3), (3, 4), (4, 6), (6, 7), (7, 8)]),
    ([4, 4, 6 * MIB, 6 * MIB, 1536 * 1024, MIB // 2, MIB, 6 * MIB, 8],
     [(0, 2), (2, 3), (3, 4), (4, 6), (6, 7), (7, 8), (8, 9)]),
    # nothing at all is still a piece
    ([], [(0, 0)]),
    # a leaf the store cannot hold crosses in no plan, whatever the rest
    ([MIB, 9 * MIB], None),
    ([9 * MIB], None),
], ids=["under_a_budget", "one_leaf_of_a_budget", "fits_leaves_over_budget",
        "fits_runs_of_leaves", "over_usable", "over_usable_stacks",
        "empty", "leaf_over_usable", "lone_leaf_over_usable"])
def test_the_plan(sizes, ranges):
    usable = 8 * MIB
    budget = usable // snapshot.PIECE_SHARE
    if ranges is None:
        with pytest.raises(ValueError, match="more than the object "
                                             "store's arena"):
            snapshot.plan(sizes, usable)
        return
    assert snapshot.plan(sizes, usable) == ranges
    # contiguous, in order, whole; a piece over the budget is one leaf
    assert [first for first, _ in ranges] == [0] + [
        stop for _, stop in ranges[:-1]]
    assert ranges[-1][1] == len(sizes)
    for first, stop in ranges:
        assert sum(sizes[first:stop]) <= budget or stop - first == 1


def test_usable_bytes_reads_the_store(host):
    cw = global_state.require_core_worker()
    assert cw.store.stats()["capacity"] == ARENA
    assert snapshot.usable_bytes(cw) == int(
        ARENA * cw.config.object_spilling_threshold)


@pytest.fixture(scope="module")
def wide(host):
    tr = Trainer(Wide, num_workers=1)
    try:
        yield tr
    finally:
        tr.shutdown(force=True)


def test_a_state_several_arenas_large_crosses_in_pieces(wide):
    used = global_state.require_core_worker().store.stats()["used"]
    reused = []
    for _ in range(4):
        wide.train()
        entry = call_log()[-1]
        (snap,) = _span(entry, "train.snapshot")
        copies = _span(entry, "train.snapshot.copy")
        # one of each leaf span a piece (a piece under 100 KiB — the
        # biases, the step counts — returns inline: no put, no get)
        assert snap["pieces"] == len(copies) > 4
        assert len(_span(entry, "train.snapshot.d2h")) == snap["pieces"]
        for name in ("object.return_put", "object.get"):
            assert 4 < len(_span(entry, name)) <= snap["pieces"], name
        assert snap["bytes"] == sum(c["bytes"] for c in copies)
        assert snap["bytes"] > 2 * ARENA
        assert sum(s["bytes"] for s in _span(
            entry, "train.snapshot.d2h")) == snap["bytes"]
        reused.append(sum(c["reused_bytes"] for c in copies))
    # into bytes the Trainer already had from its first call on (PR 54:
    # before, the first two calls' leaves were allocated as they came)
    assert reused == [snap["bytes"]] * 4
    state = wide._last_state
    reserve = wide._owned[0].reserve
    for x in jax.tree.leaves(state):
        if isinstance(x, np.ndarray):
            assert reserve.holds(x) and x.flags.writeable
    # bit-identical: pulled again (fresh buffers), the same bits
    assert _bits(wide.state_dict()) == _bits(state)
    # nothing of it is left in the arena
    _wait_released(used)


def test_nothing_is_staged_on_one_device(wide):
    """One device: no leaf has shards to join, so no staging area."""
    wide.train()
    parts = _span(call_log()[-1], "train.snapshot.d2h")
    assert len(parts) > 4
    assert all(p["staged_bytes"] == 0 and p["shards"] == 0 for p in parts)
    assert sum(p["bytes"] for p in parts) > 2 * ARENA


# ---------------------------------------------------------------------
# the boundary's critical path, piece by piece
# ---------------------------------------------------------------------

def _named(entry, name):
    return [s for s in entry["spans"] if s["name"] == name]


def test_every_piece_has_a_wait_a_d2h_and_a_copy_of_its_index(wide):
    """The driver's `train.snapshot.wait` and `train.snapshot.copy` and
    the worker's `train.snapshot.d2h` carry the piece's index, so a
    reader matches the two processes' spans by piece, not by order."""
    wide.train()
    entry = call_log()[-1]
    (snap,) = _named(entry, "train.snapshot")
    indices = list(range(snap["attrs"]["pieces"]))
    assert len(indices) > 4
    for name in ("train.snapshot.wait", "train.snapshot.d2h",
                 "train.snapshot.copy"):
        assert sorted(s["attrs"]["piece"]
                      for s in _named(entry, name)) == indices, name
    waits = {s["span"]: s for s in _named(entry, "train.snapshot.wait")}
    assert all(w["parent"] == snap["span"] for w in waits.values())
    # the map + deserialise of a piece hangs under ITS wait (a piece
    # under 100 KiB returns inline: no `object.get`), and lies inside it
    gets = span_log.under(entry, "object.get", "train.snapshot")
    assert 4 < len(gets) <= len(indices)
    for get in gets:
        wait = waits[get["parent"]]
        assert wait["start"] <= get["start"] <= get["end"] <= wait["end"]
    assert len({g["parent"] for g in gets}) == len(gets)
    # the asked-ahead submits stay where they were: under the snapshot
    assert all(s["parent"] == snap["span"] for s in _named(entry, "task.e2e")
               if s["attrs"]["name"] == "TrainWorker.state_piece")


def test_waits_and_copies_tile_the_drivers_thread(wide):
    """On the driver's thread the wait and copy spans follow one another
    and never overlap; what they leave of `train.snapshot` is the
    submits, the tree handling and the last piece's release — stated
    here as under a quarter of a second for 22 pieces (5 ms alone)."""
    wide.train()
    entry = call_log()[-1]
    (snap,) = _named(entry, "train.snapshot")
    tiles = sorted(_named(entry, "train.snapshot.wait")
                   + _named(entry, "train.snapshot.copy"),
                   key=lambda s: s["start"])
    assert [s["name"] for s in tiles] == [
        "train.snapshot.wait", "train.snapshot.copy"] * (len(tiles) // 2)
    assert [s["attrs"]["piece"] for s in tiles] == [
        i // 2 for i in range(len(tiles))]
    assert snap["start"] <= tiles[0]["start"]
    assert tiles[-1]["end"] <= snap["end"]
    for before, after in zip(tiles, tiles[1:]):
        assert before["end"] <= after["start"], (before, after)
    remainder = (snap["end"] - snap["start"]
                 - sum(s["end"] - s["start"] for s in tiles))
    assert 0 <= remainder < scale_timeout(0.25)
    # the reader's parts are the same tiling, and add up to the boundary
    path = boundary_path.call_path(entry)
    assert path["pieces"] == len(tiles) // 2
    assert path["wait_s"] > 0 and path["get_s"] > 0 and path["copy_s"] > 0
    assert path["wait_s"] + path["get_s"] + path["copy_s"] == pytest.approx(
        sum(s["end"] - s["start"] for s in tiles))
    assert 0 <= path["hops_s"] == pytest.approx(
        path["boundary_s"] - path["wait_s"] - path["get_s"]
        - path["copy_s"])


def test_a_d2h_span_accounts_for_its_seconds(wide):
    """`start_s` + `wait_s` + `join_s` are seconds INSIDE the span, and
    on one device nothing is joined."""
    wide.train()
    parts = _named(call_log()[-1], "train.snapshot.d2h")
    assert len(parts) > 4
    for part in parts:
        a = part["attrs"]
        assert a["start_s"] > 0 and a["wait_s"] > 0 and a["join_s"] == 0
        assert a["start_s"] + a["wait_s"] + a["join_s"] <= (
            part["end"] - part["start"])


def test_a_join_is_timed_where_a_leaf_is_split_over_devices(monkeypatch):
    from ray_tpu._private import tracing

    op = _operator(monkeypatch, 4)
    op.train_batch(_tiny_pieces()[-1])
    root = tracing.always_trace()
    with tracing.open_tree(root) as rows, tracing.use(root):
        _pull(op, usable=1 << 19)
    parts = [r for r in rows if r[0] == "train.snapshot.d2h"]
    assert [r[3]["piece"] for r in parts] == list(range(len(parts)))
    assert len(parts) > 4
    for _, start, end, a in parts:
        assert a["start_s"] + a["wait_s"] + a["join_s"] <= end - start
        # shards are written where something was staged, and only there
        assert (a["join_s"] > 0) == (a["staged_bytes"] > 0) == (
            a["shards"] > 0)
    assert sum(r[3]["staged_bytes"] for r in parts) > 1 << 20


def _join_threads():
    return {t for t in threading.enumerate()
            if t.name.startswith("train-join")}


def _shard_writes(monkeypatch, before=lambda out, shard: None):
    """A spy on `_write_shard`: the name of the thread that wrote each
    shard, in the order the writes ENDED; `before(out, shard)` runs on
    that thread ahead of the real write."""
    import threading

    real, names = operator_mod._write_shard, []

    def spy(out, shard):
        try:
            before(out, shard)
            return real(out, shard)
        finally:
            names.append(threading.current_thread().name)

    monkeypatch.setattr(operator_mod, "_write_shard", spy)
    return names


@pytest.mark.parametrize("case", ["first_dim", "later_dim", "replicated",
                                  "partly_replicated", "widths_differ"])
def test_the_shards_of_a_leaf_are_joined_by_a_thread_each(monkeypatch, case):
    """The shards of one leaf are waited for and written BESIDE one
    another, on threads the operator keeps, and what comes back is what
    `np.asarray` of the whole leaf gives; `join_threads` says how many,
    and the span's three counts stay wall seconds inside it."""
    from ray_tpu._private import tracing

    op = _operator(monkeypatch, 4)
    params, shards = _sharded_cases(op._mesh)[case]
    op.params, op.model_state, op.opt_state = params, {}, {}
    arrays = jax.tree.leaves(params)
    widths = [len({s.index for s in x.addressable_shards})
              for x in arrays
              if isinstance(x, jax.Array) and not x.is_fully_replicated]
    assert sum(widths) == shards
    # every shard of a leaf (told by its shape) waits until all of them
    # are in flight: only a thread a shard gets past this
    gates = {x.shape: threading.Barrier(w, timeout=scale_timeout(30))
             for x, w in zip(arrays, widths)}
    names = _shard_writes(
        monkeypatch, before=lambda out, shard: gates[out.shape].wait())
    had = _join_threads()
    root = tracing.always_trace()
    with tracing.open_tree(root) as rows, tracing.use(root):
        piece = op.state_piece(0, 1 << 30)
    (_, start, end, counts), = [r for r in rows
                                if r[0] == "train.snapshot.d2h"]
    got = piece["leaves"][-len(arrays):]    # after epoch, global_step
    assert _bits(got) == _bits([np.asarray(x) for x in arrays])
    assert counts["shards"] == shards == len(names)
    assert counts["join_threads"] == max(widths, default=0)
    assert counts["start_s"] + counts["wait_s"] + counts["join_s"] <= (
        end - start)
    assert (counts["join_s"] > 0) == (counts["staged_bytes"] > 0) == (
        counts["join_threads"] > 0)
    # never the caller's thread, and no more threads than the widest
    # leaf has shards — none at all where nothing is joined
    assert all(n.startswith("train-join") for n in names)
    assert len(_join_threads() - had) == max(widths, default=0)
    assert (op._joiners is None) == (not widths)
    # a second pull finds the threads it needs
    mine = _join_threads() - had
    op.state_piece(0, 1 << 30)
    assert _join_threads() - had == mine


def test_a_shard_that_fails_raises_in_the_caller_after_every_write_ended(
        monkeypatch):
    """An exception on one of the join's threads reaches `state_piece`'s
    caller, not before the leaf's other shards are written (nobody
    writes into the staging area behind the caller's back), and the
    next pull works."""
    op = _operator(monkeypatch, 4)
    op.train_batch(_tiny_pieces()[-1])
    usable = 1 << 19
    calls = []

    def third_fails(out, shard):
        calls.append(shard)
        if len(calls) == 3:
            raise RuntimeError("the link dropped a shard")

    names = _shard_writes(monkeypatch, before=third_fails)
    with pytest.raises(RuntimeError, match="the link dropped a shard"):
        _pull(op, usable)
    # the failing leaf's four shards were all taken up and all ended
    assert len(calls) == len(names) == 4
    monkeypatch.undo()
    (copies, _), parts = _traced_d2h(lambda: _pull(op, usable))
    assert _bits(copies) == _bits(op.state_dict())
    assert {p["join_threads"] for p in parts} == {4}


def test_on_one_device_no_thread_is_ever_started(monkeypatch):
    op = _operator(monkeypatch, 1)
    tokens = _tiny_pieces()[-1]
    before = set(threading.enumerate())
    for _ in range(2):
        op.train_batch(tokens)
        (copies, _), parts = _traced_d2h(lambda: _pull(op, 1 << 19))
        assert _bits(copies) == _bits(op.state_dict())
        assert len(parts) > 4
        assert {p["join_threads"] for p in parts} == {0}
        assert {p["join_s"] for p in parts} == {0.0}
    assert op._joiners is None and op._stage is None
    assert set(threading.enumerate()) <= before


# numpy's NPY_NEEDS_PYAPI: a dtype whose copy loops hold the GIL
NEEDS_PYAPI = 0x10


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16, np.float16,
                                   np.int8, np.int32, np.uint32])
def test_a_shards_write_leaves_the_gil_for_every_dtype_a_state_keeps(dtype):
    """`out[index] = arrived` is numpy's raw copy for these, extension
    dtypes included: the join's threads write beside one another."""
    dtype = np.dtype(dtype)
    assert not dtype.flags & NEEDS_PYAPI and not dtype.hasobject


def test_dest_writes_counts_each_buffer_sets_writes(host):
    """0, 0 (each set's first snapshot: into the bytes reserved for it,
    so `reused_bytes` = `bytes` from the first call on), 1, 1 (each
    set's second), then 2, 2 ... — a count of SNAPSHOTS the Trainer
    keeps with each set; a set that had to be allocated anew (here: the
    spare's tree is not the state's, and the reservation is taken)
    starts again at 0 and lets go of its reservation."""
    tr = Trainer(Small, num_workers=1)
    seen = []

    def call():
        tr.train()
        (copy,) = _span(call_log()[-1], "train.snapshot.copy")
        seen.append(copy["dest_writes"])
        return copy

    try:
        for _ in range(6):
            copy = call()
            assert copy["reused_bytes"] == copy["bytes"] > 0
            assert copy["reserve_wait_s"] >= 0.0
        assert seen == [0, 0, 1, 1, 2, 2]
        assert [s.writes for s in tr._owned] == [3, 3]
        assert all(s.reserve is not None for s in tr._owned)
        newer, older = tr._owned
        tr._owned = (newer, older._replace(
            state={"w": older.state["params"]["w0"]}))
        copy = call()
        assert copy["dest_writes"] == 0 and copy["reused_bytes"] == 0
        assert [s.writes for s in tr._owned] == [1, 3]
        # a changed tree is allocated as ever: its leaves own their data
        # and the set has let go of the reservation none of them is in
        assert tr._owned[0].reserve is None
        assert all(x.flags.owndata for x in jax.tree.leaves(tr._last_state)
                   if isinstance(x, np.ndarray))
        # the untouched set goes on counting, the new one starts over
        copy = call()
        assert copy["dest_writes"] == 3
        assert copy["reused_bytes"] == copy["bytes"]
        assert call()["dest_writes"] == 1
    finally:
        tr.shutdown(force=True)


# ---------------------------------------------------------------------
# the two sets are reserved, and made resident, before a state lands
# ---------------------------------------------------------------------

def _reserver_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("train-reserve")]


@pytest.fixture
def held_back(monkeypatch):
    """The reserving threads stand still until the test lets them go:
    an Event they wait for before they write their first chunk."""
    go = threading.Event()
    real = trainer_mod._Reserve.write

    def write(self, chunk):
        assert go.wait(scale_timeout(60))
        return real(self, chunk)

    monkeypatch.setattr(trainer_mod._Reserve, "write", write)
    try:
        yield go
    finally:
        go.set()


def _reserve_spans(entry):
    return sorted((s for s in entry["spans"]
                   if s["name"] == "train.snapshot.reserve"),
                  key=lambda s: s["attrs"]["set"])


def test_the_sets_are_reserved_at_the_start_and_made_resident_beside_it(
        host, held_back, monkeypatch):
    """`Trainer(...)` returns before a byte is resident; a pull that
    arrives early waits for its set to be whole, its first copy says
    how long, and the right bytes are installed; the second set is
    begun by the second call, whose pull is the first to land there,
    and whole when that call returns; each set's reservation is one
    span of the START's tree, ending after that tree closed."""
    tr = Trainer(Small, num_workers=1, config={"weights": 4})
    try:
        entry = start_log()[-1]
        assert _reserve_spans(entry) == []      # none has ended
        assert len(_reserver_threads()) >= 1
        first, second = tr._owned[1].reserve, tr._owned[0].reserve
        state_bytes = 4 * 3 * 256 * 256 * 4 + 4     # adam: two moments
        assert first.bytes.nbytes == second.bytes.nbytes >= state_bytes
        assert first.bytes.nbytes <= state_bytes + 4096 * 14
        # the first is being made resident, the second not begun: a
        # copy would wait for the one and never for the other
        assert first.ready == 0 and second.ready == second.bytes.nbytes
        real_wait = trainer_mod._Reserve.wait_for

        def wait_for(self, leaf=None):  # let go once the pull waits
            if not held_back.is_set():
                threading.Timer(0.25, held_back.set).start()
            return real_wait(self, leaf)

        monkeypatch.setattr(trainer_mod._Reserve, "wait_for", wait_for)
        tr.train()
        call = call_log()[-1]
        copies = _span(call, "train.snapshot.copy")
        assert len(copies) > 1      # several pieces
        assert copies[0]["reserve_wait_s"] >= 0.2
        assert all(c["reused_bytes"] == c["bytes"]
                   and c["dest_writes"] == 0 for c in copies)
        assert sum(c["bytes"] for c in copies) == state_bytes
        assert all(first.holds(x) for x in jax.tree.leaves(tr._last_state)
                   if isinstance(x, np.ndarray))
        assert _bits(tr.state_dict()) == _bits(tr._last_state)
        for t in _reserver_threads():
            t.join(scale_timeout(30))
        (a,) = _reserve_spans(start_log()[-1])   # the second: not begun
        assert second.ready == second.bytes.nbytes
        tr.train()
        copies = _span(call_log()[-1], "train.snapshot.copy")
        assert all(c["reused_bytes"] == c["bytes"] and c["dest_writes"] == 0
                   and c["reserve_wait_s"] >= 0.0 for c in copies)
        assert all(second.holds(x) for x in jax.tree.leaves(tr._last_state)
                   if isinstance(x, np.ndarray))
        assert second.ready == second.bytes.nbytes  # whole at its end
        for t in _reserver_threads():
            t.join(scale_timeout(30))
        a, b = _reserve_spans(start_log()[-1])
        (root,) = [s for s in start_log()[-1]["spans"]
                   if s["name"] == "train.start"]
        for k, span in enumerate((a, b)):
            assert span["parent"] == root["span"]
            assert span["end"] > root["end"] and span["start"] < span["end"]
            assert span["attrs"] == {
                "set": k, "bytes": first.bytes.nbytes,
                "writes": trainer_mod.RESERVE_WRITES,
                "threads": span["attrs"]["threads"],
                "minor_faults": span["attrs"]["minor_faults"]}
            assert span["attrs"]["threads"] >= 1
            assert span["attrs"]["minor_faults"] >= 0
        (second_call,) = [s for s in call_log()[-1]["spans"]
                          if s["name"] == "train.call"]
        assert a["end"] <= second_call["start"] <= b["start"]
        # from here on nothing waits
        for _ in range(3):
            tr.train()
            assert all(c["reserve_wait_s"] == 0.0 for c in _span(
                call_log()[-1], "train.snapshot.copy"))
    finally:
        tr.shutdown(force=True)
    assert _reserver_threads() == []


def test_shutdown_leaves_no_reserver_thread(host, held_back):
    """... also one that had not written a byte; and a restarted group
    reserves nothing anew: the Trainer has its sets."""
    tr = Trainer(Small, num_workers=1)
    try:
        reserver, sets = tr._reserver, [s.reserve for s in tr._owned]
        assert _reserver_threads()
        held_back.set()
        starts = len(start_log())
        ray_tpu.kill(tr.workers[0])
        tr._kill_workers()
        tr._resize_worker_group()
        assert len(start_log()) == starts + 1 or starts == 32
        assert tr._reserver is reserver
        assert [s.reserve for s in tr._owned] == sets
        assert len(start_log()[-1]["spans"]) > 1    # the restart's tree
        assert _reserve_spans(start_log()[-1]) == []
    finally:
        tr.shutdown(force=True)
    assert _reserver_threads() == []
    tr = Trainer(Small, num_workers=1)
    tr.shutdown()           # the graceful one too
    assert _reserver_threads() == []


@pytest.mark.parametrize("fail_at", [0, 2])
def test_a_first_copy_that_raises_leaves_the_reservation_usable(
        host, monkeypatch, fail_at):
    """PR 26's guarantee on a Trainer's FIRST call, whose leaves come
    out of the reservation: a copy that raises half-way installs
    nothing and hands back what it took, and the next pull lands where
    this one would have."""
    tr = Trainer(Small, num_workers=1, config={"weights": 4})
    real, calls = np.copyto, []

    def copyto(dst, src, *a, **kw):
        calls.append(dst)
        if len(calls) == fail_at + 1:
            raise MemoryError("injected: the copy-out's leaf failed")
        return real(dst, src, *a, **kw)

    try:
        with monkeypatch.context() as m:
            m.setattr(np, "copyto", copyto)
            with pytest.raises(MemoryError, match="injected"):
                tr.train()
        assert len(calls) == fail_at + 1
        assert tr._last_state is None and tr._snapshot_of is None
        newer, older = tr._owned
        assert older.state is None and older.writes == 0
        assert older.reserve.taken == 0 and newer.reserve.taken == 0
        tr.train()
        copies = _span(call_log()[-1], "train.snapshot.copy")
        assert all(c["reused_bytes"] == c["bytes"]
                   and c["dest_writes"] == 0 for c in copies)
        assert all(older.reserve.holds(x)
                   for x in jax.tree.leaves(tr._last_state)
                   if isinstance(x, np.ndarray))
        assert _bits(tr.state_dict()) == _bits(tr._last_state)
    finally:
        tr.shutdown(force=True)


def test_state_dict_and_a_loaded_state_stay_the_callers(host):
    """`state_dict()` returns arrays that own their data (nothing of a
    reservation); arrays a caller loads are never written, before the
    first pull or after, and never become a set's."""
    tr = Trainer(Small, num_workers=1)
    try:
        theirs = tr.state_dict()
        arrays = [x for x in jax.tree.leaves(theirs)
                  if isinstance(x, np.ndarray)]
        assert arrays and all(x.flags.owndata and x.base is None
                              for x in arrays)
        tr.load_state_dict(theirs)      # before any pull
        before = _bits(theirs)
        for _ in range(3):
            tr.train()
            assert _bits(theirs) == before
            ours = [x for x in jax.tree.leaves(tr._last_state)
                    if isinstance(x, np.ndarray)]
            assert not any(np.shares_memory(x, y)
                           for x in arrays for y in ours)
            assert all(any(s.reserve.holds(y) for s in tr._owned)
                       for y in ours)
        again = tr.state_dict()
        assert all(x.flags.owndata for x in jax.tree.leaves(again)
                   if isinstance(x, np.ndarray))
        assert not any(s.reserve.holds(x) for s in tr._owned
                       for x in jax.tree.leaves(again)
                       if isinstance(x, np.ndarray))
    finally:
        tr.shutdown(force=True)


@pytest.mark.parametrize("order", ["C", "F", "permuted", "padded",
                                   "reversed", "scalar"])
def test_a_reserved_leaf_has_the_layout_np_array_gives(order):
    """`_Reserve.take` against `np.array(x)`: the same strides wherever
    `x` is dense in some order of its axes (a leaf the device keeps
    transposed stays transposed), a dense copy's otherwise; 16 bytes
    into a page, as `np.array` puts a large leaf; one after the other; None when the bytes run
    out."""
    x = np.arange(2 * 3 * 4, dtype=np.float32).reshape(2, 3, 4)
    x = {"C": x, "F": np.asfortranarray(x),
         "permuted": x.transpose(1, 2, 0), "padded": x[:, :, :3],
         "reversed": x[::-1], "scalar": np.array(3, np.float32)}[order]
    reserve = trainer_mod._Reserve(2 * (x.nbytes + 4096))
    dst = reserve.take(x)
    assert reserve.holds(dst) and not dst.flags.owndata
    assert dst.shape == x.shape and dst.dtype == x.dtype
    assert dst.strides == np.array(x).strides
    assert dst.ctypes.data % 4096 == 16     # as a mapping of its own
    np.copyto(dst, x)
    assert dst.tobytes() == x.tobytes()
    other = reserve.take(x)
    assert other.ctypes.data >= dst.ctypes.data + x.nbytes
    assert not np.shares_memory(other, dst)
    assert reserve.take(np.zeros(8192, np.uint8)) is None   # no room left
    reserve.seal()
    assert reserve.take(np.zeros((), np.uint8)) is None
    # a reservation nobody makes resident never holds a copy up
    assert reserve.wait_for(other) == 0.0
    assert trainer_mod._Reserve(8192).take(np.zeros((0, 3))) is None


def _snap_seconds(entry):
    (snap,) = _named(entry, "train.snapshot")
    return snap["end"] - snap["start"]


def _as_the_parent_recorded(entry):
    """`entry` less what this round of spans and counts added: the tree
    the parent commit's program gives for the same call."""
    waits = {s["span"]: s["parent"]
             for s in _named(entry, "train.snapshot.wait")}
    new = ("piece", "dest_writes", "start_s", "wait_s", "join_s",
           "spans_dropped")
    return {"trace_id": entry["trace_id"], "spans": [
        dict(s, parent=waits.get(s["parent"], s["parent"]),
             attrs={k: v for k, v in s["attrs"].items() if k not in new})
        for s in entry["spans"] if s["span"] not in waits]}


NEW_READERS = ("boundary_wait_s", "snapshot_link_wait_s", "snapshot_join_s",
               "snapshot_worker_starved_s", "snapshot_copy_rewrite_s",
               "first_pull_s")


def test_the_boundary_readers_on_a_recorded_tree_and_on_the_parents(
        host, monkeypatch):
    """The six readers of `benchmark/boundary_path.py` on the log of a
    run shaped like the benchmark's (`first`, `warm`, a window of four
    calls), multi-piece; on the parent's trees five give None and
    `first_pull_s`, which reads a span the parent has, its number."""
    import ray_tpu.train
    from benchmark import manifest

    tr = Trainer(Wide, num_workers=1)
    try:
        before = len(call_log())
        for _ in range(6):
            tr.train()
        log = call_log()[before:]
    finally:
        tr.shutdown(force=True)
    walls = [s["end"] - s["start"] for e in log
             for s in _named(e, "train.call")]
    run = {"attempted": 6, "first": {"wall_s": walls[0]},
           "calls": [{"wall_s": w} for w in walls[2:]]}
    paths = [boundary_path.call_path(e) for e in log]
    assert [p["dest_writes"] for p in paths] == [
        [0], [0], [1], [1], [2], [2]]

    def read(name):
        return manifest.module("layer_metrics", name).read(run, None)

    monkeypatch.setattr(ray_tpu.train, "call_log", lambda: list(log))
    window = paths[2:]
    median = statistics.median
    assert read("boundary_wait_s") == median(p["wait_s"] for p in window) > 0
    assert read("snapshot_link_wait_s") == median(
        p["link_wait_s"] for p in window) > 0
    assert read("snapshot_join_s") == 0.0       # one device
    assert read("snapshot_worker_starved_s") == median(
        p["starved_s"] for p in window) >= 0
    # read from exactly the window's first two calls: each set's second
    # write; `snapshot_copy_s` is the median over all four
    assert read("snapshot_copy_rewrite_s") == median(
        p["copy_s"] for p in window[:2])
    assert read("first_pull_s") == _snap_seconds(log[0]) > 0
    # the worker stands still only between its tasks: inside the pull
    for p, e in zip(paths, log):
        assert 0 <= p["starved_s"] < _snap_seconds(e)
    # a window without a second write has no rewrite reading
    monkeypatch.setattr(ray_tpu.train, "call_log", lambda: list(log[2:]))
    late = {"attempted": 4, "first": {"wall_s": walls[2]},
            "calls": [{"wall_s": w} for w in walls[4:]]}
    assert manifest.module("layer_metrics", "snapshot_copy_rewrite_s").read(
        late, None) is None
    # the ring dropped the first call: no first pull
    assert manifest.module("layer_metrics", "first_pull_s").read(
        dict(late, attempted=6), None) is None
    # the parent's trees: nothing to read but the span it already had
    old = [_as_the_parent_recorded(e) for e in log]
    assert "train.snapshot.wait" not in {
        s["name"] for e in old for s in e["spans"]}
    monkeypatch.setattr(ray_tpu.train, "call_log", lambda: list(old))
    for name in NEW_READERS[:-1]:
        assert read(name) is None, name
    assert read("first_pull_s") == _snap_seconds(old[0])
    # ... whose older readers read what they read before
    from benchmark.layer_metrics import snapshot_pieces
    assert snapshot_pieces.read(run, None) == paths[0]["pieces"]


# ---------------------------------------------------------------------
# a sharded leaf is joined in memory the operator keeps
# ---------------------------------------------------------------------

def _traced_d2h(call):
    """`call()` and the counts of the `train.snapshot.d2h` spans under it."""
    from ray_tpu._private import tracing

    root = tracing.always_trace()
    with tracing.open_tree(root) as rows, tracing.use(root):
        out = call()
    return out, [row[3] for row in rows if row[0] == "train.snapshot.d2h"]


def _pull(op, usable=1 << 30):
    """Every piece of `op`'s state in order, as the driver asks them;
    each piece's leaves are COPIED on arrival (what `_pack_returns`
    does), and the views themselves are kept to be looked at later."""
    copies, views, index, pieces = [], [], 0, 1
    while index < pieces:
        piece = op.state_piece(index, usable)
        if index == 0:
            pieces = len(piece["ranges"])
        views.append(piece["leaves"])
        copies.extend(np.array(x) if isinstance(x, np.ndarray) else x
                      for x in piece["leaves"])
        index += 1
    return copies, views


def _sharded_cases(mesh):
    from jax.sharding import Mesh, NamedSharding

    rng = np.random.default_rng(7)

    def put(shape, spec, dtype=np.float32, on=mesh):
        host = (rng.standard_normal(shape) * 100).astype(dtype)
        return jax.device_put(host, NamedSharding(on, spec))

    two_by_four = Mesh(np.array(jax.devices()).reshape(2, 4),
                       ("data", "fsdp"))
    return {
        "first_dim": ({"w": put((8, 6, 5), P("fsdp"))}, 4),
        "later_dim": ({"w": put((3, 5, 8), P(None, None, "fsdp")),
                       "v": put((6, 12), P(None, "fsdp"))}, 8),
        # sizes that are no multiple of the staging area's alignment,
        # dtypes of 1, 2 and 4 bytes, side by side in one piece
        "odd_sizes": ({"a": put((4, 3), P("fsdp")),
                       "b": put((8, 5), P("fsdp"), jnp.bfloat16),
                       "c": put((4, 7), P("fsdp"), np.int8),
                       "d": put((12,), P("fsdp")),
                       "e": put((5, 4, 3), P(None, "fsdp"))}, 20),
        # every index held twice: each is written once
        "partly_replicated": (
            {"w": put((8, 9), P("fsdp"), on=two_by_four)}, 4),
        "replicated": ({"w": put((8, 6), P()),
                        "host": np.arange(5.0)}, 0),
        # a narrow leaf, then a wider one: the join's threads grow
        "widths_differ": (
            {"a": put((6, 3), P("fsdp"),
                      on=Mesh(np.array(jax.devices()[:2]), ("fsdp",))),
             "b": put((16, 5), P(("data", "fsdp")), on=two_by_four)}, 10),
    }


@pytest.mark.parametrize("case", ["first_dim", "later_dim", "odd_sizes",
                                  "partly_replicated", "replicated",
                                  "widths_differ"])
def test_a_joined_leaf_equals_np_asarray_bit_for_bit(monkeypatch, case):
    op = _operator(monkeypatch, 4)
    params, shards = _sharded_cases(op._mesh)[case]
    op.params, op.model_state, op.opt_state = params, {}, {}
    piece, (counts,) = _traced_d2h(lambda: op.state_piece(0, 1 << 30))
    arrays = jax.tree.leaves(params)
    want = [np.asarray(x) for x in arrays]
    got = piece["leaves"][-len(arrays):]    # after epoch, global_step
    assert _bits(got) == _bits(want)
    joined = [x.nbytes for x in arrays
              if isinstance(x, jax.Array) and not x.is_fully_replicated]
    assert counts["bytes"] == sum(x.nbytes for x in arrays)
    assert counts["staged_bytes"] == sum(joined)
    assert counts["shards"] == shards
    if case == "replicated":    # nothing to join: no staging area at all
        assert op._stage is None
        return
    for x, leaf in zip(arrays, got):
        assert np.shares_memory(leaf, op._stage)
        assert leaf.flags.aligned and leaf.dtype == x.dtype
    # side by side, not on top of each other
    assert sum(np.shares_memory(a, b) for a in got for b in got) == len(got)


def test_state_dict_is_the_callers_whatever_is_pulled_later(monkeypatch):
    op = _operator(monkeypatch, 4)
    tokens = _tiny_pieces()[-1]
    op.train_batch(tokens)
    kept = op.state_dict()
    bits = _bits(kept)
    for _ in range(2):          # two later pulls, of a state that moved
        op.train_batch(tokens)
        copies, _ = _pull(op, usable=1 << 19)
        assert _bits(copies) != bits
    assert op._stage is not None
    assert _bits(kept) == bits
    for x in jax.tree.leaves(kept):
        if isinstance(x, np.ndarray):
            assert not np.shares_memory(x, op._stage)


def test_one_staging_area_serves_every_piece_of_every_call(monkeypatch):
    from ray_tpu.train.operator import _stage_room

    op = _operator(monkeypatch, 4)
    tokens = _tiny_pieces()[-1]
    usable = 1 << 19            # the 1.5 MB state in several pieces
    areas = []
    for _ in range(3):
        op.train_batch(tokens)
        (copies, views), parts = _traced_d2h(lambda: _pull(op, usable))
        assert len(parts) == len(views) > 4
        areas.append(op._stage)
        # what each piece was when it arrived is what state_dict gives
        assert _bits(copies) == _bits(op.state_dict())
        # ... and every piece's views lie in the one area, so an early
        # piece's are NOT good after a later piece (the lifetime rule)
        arrays = [x for leaves in views for x in leaves
                  if isinstance(x, np.ndarray)]
        staged = [x for x in arrays if np.shares_memory(x, op._stage)]
        # all but the optimizer's step counts (scalars: nothing to join)
        assert sum(x.nbytes for x in arrays if x.ndim) == sum(
            x.nbytes for x in staged) > 1 << 20
        assert _bits([x for v in views for x in v]) != _bits(copies)
        assert sum(p["staged_bytes"] for p in parts) == sum(
            x.nbytes for x in staged)
        assert all(p["staged_bytes"] <= p["bytes"] for p in parts)
    assert areas[1] is areas[0] and areas[2] is areas[0]
    from ray_tpu.train import snapshot as snapshot_mod

    room = _stage_room(snapshot_mod.cut(op._state_tree(), usable))
    assert areas[0].nbytes == room <= usable


# ---------------------------------------------------------------------
# the transfers of the two pieces after the one waited for are in flight
# ---------------------------------------------------------------------

def _issues(monkeypatch, op, usable):
    """A spy on `_start_transfers`: for every call, the pieces (of the
    cut for `usable`) whose leaves it was handed, in order."""
    whole = snapshot.cut(op._state_tree(), usable)
    piece_of = {id(x): i for i, (a, b) in enumerate(whole.ranges)
                for x in whole.leaves[a:b]}
    real, seen = operator_mod._start_transfers, []

    def spy(leaves, counts):
        pieces = [piece_of[id(x)] for x in leaves]
        seen.append(sorted(set(pieces)))
        assert pieces == sorted(pieces)     # the order the link takes
        return real(leaves, counts)

    monkeypatch.setattr(operator_mod, "_start_transfers", spy)
    return seen, len(whole.ranges)


@pytest.mark.parametrize("chips", [4, 1])
def test_two_pieces_after_the_one_waited_for_are_in_flight(
        monkeypatch, chips):
    """Call k issues pieces k, k + 1 and k + 2 at once, in that order
    and BEFORE it waits for piece k; what arrives is `state_dict()` bit
    for bit, joined leaves included."""
    op = _operator(monkeypatch, chips)
    op.train_batch(_tiny_pieces()[-1])      # fresh arrays: none fetched
    usable = 1 << 19
    seen, pieces = _issues(monkeypatch, op, usable)
    whole = snapshot.cut(op._state_tree(), usable)
    fetched = []        # per issue: were the first piece's leaves here?
    real = operator_mod._start_transfers

    def spy(leaves, counts):
        if len(fetched) < pieces:       # the pull's; not `state_dict`'s
            first, stop = whole.ranges[len(fetched)]
            fetched.append([x._npy_value is not None
                            for x in whole.leaves[first:stop]
                            if isinstance(x, jax.Array)])
        return real(leaves, counts)

    monkeypatch.setattr(operator_mod, "_start_transfers", spy)
    copies, _ = _pull(op, usable)
    assert pieces > 4 and _bits(copies) == _bits(op.state_dict())
    assert seen[:pieces] == [list(range(k, min(k + 3, pieces)))
                             for k in range(pieces)]
    assert len(fetched) == pieces and not any(map(any, fetched))
    assert (op._stage is not None) == (chips == 4)


def test_a_pull_abandoned_midway_then_a_step_then_a_whole_pull(monkeypatch):
    """The step donates arrays whose transfers are in flight: no error,
    and the next pull brings the state the step left."""
    op = _operator(monkeypatch, 4)
    tokens = _tiny_pieces()[-1]
    op.train_batch(tokens)
    usable = 1 << 19
    before = _bits(op.state_dict())
    for index in (0, 1):        # pieces 2 and 3 are on their way
        op.state_piece(index, usable)
    op.train_batch(tokens)
    copies, _ = _pull(op, usable)
    assert _bits(copies) == _bits(op.state_dict()) != before
    # ... and so after a load in the middle of a pull
    op.state_piece(0, usable)
    op.load_state_dict(op.state_dict())
    copies, _ = _pull(op, usable)
    assert _bits(copies) == _bits(op.state_dict())


def test_pieces_asked_out_of_order_are_still_the_state(monkeypatch):
    """Nothing is kept from one call to the next but transfers that have
    been started: any piece of any cut can be asked at any time."""
    op = _operator(monkeypatch, 4)
    op.train_batch(_tiny_pieces()[-1])
    leaves = jax.tree.leaves(op.state_dict())
    first = op.state_piece(0, 1 << 19)
    for index, usable in ((3, 1 << 19), (1, 1 << 20), (2, 1 << 19)):
        piece, (count,) = _traced_d2h(
            lambda: op.state_piece(index, usable))
        at = piece["first"]
        assert count["piece"] == index and count["wait_s"] > 0
        assert _bits(piece["leaves"]) == _bits(
            leaves[at:at + len(piece["leaves"])])
    assert len(first["ranges"]) > 4


def test_a_state_of_one_piece_issues_its_own_leaves_and_no_thread(
        monkeypatch):
    import threading

    op = _operator(monkeypatch, 4)
    op.train_batch(_tiny_pieces()[-1])
    op.state_piece(0, 1 << 30)      # the join's threads are there from now
    seen, pieces = _issues(monkeypatch, op, 1 << 30)
    threads = threading.active_count()
    piece, (count,) = _traced_d2h(lambda: op.state_piece(0, 1 << 30))
    assert pieces == len(piece["ranges"]) == 1 and seen == [[0]]
    assert threading.active_count() == threads
    assert count["start_s"] + count["wait_s"] + count["join_s"] > 0
    assert _bits(piece["leaves"]) == _bits(jax.tree.leaves(op.state_dict()))


def test_a_sharded_state_of_several_pieces_reaches_the_driver_intact(
        host, monkeypatch):
    """The `wide` case on a lease of four chips: 30 MiB of four-way
    sharded leaves through an 8 MiB arena and ONE staging area. Compared
    only after the last piece arrived, so a piece written over before
    its put was done would show."""
    tr = Trainer(Wide, num_workers=1, use_tpu=True, config={"seed": 5},
                 resources_per_worker={"CPU": 1, "TPU": 4})
    try:
        for _ in range(3):
            tr.train()
        entry = call_log()[-1]
        pulled = tr._last_state
        again = tr.state_dict()
    finally:
        tr.shutdown(force=True)
    (snap,) = _span(entry, "train.snapshot")
    parts = _span(entry, "train.snapshot.d2h")
    assert snap["pieces"] == len(parts) > 4
    assert sum(p["bytes"] for p in parts) == snap["bytes"] > 2 * ARENA
    # the same operator in this process, on four devices too
    monkeypatch.setattr(operator_mod, "_leased_chips", lambda: 4)
    ref = Wide({"seed": 5}, 0, 1)
    for _ in range(3):
        ref.train_epoch()
    arrays = [x for x in jax.tree.leaves(ref._state_tree())
              if isinstance(x, jax.Array)]
    joined = [x for x in arrays if not x.is_fully_replicated]
    assert len(joined) >= 24    # weights and both moments; not the counts
    # staged + unstaged = bytes
    staged = sum(p["staged_bytes"] for p in parts)
    assert staged == sum(x.nbytes for x in joined)
    assert snap["bytes"] - staged == sum(
        x.nbytes for x in arrays if x.is_fully_replicated)
    assert sum(p["shards"] for p in parts) == 4 * len(joined)
    want = _bits(ref.state_dict())
    assert _bits(pulled) == want
    assert _bits(again) == want


def test_a_state_several_arenas_large_goes_back_in_pieces(wide):
    wide.train()
    saved = wide.state_dict()
    wide.train()
    assert _bits(wide.state_dict()) != _bits(saved)
    wide.load_state_dict(saved)
    assert wide._last_state is saved
    assert _bits(wide.state_dict()) == _bits(saved)
    # ... and the elastic restore takes the same road
    ray_tpu.kill(wide.workers[0])
    wide.train()
    assert _span(call_log()[-1], "train.epoch")[0]["attempts"] == 2
    assert wide._last_state["epoch"] == saved["epoch"] + 1


def test_a_piece_that_raises_changes_nothing(wide, monkeypatch):
    wide.train()
    wide.train()
    before, owned = wide._last_state, wide._owned
    bits = _bits(before)
    real, calls = np.copyto, []

    def copyto(dst, src, *a, **kw):
        calls.append(dst)
        if len(calls) == 9:     # in a later piece: earlier ones are whole
            raise MemoryError("injected: a piece's copy-out failed")
        return real(dst, src, *a, **kw)

    with monkeypatch.context() as m:
        m.setattr(np, "copyto", copyto)
        with pytest.raises(MemoryError, match="injected"):
            wide.train()
    assert wide._last_state is before and wide._owned is owned
    assert _bits(wide._last_state) == bits
    wide.train()                # the half-written spare is a destination
    copies = _span(call_log()[-1], "train.snapshot.copy")
    assert all(c["reused_bytes"] == c["bytes"] for c in copies)
    assert _bits(wide.state_dict()) == _bits(wide._last_state)


def test_a_state_under_one_budget_crosses_in_one_piece(host):
    tr = Trainer(Small, num_workers=1)
    try:
        for _ in range(3):
            tr.train()
        entry = call_log()[-1]
        (snap,) = _span(entry, "train.snapshot")
        (copy,) = _span(entry, "train.snapshot.copy")
        assert snap == {"deferred": 0, "of_call": 3, "pieces": 1,
                        "bytes": copy["bytes"]}
        assert copy["reused_bytes"] == copy["bytes"] > 100 * 1024
        for name in ("train.snapshot.d2h", "object.return_put",
                     "object.get"):
            assert len(_span(entry, name)) == 1, name
    finally:
        tr.shutdown(force=True)


def test_a_state_the_store_holds_whole_crosses_in_pieces_too(host):
    """Between one budget and what the store holds before it spills the
    state is cut like a larger one, so that link, put and copy overlap
    (GPT-2-small's 1.39 GiB in the default 2 GiB store)."""
    cw = global_state.require_core_worker()
    used, usable = cw.store.stats()["used"], snapshot.usable_bytes(cw)
    tr = Trainer(Small, num_workers=1, config={"weights": 4})
    try:
        for _ in range(3):
            tr.train()
        entry = call_log()[-1]
        (snap,) = _span(entry, "train.snapshot")
        assert usable // snapshot.PIECE_SHARE < snap["bytes"] < usable
        assert snap["pieces"] > 1
        for name in ("train.snapshot.d2h", "train.snapshot.wait",
                     "train.snapshot.copy"):
            assert sorted(s["piece"] for s in _span(entry, name)) == list(
                range(snap["pieces"])), name
        copies = _span(entry, "train.snapshot.copy")
        assert sum(c["bytes"] for c in copies) == snap["bytes"]
        assert all(c["reused_bytes"] == c["bytes"] for c in copies)
        # the worker's own state, whole, by the road that cuts nothing
        pulled = tr._last_state
        want = _bits(ray_tpu.get(tr.workers[0].state_dict.remote()))
        assert _bits(pulled) == want
        assert _bits(tr.state_dict()) == want
        # the reader still tiles the call along the driver's thread
        path = boundary_path.call_path(entry)
        tiles = (_named(entry, "train.snapshot.wait")
                 + _named(entry, "train.snapshot.copy"))
        assert path["pieces"] == snap["pieces"]
        assert path["wait_s"] + path["get_s"] + path["copy_s"] == (
            pytest.approx(sum(s["end"] - s["start"] for s in tiles)))
        # ... and what is left of the boundary is hops, not a gap
        assert 0 <= path["hops_s"] < scale_timeout(0.25)
        # back the same way: load_state_dict, then the elastic restore
        saved = tr.state_dict()
        tr.train()
        assert _bits(tr.state_dict()) != _bits(saved)
        tr.load_state_dict(saved)
        assert _bits(tr.state_dict()) == _bits(saved)
        assert _bits(ray_tpu.get(
            tr.workers[0].state_dict.remote())) == _bits(saved)
        ray_tpu.kill(tr.workers[0])
        tr.train()
        assert _span(call_log()[-1], "train.epoch")[0]["attempts"] == 2
        assert tr._last_state["epoch"] == saved["epoch"] + 1
        # nothing of all that is left pinned in the arena
        pulled = want = saved = None
        _wait_released(used)
    finally:
        tr.shutdown(force=True)


def test_the_benchmarks_new_readers(wide):
    """`state_shard_share` and `snapshot_pieces` read the call log the
    way the benchmark does; a log without the counts gives None."""
    from benchmark.layer_metrics import snapshot_pieces, state_shard_share

    wide.train()
    wide.train()
    log = call_log()[-2:]
    host = {"attempted": len(call_log()), "calls": [
        {"wall_s": s["end"] - s["start"]} for e in log
        for s in e["spans"] if s["name"] == "train.call"]}
    # positions 2.. of the log are the window's: drop what came before
    import ray_tpu.train.trainer as trainer_mod
    kept = list(trainer_mod._call_log)
    try:
        trainer_mod._call_log.clear()
        trainer_mod._call_log.extend(kept[-4:])
        host["attempted"] = 4
        assert snapshot_pieces.read(host, None) > 4
        assert state_shard_share.read(host, None) == 100.0
    finally:
        trainer_mod._call_log.clear()
        trainer_mod._call_log.extend(kept)
    assert snapshot_pieces.read({"attempted": 0, "calls": []}, None) is None


def test_collective_time_share_reads_operations_by_name():
    from benchmark.layer_metrics import collective_time_share

    trace = {"busy_s": 2.0, "op_self_s": {
        "all-gather.3": 0.1, "all-gather-start.1": 0.02,
        "all-gather-done.1": 0.08, "all-reduce.7": 0.1,
        "reduce-scatter.2": 0.05, "collective-permute-done": 0.03,
        "all-to-all.1": 0.02, "async-collective-start": 0.01,
        "async-collective-done": 0.03, "fusion.12": 1.0,
        "flash_fwd.3": 0.5, "reduce.4": 0.1, "async-copy-done": 0.2}}
    assert collective_time_share.read({}, trace) == pytest.approx(22.0)
    assert collective_time_share.read(
        {}, {"busy_s": 1.0, "op_self_s": {"fusion": 1.0}}) == 0.0
    assert collective_time_share.read({}, None) is None


def test_a_return_nobody_waits_for_leaves_the_store(host):
    """The driver asks pieces ahead and may drop them unread (a piece
    that raised): a plasma return whose last ref went while the task
    ran is freed when the reply comes, not kept for ever."""
    @ray_tpu.remote
    def slow_megabyte():
        time.sleep(0.5)
        return np.ones(1 << 20, np.uint8)

    used = global_state.require_core_worker().store.stats()["used"]
    ref = slow_megabyte.remote()
    del ref
    time.sleep(1.0)
    _wait_released(used, seconds=19)
