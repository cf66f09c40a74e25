"""The state pull beside the NEXT epoch (train/trainer.py
`_epoch_beside_pull`, train/operator.py `_hold` / `_room_to_hold` /
`_held_part`, `TrainWorker.task_lane`). Where the worker's devices have
room for a second copy of the state, the worker holds one at the epoch's
end, `train()` returns without pulling, and the next call pulls the held
copy while its own epoch runs; where they have room for a part, the
pieces at the cut's tail that fit are held and pulled so, and only the
pieces before them at once. CPU: `memory_stats()` is None there, so the
room rule is reached through its one seam, `_device_memory`, which
`Roomy` fakes from its config; everything else is the program's own
path: two processes, the object store, the actor's lanes."""

import pickle
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu
from jax._src import monitoring
from benchmark import boundary_path
from benchmark.layer_metrics import snapshot_held_share
from benchmark.layer_metrics import snapshot_hidden_share
from ray_tpu import exceptions as exc
from ray_tpu.train import Trainer, TrainingOperator, call_log
from ray_tpu.train import operator as operator_mod
from ray_tpu.train import snapshot as snapshot_mod
from ray_tpu.train import trainer as trainer_mod

GIB = 1 << 30
STEPS = [1, 2, 2, 1, 2, 2]      # a call's steps, call by call

# what one `train()` call of the parent records, by name (a first call
# also resolves its step: FIRST_CALL_ONLY)
PARENT_SPANS = {
    "train.call", "train.epoch", "train.snapshot", "task", "task.e2e",
    "task.queue_wait", "train.dispatch", "train.sync",
    "train.snapshot.wait", "train.snapshot.d2h", "object.return_put",
    "object.get", "train.snapshot.copy"}


FIRST_CALL_ONLY = {"jax.compile"}


class Roomy(TrainingOperator):
    """Four (256, 256) weights under adam — 3 MiB of state, two pieces
    through an 8 MiB store — on a device whose memory statistics are
    `config["memory"]` (a dict, `None`, or absent: the backend's own)."""

    def setup(self, config):
        import optax

        def model_init(rng):
            return {f"w{i}": jax.random.normal(key, (256, 256)) / 16
                    for i, key in enumerate(jax.random.split(rng, 4))}

        def loss_fn(params, batch):
            x = batch
            for i in range(4):
                x = jnp.tanh(x @ params[f"w{i}"])
            return jnp.mean(x ** 2)

        self.register(model_init=model_init, loss_fn=loss_fn,
                      optimizer=optax.adam(1e-2))
        self.register_data(
            train_loader=[np.ones((4, 256), np.float32)] * 4)

    def _device_memory(self):
        if "memory" in self.config:
            return [self.config["memory"]]
        return super()._device_memory()


ROOM = {"bytes_limit": 16 * GIB, "peak_bytes_in_use": 10 * GIB,
        "bytes_in_use": 2 * GIB, "bytes_reserved": 6 * GIB}
FULL = dict(ROOM, peak_bytes_in_use=15 * GIB)
MIB = 1 << 20
STATE = 3 * MIB + 4             # Roomy's: 12 leaves of 256 KiB and a count


def _room(nbytes):
    """A device that has `nbytes` of room by the rule."""
    return {"bytes_limit": 16 * GIB, "bytes_in_use": 0, "bytes_reserved": 0,
            "peak_bytes_in_use": 15 * GIB - nbytes}


# the second of the two pieces (six leaves) fits; not one leaf fits
PART, SLIVER = _room(7 * MIB // 4), _room(MIB // 8)


@pytest.fixture(scope="module")
def runtime():
    # (four declared chips: one test's worker leases them all)
    ray_tpu.init(num_cpus=4, num_tpus=4, object_store_memory=8 << 20)
    try:
        yield
    finally:
        ray_tpu.shutdown()


def _trainer(memory="absent", **kw):
    config = {} if memory == "absent" else {"memory": memory}
    return Trainer(Roomy, num_workers=1, config=config, **kw)


def _bits(tree):
    return [(x.dtype.str, x.shape, x.tobytes()) if isinstance(x, np.ndarray)
            else x for x in jax.tree.leaves(tree)]


def _names(entry):
    return {s["name"] for s in entry["spans"]}


def _attrs(entry, name):
    return [s["attrs"] for s in entry["spans"] if s["name"] == name]


def _run(memory, steps=STEPS, kill_before=None, **kw):
    """-> per call (result epoch, installed epoch, installed bits), and
    the final `state_dict()`'s bits."""
    tr = _trainer(memory, **kw)
    try:
        rows = []
        for call, n in enumerate(steps, 1):
            if call == kill_before:
                ray_tpu.kill(tr.workers[0])
            out = tr.train(num_steps=n)
            rows.append((int(out["epoch"]), tr._last_state["epoch"],
                         _bits(tr._last_state)))
        return rows, _bits(tr.state_dict())
    finally:
        tr.shutdown(force=True)


@pytest.fixture(scope="module")
def immediate(runtime):
    """The parent's run: the backend's own (no) memory statistics."""
    return _run("absent")


@pytest.fixture(scope="module")
def deferred(runtime):
    rows = _run(ROOM)
    return rows, call_log()[-len(STEPS):]


@pytest.fixture(scope="module")
def part(runtime):
    """A device with room for the second of the state's two pieces."""
    rows = _run(PART)
    return rows, call_log()[-len(STEPS):]


# ---------------------------------------------------------------------
# the rule
# ---------------------------------------------------------------------

def _operator(memory, **config):
    if memory != "absent":
        config["memory"] = memory
    return Roomy(config, 0, 1)


@pytest.mark.parametrize("memory, room", [
    (ROOM, 5 * GIB),
    (FULL, 0),                                  # the peak leaves no room
    (dict(ROOM, bytes_in_use=9 * GIB), 0),      # live + reserved does
    # the margin is a share of the device, whatever the state's size
    (dict(ROOM, peak_bytes_in_use=15 * GIB - 2 * MIB,
          bytes_in_use=0, bytes_reserved=0), 2 * MIB),
    (dict(ROOM, peak_bytes_in_use=14 * GIB, bytes_in_use=0,
          bytes_reserved=0), GIB),
    (_room(STATE), STATE),                      # to the byte
    (_room(STATE - 1), STATE - 1),
    (PART, 7 * MIB // 4),
    (SLIVER, MIB // 8),
    (None, 0),                                  # the CPU: no count kept
    ({}, 0),
    ({"bytes_limit": 16 * GIB}, 0),             # ... or half a count
    ({"peak_bytes_in_use": GIB}, 0),
    ("absent", 0),                              # this backend's own
])
def test_the_rule_reads_the_devices_memory_and_nothing_else(memory, room):
    """The rule answers in bytes; a first epoch ends holding the whole
    state where that fits, and nothing where it does not: which pieces
    a part is made of nobody knows before the state has crossed once."""
    op = _operator(memory)
    assert op._room_to_hold() == room
    assert type(op._room_to_hold()) is int
    holds = room >= STATE
    out = op.train_epoch(num_steps=1)
    assert op._room == room
    assert op.holds_state is holds
    assert ("held_epoch" in out) is ("held_from" in out) is holds
    if holds:
        assert out["held_epoch"] == op.epoch == 1 and out["held_from"] == 0
        assert op._held_part() == (0, STATE)


def test_the_rule_is_read_once_after_the_first_epoch(monkeypatch):
    op = _operator(ROOM)
    reads = []
    real = Roomy._device_memory
    monkeypatch.setattr(Roomy, "_device_memory",
                        lambda self: reads.append(1) or real(self))
    assert op._room is None and not op.holds_state      # nothing yet
    for _ in range(3):
        op.train_epoch(num_steps=1)
    assert len(reads) == 1 and op._room == 5 * GIB


def _cut(op, usable):
    leaves = jax.tree.leaves(op._state_tree())
    sizes = [snapshot_mod.leaf_bytes(x) for x in leaves]
    return sizes, snapshot_mod.plan(sizes, usable)


@pytest.mark.parametrize("usable, room, pieces, held", [
    # a piece a leaf (12 of 256 KiB behind the first: the count's)
    (MIB, 0, 13, 0),
    (MIB, MIB // 4 - 1, 13, 0),             # not one piece fits
    (MIB, MIB // 4, 13, 1),                 # to the byte
    (MIB, MIB, 13, 4),
    (MIB, STATE - 1, 13, 12),               # all but the first
    (MIB, STATE, 13, 13),                   # ... and the whole
    # six leaves a piece, as through the tests' 8 MiB store
    (8 * MIB * 4 // 5, 3 * MIB // 2 - 1, 2, 0),
    (8 * MIB * 4 // 5, 3 * MIB // 2, 2, 1),
    (8 * MIB * 4 // 5, STATE - 1, 2, 1),    # whole pieces only
    (8 * MIB * 4 // 5, STATE, 2, 2),
    (64 * MIB, STATE - 1, 1, 0),            # one piece: all or nothing
])
def test_the_held_part_is_whole_pieces_at_the_tail_of_the_cut(
        usable, room, pieces, held):
    op = _operator(_room(room))
    op.train_epoch(num_steps=1)
    if room < STATE:
        assert not op.holds_state           # the cut is not known yet
    list(map(np.asarray, op.state_piece(0, usable)["leaves"]))
    sizes, ranges = _cut(op, usable)
    assert len(ranges) == pieces and sum(sizes) == STATE
    out = op.train_epoch(num_steps=1)
    if not held:
        assert op._held_part() is None and not op.holds_state
        assert "held_epoch" not in out and "held_from" not in out
        assert [k for k in op._step_cache if k[0] == "hold"] == []
        return
    first = ranges[pieces - held][0]
    assert out["held_epoch"] == 2 and out["held_from"] == first
    assert op._held_part() == (first, sum(sizes[first:]))
    assert sum(sizes[first:]) <= room
    if held < pieces:       # one piece more would not have fitted
        assert sum(sizes[ranges[pieces - held - 1][0]:]) > room
    kept = op._held
    assert kept["first"] == first and kept["sizes"] == sizes
    assert len(kept["leaves"]) == len(sizes) - first
    # one program, of the held leaves only: the whole state's where all
    # of it is held, else one named by where the part begins
    assert [k for k in op._step_cache if k[0] == "hold"] == [
        ("hold", f"from{first}" if first else "state")]
    live = _bits(op.state_dict())
    op.train_batch(np.ones((4, 256), np.float32))   # the next epoch
    got = []
    for index in range(pieces - held, pieces):
        assert op.holds_state
        got += op.state_piece(index, usable, (), 2)["leaves"]
    assert not op.holds_state               # read to its last piece: gone
    assert _bits(got) == live[first:] != _bits(op.state_dict())[first:]
    if held < pieces:
        # a piece before the part is an error, never the live state's
        # bytes (and, as any piece that raises, the end of the pull)
        op.train_epoch(num_steps=1)
        with pytest.raises(ValueError, match="is not held"):
            op.state_piece(pieces - held - 1, usable, (), 3)
        assert not op.holds_state and not op._pull_open


@pytest.mark.parametrize("why", ["several workers", "sharded update"])
def test_an_operator_that_does_not_own_its_whole_state_never_holds(
        monkeypatch, why):
    if why == "several workers":
        op = Roomy({"memory": ROOM}, 0, 2, group_name="none")
    else:
        op = Roomy({"memory": ROOM, "sharded_update": True}, 0, 1)
    assert op._room_to_hold() == 0
    assert op._hold() is False and not op.holds_state


def test_the_state_is_measured_on_its_fullest_device(monkeypatch):
    """Four devices hold a quarter each: what has to fit beside the
    step's peak is the quarter, and every device is asked."""
    monkeypatch.setattr(operator_mod, "_leased_chips", lambda: 4)
    op = _operator("absent")
    assert op._mesh is not None
    assert len(TrainingOperator._device_memory(op)) == 4
    quarter = op._layout_facts()["state_bytes_fullest_chip"]
    assert quarter < 0.3 * op._layout_facts()["state_bytes"]
    limit = 64 * quarter
    tight = {"bytes_limit": limit, "bytes_in_use": 0, "bytes_reserved": 0,
             "peak_bytes_in_use": limit - limit // 16 - quarter}
    op.config["memory"] = tight
    op._room = op._room_to_hold()
    assert op._room == quarter
    assert op._held_part() == (0, op._layout_facts()["state_bytes"])
    op.config["memory"] = dict(tight, peak_bytes_in_use=tight[
        "peak_bytes_in_use"] + 1)
    op._room = op._room_to_hold()
    assert op._room == quarter - 1 and op._held_part() is None


# ---------------------------------------------------------------------
# the held copy
# ---------------------------------------------------------------------

def test_the_held_copy_outlives_the_steps_that_donate_the_live_state():
    op = _operator(ROOM)
    op.train_epoch(num_steps=2)
    held = op._held
    before = _bits(op.state_dict())
    assert held["epoch"] == 1 and held["first"] == 0
    assert held["leaves"][:2] == [1, 2]     # the epoch, the global step
    live = jax.tree.leaves((op.params, op.opt_state))
    assert len(held["leaves"]) == 2 + len(live)
    assert not {id(x) for x in live} & {id(x) for x in held["leaves"]}
    for _ in range(2):      # the next epoch's steps, under way
        op.train_batch(np.ones((4, 256), np.float32))
    usable = 8 << 20
    first = op.state_piece(0, usable, (), 1)
    leaves = [np.array(x) if isinstance(x, np.ndarray) else x
              for x in first["leaves"]]
    for index in range(1, len(first["ranges"])):
        leaves += [np.array(x) if isinstance(x, np.ndarray) else x
                   for x in op.state_piece(index, usable, (), 1)["leaves"]]
    assert _bits(jax.tree.unflatten(first["treedef"], leaves)) == before
    assert _bits(op.state_dict()) != before     # the live state moved on


def test_a_copy_of_another_epoch_is_an_error_never_its_bytes():
    op = _operator(ROOM)
    op.train_epoch(num_steps=1)
    with pytest.raises(ValueError, match="no state is held of epoch 2"):
        op.state_piece(0, 8 << 20, (), 2)
    plain = _operator(FULL)
    plain.train_epoch(num_steps=1)
    with pytest.raises(ValueError, match="held: None"):
        plain.state_piece(0, 8 << 20, (), 1)


def test_an_epochs_end_waits_for_the_pull_of_the_last_copy(monkeypatch):
    """The pull may outlast its epoch: the copy it reads is not let go
    before its last piece has been read, or the driver has said so."""
    monkeypatch.setattr(operator_mod, "_HOLD_WAIT_S", 0.3)
    op = _operator(ROOM)
    op.train_epoch(num_steps=1)
    usable = 1 << 20            # a piece a leaf: several pieces
    first = op.state_piece(0, usable, (), 1)
    assert len(first["ranges"]) > 2 and op._pull_open
    with pytest.raises(RuntimeError, match="has not ended"):
        op.train_epoch(num_steps=1)
    assert op._held["epoch"] == 1       # still the copy being read
    done = threading.Timer(0.1, lambda: [
        op.state_piece(i, usable, (), 1)
        for i in range(1, len(first["ranges"]))])
    done.start()
    op._hold()                          # returns once the last is read
    done.join()
    assert not op._pull_open and op._held["epoch"] == op.epoch
    op.state_piece(0, usable, (), op.epoch)
    assert op._pull_open
    op.end_pull()                       # a driver that gave up
    op._hold()


def test_a_pull_announced_with_the_epoch_is_open_before_its_first_piece(
        monkeypatch):
    """However short the epoch: its end finds the pull open although no
    piece has arrived yet, and keeps the copy for it."""
    monkeypatch.setattr(operator_mod, "_HOLD_WAIT_S", 0.3)
    op = _operator(ROOM)
    op.train_epoch(num_steps=1)
    assert op.expect_pull(7) is False and not op._pull_open     # not held
    assert op.expect_pull(1) is True
    with pytest.raises(RuntimeError, match="has not ended"):
        op.train_epoch(num_steps=1)         # nobody pulls: bounded
    assert op._held["epoch"] == 1
    usable = 8 << 20
    late = threading.Timer(0.1, lambda: [
        op.state_piece(i, usable, (), 1) for i in range(2)])
    late.start()
    op._hold()                              # the first piece came late
    late.join()
    assert op._held["epoch"] == op.epoch == 2 and not op._pull_open


def test_the_copy_goes_with_its_last_piece_and_the_pieces_get_a_lane():
    worker = object.__new__(trainer_mod.TrainWorker)
    worker.operator = None
    assert worker.task_lane("state_piece") is None      # not set up yet
    op = worker.operator = _operator(ROOM)
    assert worker.task_lane("state_piece") is None      # nothing held
    op.train_epoch(num_steps=1)
    assert worker.task_lane("state_piece") == "pull"
    assert worker.task_lane("end_pull") == "pull"
    # the epoch stays where every epoch runs; so does everything else
    for name in ("train_epoch", "validate", "state_dict", "load_state_piece"):
        assert worker.task_lane(name) is None
    usable = 1 << 20
    count = len(op.state_piece(0, usable, (), 1)["ranges"])
    for index in range(1, count):
        assert op.holds_state and op._pull_open
        op.state_piece(index, usable, (), 1)
    assert not op.holds_state and not op._pull_open     # read: gone
    assert worker.task_lane("state_piece") is None
    plain = worker.operator = _operator(FULL)
    plain.train_epoch(num_steps=1)
    assert worker.task_lane("state_piece") is None      # no room: never


def test_loading_a_state_drops_the_copy_of_the_one_it_replaces():
    op = _operator(ROOM)
    op.train_epoch(num_steps=1)
    state = op.state_dict()
    op.train_epoch(num_steps=1)
    assert op.holds_state
    op.load_state_dict(state)
    assert not op.holds_state and op.epoch == 1


# ---------------------------------------------------------------------
# a cell with no room runs the parent's programs and nothing else
# ---------------------------------------------------------------------

def _fused(op):
    ((name, shape), fn), = [(k, v) for k, v in op._step_cache.items()
                            if k[0] == "fused"]
    return fn


def _built(memory, steps=(2, 2)):
    """An operator and the programs JAX compiled (or loaded) for it:
    in its set-up and first epoch, and in its second epoch."""
    built = []

    def count(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            built[-1] += 1

    jax.monitoring.register_event_duration_secs_listener(count)
    try:
        built.append(0)
        op = _operator(memory)
        op.train_epoch(num_steps=steps[0])
        built.append(0)
        op.train_epoch(num_steps=steps[1])
    finally:
            monitoring.unregister_event_duration_listener(count)
    return op, built


def _step_key(op, batch):
    """What JAX keys the fused step by, beside its shapes: the traced
    computation."""
    return str(jax.make_jaxpr(op._fused_step)(
        op.params, op.model_state, op.opt_state, batch))


def test_with_no_room_the_step_is_the_parents_and_no_program_is_added():
    """The regression test for PR 51's refusal: an operator that holds
    nothing builds the fused step and nothing else, no second lowering
    of it either, and the step of one that holds is the same program
    under the same key: one program more, the copy, built in the FIRST
    epoch."""
    batch = np.ones((4, 256), np.float32)
    _built(FULL)                    # this process's first: jax's own too
    # (built from one line: a program's text carries its call sites)
    (plain, built_plain), (holder, built_holder), (sliver, built_sliver) = [
        _built(memory) for memory in (FULL, ROOM, SLIVER)]
    assert list(plain._step_cache) == [("fused", "4x256")]
    assert plain._held is None and not plain.holds_state
    assert built_plain[1] == 0      # nothing in a second epoch
    # room for less than a piece is no room: the same, to the program
    assert list(sliver._step_cache) == [("fused", "4x256")]
    assert sliver._held is None and built_sliver == built_plain
    assert sorted(holder._step_cache) == [("fused", "4x256"),
                                          ("hold", "state")]
    assert built_holder == [built_plain[0] + 1, 0]
    # the step: the same computation recorded under the same key (its
    # name and shape class), compiled to the same text, donating what it
    # donated; the copy donates nothing
    assert _step_key(holder, batch) == _step_key(plain, batch)
    texts = [op.compiled_step_text(batch) for op in (holder, plain)]
    assert texts[0] == texts[1]
    assert _fused(holder).key == _fused(plain).key \
        == "train.step:fused:4x256"
    assert _fused(holder).donate_argnums == (0, 2) \
        == _fused(plain).donate_argnums
    assert holder._step_cache[("hold", "state")].donate_argnums == ()


@pytest.mark.parametrize("memory", ["absent", None, FULL, SLIVER])
def test_with_no_room_a_call_has_exactly_the_parents_spans(runtime, memory):
    tr = _trainer(memory)
    try:
        for n in (1, 2, 2):
            tr.train(num_steps=n)
        assert tr._pending is None
        assert _bits(tr._last_state) == _bits(tr.state_dict())
    finally:
        tr.shutdown(force=True)
    first, *later = call_log()[-3:]
    assert _names(first) - FIRST_CALL_ONLY == PARENT_SPANS
    for call, entry in enumerate(later, 2):
        assert _names(entry) == PARENT_SPANS
        (snap,) = _attrs(entry, "train.snapshot")
        assert snap["deferred"] == 0 and snap["of_call"] == call
        assert _attrs(entry, "train.call")[0]["call"] == call
        # every task ran on the actor's one lane: one after the other
        tasks = sorted((s["start"], s["end"]) for s in entry["spans"]
                       if s["name"] == "task")
        assert all(a[1] <= b[0] for a, b in zip(tasks, tasks[1:]))


# ---------------------------------------------------------------------
# with room: the same snapshots, a call later
# ---------------------------------------------------------------------

@pytest.mark.parametrize("held", ["deferred", "part"])
def test_deferred_and_immediate_pulls_give_the_same_snapshots(
        immediate, held, request):
    (rows, final), _ = request.getfixturevalue(held)
    plain, plain_final = immediate
    assert [r[0] for r in rows] == [r[0] for r in plain] == [1, 2, 3, 4, 5, 6]
    by_epoch = {epoch: bits for _, epoch, bits in plain}
    # the first call pulls at once; from then on a call ends with the
    # call before's snapshot: byte for byte what the parent installed
    assert [r[1] for r in rows] == [1, 1, 2, 3, 4, 5]
    for _, epoch, bits in rows:
        assert bits == by_epoch[epoch]
    assert final == plain_final == by_epoch[6]   # state_dict(): current


def test_a_deferred_call_pulls_the_last_calls_state_beside_its_epoch(
        deferred):
    _, entries = deferred
    first, second, *later = entries
    assert _attrs(first, "train.snapshot") == [
        dict(_attrs(first, "train.snapshot")[0], deferred=0, of_call=1)]
    assert "train.hold" in _names(first)        # ... and built in call 1
    assert "train.snapshot" not in _names(second)   # returns, unpulled
    assert _names(second) == {"train.call", "train.epoch", "task",
                              "task.e2e", "task.queue_wait",
                              "train.dispatch", "train.sync", "train.hold"}
    for call, entry in enumerate(later, 3):
        assert _names(entry) == PARENT_SPANS | {"train.hold"}
        (snap,) = _attrs(entry, "train.snapshot")
        assert snap["deferred"] == 1 and snap["of_call"] == call - 1
        assert snap["pieces"] == 2
        (hold,) = _attrs(entry, "train.hold")
        assert hold["epoch"] == call
        assert hold["held_bytes"] == hold["bytes"] == snap["bytes"]
        # the epoch's task and the pieces' overlap: two lanes
        by = {s["span"]: s for s in entry["spans"]}
        epoch_task, = [s for s in entry["spans"] if s["name"] == "task"
                       and s["attrs"]["name"] == "TrainWorker.train_epoch"]
        pieces = [s for s in entry["spans"] if s["name"] == "task"
                  and s["attrs"]["name"] == "TrainWorker.state_piece"]
        assert len(pieces) == 2
        assert pieces[0]["start"] < epoch_task["end"]
        assert pieces[0]["end"] <= pieces[1]["start"]   # in order
        # the hold came after the last piece was read (its put may
        # still run: the piece's leaves are on the host by then)
        hold_span, = [s for s in entry["spans"] if s["name"] == "train.hold"]
        read = max(s["end"] for s in entry["spans"]
                   if s["name"] == "train.snapshot.d2h")
        assert hold_span["end"] >= read - 1e-3
        # the tree is one tree: every span but the root has its parent
        assert all(s["parent"] in by for s in entry["spans"]
                   if s["name"] != "train.call")


def test_a_part_held_call_pulls_the_rest_at_once_and_the_part_beside_the_next(
        part):
    _, entries = part
    first, second, *later = entries
    # call 1: everything at once, nothing held (the cut is not known)
    assert _names(first) - FIRST_CALL_ONLY == PARENT_SPANS
    (snap,) = _attrs(first, "train.snapshot")
    assert (snap["deferred"], snap["pieces"], snap["bytes"]) == (0, 2, STATE)
    tail = 3 * MIB // 2         # the second piece: six leaves
    for call, entry in enumerate([second] + later, 2):
        (hold,) = _attrs(entry, "train.hold")
        assert (hold["epoch"], hold["bytes"], hold["held_bytes"]) == (
            call, STATE, tail)
        *beside, now = _attrs(entry, "train.snapshot")
        # this call's state: the first piece from the live state, at
        # once; nothing installed by it
        assert (now["deferred"], now["of_call"], now["pieces"],
                now["bytes"]) == (0, call, 1, STATE - tail)
        if call == 2:
            assert beside == []
            continue
        # ... and the last call's: the held piece, beside the epoch
        (then,) = beside
        assert (then["deferred"], then["of_call"], then["pieces"],
                then["bytes"]) == (1, call - 1, 1, tail)
        assert _names(entry) == PARENT_SPANS | {"train.hold"}
        epoch_task, = [s for s in entry["spans"] if s["name"] == "task"
                       and s["attrs"]["name"] == "TrainWorker.train_epoch"]
        pieces = [s for s in entry["spans"] if s["name"] == "task"
                  and s["attrs"]["name"] == "TrainWorker.state_piece"]
        assert len(pieces) == 2
        assert pieces[0]["start"] < epoch_task["end"] <= pieces[1]["start"]
        d2h = sorted(_attrs(entry, "train.snapshot.d2h"),
                     key=lambda a: a["piece"])
        assert [(a["piece"], a["bytes"]) for a in d2h] == [
            (0, STATE - tail), (1, tail)]
        # the benchmark's tiling of the driver's thread still reads a
        # row a piece: the two parts' pieces carry different indexes
        # (the held piece's wait lies beside the epoch, not behind it)
        path = boundary_path.call_path(entry)
        assert path["pieces"] == 2 and path["boundary_s"] > 0
        by = {s["span"]: s for s in entry["spans"]}
        assert all(s["parent"] in by for s in entry["spans"]
                   if s["name"] != "train.call")


@pytest.mark.parametrize("memory", [ROOM, PART], ids=["whole", "part"])
def test_the_installed_snapshot_during_a_call_and_after_it(
        runtime, monkeypatch, memory):
    """While call k + 1 runs, the installed snapshot is call k - 1's
    until the pull of call k's lands; call k's after it — also where a
    call has pulled a part of its state at once: that installs nothing."""
    seen = []
    real = Trainer._pull_state

    def pull(self, *a, **kw):
        seen.append((self._calls, self._last_state["epoch"],
                     self._snapshot_of, kw.get("of_epoch")))
        return real(self, *a, **kw)

    tr = _trainer(memory)
    try:
        tr.train(num_steps=1)
        monkeypatch.setattr(Trainer, "_pull_state", pull)
        for call in (2, 3, 4):
            tr.train(num_steps=1)
            assert tr._last_state["epoch"] == tr._snapshot_of == call - 1
            assert tr._pending[:3] == (call, call, 1)
            if memory is ROOM:
                assert tr._pending.pull is None
            else:       # what crossed at once waits in the older set
                assert tr._pending.pull.pieces == 1
                assert not tr._pending.pull.whole
                assert tr._pending.pull.spare is tr._owned[1]
    finally:
        tr.shutdown(force=True)
    # (call, installed epoch, installed call, copy asked for) at the pull
    assert [s for s in seen if s[3] is not None] == [(3, 1, 1, 2),
                                                     (4, 2, 2, 3)]
    assert [s for s in seen if s[3] is None] == (
        [] if memory is ROOM else [(2, 1, 1, None), (3, 2, 2, None),
                                   (4, 3, 3, None)])


@pytest.mark.parametrize("memory", [ROOM, PART], ids=["whole", "part"])
@pytest.mark.parametrize("how", ["state_dict", "save", "shutdown"])
def test_what_a_caller_asks_for_is_never_stale(runtime, tmp_path, how,
                                               memory):
    tr = _trainer(memory)
    try:
        for _ in range(3):
            tr.train(num_steps=1)
        assert tr._pending.call == 3 and tr._last_state["epoch"] == 2
        if how == "state_dict":
            got = tr.state_dict()
        elif how == "save":
            with open(tr.save(str(tmp_path / "ckpt")), "rb") as f:
                got = pickle.load(f)
        else:
            tr.shutdown()
            got = tr._last_state
        assert got["epoch"] == 3 and got["global_step"] == 3
        assert tr._pending is None and tr._snapshot_of == 3
        assert _bits(tr._last_state) == _bits(got)
        if how != "shutdown":           # ... and the run goes on deferred
            tr.train(num_steps=1)
            assert tr._pending.call == 4 and tr._last_state["epoch"] == 3
    finally:
        tr.shutdown(force=True)


@pytest.mark.parametrize("memory", [ROOM, PART], ids=["whole", "part"])
def test_loading_a_state_drops_the_pending_pull(runtime, memory):
    tr = _trainer(memory)
    try:
        tr.train(num_steps=1)
        first = tr.state_dict()
        tr.train(num_steps=2)
        assert tr._pending is not None
        # a part pulled at once took its leaves' bytes of the older
        # set's reservation, which nothing has landed in before
        taken = tr._owned[1].reserve.taken
        assert (taken > 0) is (memory is PART)
        tr.load_state_dict(first)
        assert tr._pending is None and tr._owned[1].reserve.taken == 0
        out = tr.train(num_steps=2)     # no copy of epoch 2 is asked for
        assert int(out["epoch"]) == 2 and tr._last_state is first
        assert tr._pending[:3] == (3, 2, 2)
        assert tr.state_dict()["global_step"] == 3
    finally:
        tr.shutdown(force=True)


@pytest.mark.parametrize("memory, fails_at", [(ROOM, 9), (PART, 3)],
                         ids=["whole", "part"])
def test_a_deferred_pull_that_raises_installs_nothing(runtime, monkeypatch,
                                                      memory, fails_at):
    """... in the held copy's second piece (the whole state held) or in
    its only one (a part: the first crossed at the last call's end)."""
    tr = _trainer(memory)
    try:
        for _ in range(3):
            tr.train(num_steps=1)
        before, owned = tr._last_state, tr._owned
        bits = _bits(before)
        real, calls = np.copyto, []

        def copyto(dst, src, *a, **kw):
            calls.append(dst)
            if len(calls) == fails_at:
                raise MemoryError("injected: a piece's copy-out failed")
            return real(dst, src, *a, **kw)

        with monkeypatch.context() as m:
            m.setattr(np, "copyto", copyto)
            with pytest.raises(MemoryError, match="injected"):
                tr.train(num_steps=1)
        assert tr._last_state is before and tr._owned is owned
        assert _bits(tr._last_state) == bits and tr._snapshot_of == 2
        assert tr._pending is None      # with what had crossed at once
        # the epoch under way ended (the worker let go of the copy) and
        # the trainer goes on: the next call finds the snapshot two
        # calls behind and pulls at once
        out = tr.train(num_steps=1)
        assert int(out["epoch"]) == 5 and tr._last_state["epoch"] == 5
        assert _attrs(call_log()[-1], "train.snapshot")[0]["deferred"] == 0
        tr.train(num_steps=1)
        assert tr._pending.call == 6 and tr._last_state["epoch"] == 5
        assert _bits(tr.state_dict()) != bits
    finally:
        tr.shutdown(force=True)


@pytest.mark.parametrize("memory", [ROOM, PART], ids=["whole", "part"])
@pytest.mark.parametrize("kill_before", [3, 4, 6])
def test_a_worker_killed_between_hold_and_pull_reaches_the_straight_state(
        immediate, runtime, kill_before, memory):
    """The worker dies holding call k's state (or, the rest pulled at
    once, a part of it), unpulled: the restore goes back to call k - 1's
    snapshot and runs call k again, with call k's steps, then the call
    under way."""
    plain, plain_final = immediate
    by_epoch = {epoch: bits for _, epoch, bits in plain}
    rows, final = _run(memory, kill_before=kill_before, max_retries=2)
    assert [r[0] for r in rows] == [1, 2, 3, 4, 5, 6]
    assert final == plain_final
    for _, epoch, bits in rows:
        assert bits == by_epoch[epoch]
    installed = [r[1] for r in rows]
    k = kill_before
    # up to the kill a call behind; the call that met the dead worker
    # pulls at once (its snapshot was two calls behind); then as before
    assert installed[:k - 1] == [1, 1, 2, 3, 4, 5][:k - 1]
    assert installed[k - 1] == k
    assert installed[k:] == list(range(k, 6))
    entry = call_log()[-(len(STEPS) - k + 1)]
    assert _attrs(entry, "train.epoch")[0]["attempts"] == 2


@pytest.mark.parametrize("memory", [ROOM, PART], ids=["whole", "part"])
def test_a_worker_lost_outside_train_runs_the_unpulled_call_again(
        immediate, runtime, memory):
    """`validate()` (any call that restores the group) finds the worker
    dead while a pull is pending: the restore runs that call again."""
    plain, _ = immediate
    tr = _trainer(memory, max_retries=2)
    try:
        for n in STEPS[:3]:
            tr.train(num_steps=n)
        assert tr._pending[:3] == (3, 3, STEPS[2])
        ray_tpu.kill(tr.workers[0])
        tr._resize_worker_group()
        assert tr._pending is None and tr._snapshot_of == 2
        assert _bits(tr.state_dict()) == plain[2][2]    # call 3's state
        tr.train(num_steps=STEPS[3])
        assert _bits(tr._last_state) == plain[3][2]     # pulled at once
    finally:
        tr.shutdown(force=True)


def test_with_max_retries_0_a_dead_worker_is_the_callers_error(runtime):
    tr = _trainer(ROOM, max_retries=0)
    try:
        tr.train(num_steps=1)
        tr.train(num_steps=1)
        before = tr._last_state
        ray_tpu.kill(tr.workers[0])
        with pytest.raises((exc.ActorDiedError, exc.WorkerCrashedError)):
            tr.train(num_steps=1)
        assert tr._last_state is before
    finally:
        tr.shutdown(force=True)


# ---------------------------------------------------------------------
# one worker on four chips: the copy keeps the layout, the pieces join
# ---------------------------------------------------------------------

from tests.test_train_pieces import TinyGPT  # noqa: E402


class RoomyGPT(TinyGPT):
    def _device_memory(self):
        mine = TrainingOperator._device_memory(self)
        if not self.config.get("room"):
            return mine
        return [ROOM for _ in mine]


def test_a_state_sharded_over_four_chips_is_held_as_it_lies_and_pulled_late(
        runtime):
    runs = {}
    for room in (False, True):
        tr = Trainer(RoomyGPT, num_workers=1, use_tpu=True,
                     config={"room": room, "layers": 12},
                     resources_per_worker={"CPU": 1, "TPU": 4})
        try:
            rows = []
            for _ in range(4):
                out = tr.train(num_steps=2)
                rows.append((tr._last_state["epoch"],
                             _bits(tr._last_state),
                             out["last_train_loss"]))
            runs[room] = rows, _bits(tr.state_dict())
        finally:
            tr.shutdown(force=True)
    entry = call_log()[-1]
    (plain, plain_final), (held, held_final) = runs[False], runs[True]
    assert [r[0] for r in plain] == [1, 2, 3, 4]
    assert [r[0] for r in held] == [1, 1, 2, 3]
    assert [r[2] for r in held] == [r[2] for r in plain]    # the losses
    by_epoch = {epoch: bits for epoch, bits, _ in plain}
    assert all(bits == by_epoch[epoch] for epoch, bits, _ in held)
    assert held_final == plain_final
    # the last call: a deferred pull of several pieces, joined from the
    # four devices' shards in the staging area, on the mesh (1, 4)
    (snap,) = _attrs(entry, "train.snapshot")
    assert snap["deferred"] == 1 and snap["pieces"] > 2
    assert _attrs(entry, "train.dispatch")[0]["mesh"] == [1, 4]
    d2h = _attrs(entry, "train.snapshot.d2h")
    assert sum(d["staged_bytes"] for d in d2h) > 0.9 * snap["bytes"]
    (hold,) = _attrs(entry, "train.hold")
    assert hold["bytes"] == snap["bytes"]


# ---------------------------------------------------------------------
# the runtime's part: an actor names a lane for a call
# ---------------------------------------------------------------------

class Laned:
    def task_lane(self, method_name):
        return "side" if method_name == "slow" else None

    def slow(self, seconds, log):
        time.sleep(seconds)
        log.append("slow")
        return threading.current_thread().name, list(log)

    def fast(self):
        return threading.current_thread().name


def test_a_call_on_a_lane_runs_beside_the_actors_own_lane(runtime):
    actor = ray_tpu.remote(Laned).remote()
    try:
        ray_tpu.get(actor.fast.remote(), timeout=60)    # it has started
        first = actor.slow.remote(0.6, ["a"])
        second = actor.slow.remote(0.0, ["b"])
        t0 = time.monotonic()
        main = ray_tpu.get(actor.fast.remote(), timeout=30)
        assert time.monotonic() - t0 < 0.5      # not behind the slow one
        (lane, one), (lane2, two) = ray_tpu.get([first, second], timeout=30)
        assert lane == lane2 == "actor-lane-side_0" != main
        assert (one, two) == (["a", "slow"], ["b", "slow"])     # in order
        assert ray_tpu.get(actor.fast.remote(), timeout=30) == main
    finally:
        ray_tpu.kill(actor)


# ---------------------------------------------------------------------
# the benchmark's reader
# ---------------------------------------------------------------------

def _entry(epoch, snapshots, wall=None, holds=()):
    lo, hi = epoch
    spans = [{"name": "train.call", "start": 0.0,
              "end": wall if wall is not None else hi, "span": "r",
              "parent": None, "attrs": {}},
             {"name": "train.dispatch", "start": lo, "end": (lo + hi) / 2,
              "span": "d", "parent": "r", "attrs": {}},
             {"name": "train.sync", "start": (lo + hi) / 2, "end": hi,
              "span": "s", "parent": "r", "attrs": {}}]
    for i, (start, end, attrs) in enumerate(snapshots):
        spans.append({"name": "train.snapshot", "start": start, "end": end,
                      "span": f"p{i}", "parent": "r", "attrs": attrs})
    for i, attrs in enumerate(holds):
        spans.append({"name": "train.hold", "start": hi, "end": hi,
                      "span": f"h{i}", "parent": "r", "attrs": attrs})
    return {"trace_id": "t", "spans": spans}


def _window(monkeypatch, entries):
    """The call log of a run whose window is `entries`, and its host
    record."""
    log = [_entry((0, 1), [], 1.0)] * 2 + entries   # first, warm, window
    monkeypatch.setattr(trainer_mod, "_call_log", [
        (e["trace_id"], [[s["name"], s["start"], s["end"], dict(
            s["attrs"], sid=s["span"], psid=s["parent"])]
            for s in e["spans"]]) for e in log])
    return {"attempted": len(log),
            "calls": [{"wall_s": e["spans"][0]["end"]} for e in entries]}


@pytest.mark.parametrize("entries, share", [
    # a deferred pull inside its call's epoch: all of it hidden
    ([_entry((0.1, 4.0), [(0.0, 2.0, {"deferred": 1})], 4.0)], 95.0),
    # one that outlasts the epoch by a quarter of its length
    ([_entry((0.0, 3.0), [(0.0, 4.0, {"deferred": 1})], 4.0)], 75.0),
    # pulls after the epoch hide nothing and count for nothing
    ([_entry((0.0, 3.0), [(3.0, 4.0, {"deferred": 0})], 4.0)], 0.0),
    ([_entry((0.0, 3.0), [(3.0, 4.0, {"deferred": 0})], 4.0),
      _entry((0.0, 2.0), [(0.0, 1.0, {"deferred": 1})], 2.0)], 100.0),
    # the parent's tree does not say: nothing to read
    ([_entry((0.0, 3.0), [(3.0, 4.0, {})], 4.0)], None),
    ([], None),
])
def test_snapshot_hidden_share_reads_the_deferred_pulls(monkeypatch,
                                                        entries, share):
    got = snapshot_hidden_share.read(_window(monkeypatch, entries), None)
    assert got == (None if share is None else pytest.approx(share))


def _held(*shares):
    """A window of calls whose `train.hold` says it held `share` of
    1000 bytes (None: the call has no such span; "?": it does not say)."""
    return [_entry((0.0, 1.0), [], 1.0, holds=[] if share is None else [
        {"bytes": 1000} if share == "?" else
        {"bytes": 1000, "held_bytes": share}]) for share in shares]


@pytest.mark.parametrize("entries, share", [
    (_held(1000, 1000, 1000), 100.0),       # the whole state held
    (_held(290, 290, 290, 290), 29.0),      # a part
    (_held(None, None, None), 0.0),         # nothing held: no such span
    # the median over the calls: one that held nothing counts 0
    (_held(None, 300, 300), 30.0),
    (_held(None, None, 300), 0.0),
    # the parent's span does not say: nothing to read
    (_held("?", "?"), None),
    ([], None),
])
def test_snapshot_held_share_reads_the_holds(monkeypatch, entries, share):
    got = snapshot_held_share.read(_window(monkeypatch, entries), None)
    assert got == (None if share is None else pytest.approx(share))
