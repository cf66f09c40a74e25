"""The epoch-boundary snapshot keeps its host buffers (train/trainer.py
`_own`, `Trainer.train`): two sets of driver-owned buffers alternate,
call k is copied into the buffers of the snapshot call k - 1 retired.
Both sets' bytes are reserved when the workers have started, before a
state lands in them (`_Reserve`), so every call's copy goes into bytes
the Trainer already has — the first two into leaves taken from the
reservation as they arrive. What must hold whatever the buffers do:
`_last_state` is a whole, bit-identical, driver-owned copy of THAT
call's state; it is never a destination; a caller's arrays are never
written to; reuse is decided leaf by leaf (the Trainer's own — owning
its data or held by the set's reservation —, writable, same shape,
dtype and strides). CPU, tiny models."""

import sys
import time

import jax
import numpy as np
import pytest

import ray_tpu
from ray_tpu._private import global_state, tracing
from ray_tpu.train import Trainer, TrainingOperator, call_log
from ray_tpu.train import trainer as trainer_mod

COPY = "train.snapshot.copy"


class Op(TrainingOperator):
    """(2048, 16) weights and a bias under adam: a 0.4 MB snapshot, so
    it returns through the object store and `get` hands out arena views.
    Works replicated and sharded (2048 * 16 + 16 divides by 1 and 2)."""

    def setup(self, config):
        import jax.numpy as jnp
        import optax

        def model_init(rng):
            return {"w": jnp.zeros((2048, 16)), "b": jnp.zeros((16,))}

        def loss_fn(params, batch):
            x, y = batch
            return jnp.mean((x @ params["w"] + params["b"] - y) ** 2)

        self.register(model_init=model_init, loss_fn=loss_fn,
                      optimizer=optax.adam(1e-2))
        x = np.ones((8, 2048), np.float32) / 8.0
        self.register_data(
            train_loader=[(x, np.ones((8, 16), np.float32))] * 2)


def _arrays(tree) -> dict:
    """path -> leaf, for the tree's array leaves."""
    return {jax.tree_util.keystr(p): x
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]
            if isinstance(x, np.ndarray)}


def _bits(tree) -> dict:
    return {p: (x.dtype.str, x.shape, x.tobytes())
            for p, x in _arrays(tree).items()}


def _addresses(tr) -> set:
    return {x.ctypes.data
            for x in _arrays((tr._last_state, tr._last_shards)).values()}


def _is_the_trainers(tr, x) -> bool:
    """An array only the Trainer writes: it owns its data, or it is a
    leaf one of the two sets' reservations handed out."""
    return (x.flags.owndata and x.base is None) or any(
        s.reserve is not None and s.reserve.holds(x) for s in tr._owned)


def _copies(entry) -> list[dict]:
    return [s["attrs"] for s in entry["spans"] if s["name"] == COPY]


def _workers_snapshot(tr):
    """What the workers hold now, in the shape the trainer keeps it."""
    state = tr.state_dict()
    shards = None
    if tr._sharded:
        state.pop("opt_shard", None)
        shards = ray_tpu.get(
            [w.opt_shard_state.remote() for w in tr.workers], timeout=60)
    return _bits((state, shards))


def _store_used() -> int:
    return global_state.require_core_worker().store.stats()["used"]


def _make(sharded: bool) -> Trainer:
    return Trainer(Op, num_workers=2 if sharded else 1, sharded=sharded)


@pytest.fixture(scope="module", params=[False, True],
                ids=["replicated", "sharded"])
def warm(request, ray_start_shared):
    """A trainer past its second call: both sets of buffers exist."""
    tr = _make(request.param)
    try:
        tr.train()
        tr.train()
        yield tr
    finally:
        tr.shutdown(force=True)


@pytest.mark.parametrize("sharded", [False, True],
                         ids=["replicated", "sharded"])
def test_buffers_alternate_and_every_call_lands_in_bytes_already_there(
        ray_start_shared, sharded):
    tr = _make(sharded)
    try:
        reused, sets = [], []
        for _ in range(5):
            tr.train()
            copies = _copies(call_log()[-1])
            # the state's copy-out, and in sharded mode the shards'
            assert len(copies) == (2 if sharded else 1)
            assert all(c["bytes"] > 0 for c in copies)
            reused.append([c["reused_bytes"] and c["reused_bytes"]
                           == c["bytes"] for c in copies])
            sets.append(_addresses(tr))
            if sharded:     # rank 0's shard is popped BEFORE the copy
                assert "opt_shard" not in tr._last_state
    finally:
        tr.shutdown(force=True)
    # everything from the first call on (PR 54: before, nothing went
    # into a buffer that was there on a Trainer's first two calls)
    assert reused == [[True] * len(copies)] * 5
    # exactly two sets of buffers, taking turns
    assert sets[0] == sets[2] == sets[4] and sets[1] == sets[3]
    assert not sets[0] & sets[1]


class HoldingOp(Op):
    """`Op` on a device with room for a second copy of its state (the
    room rule's seam: tests/test_train_deferred.py)."""

    def _device_memory(self):
        return [{"bytes_limit": 1 << 34, "peak_bytes_in_use": 1 << 30}]


def test_buffers_alternate_when_the_pull_is_a_call_late(ray_start_shared):
    """A held copy is pulled beside the next call's epoch: the two sets
    still take turns, one install at a time, and the installed snapshot
    — a call older now — is never a destination."""
    tr = Trainer(HoldingOp, num_workers=1)
    try:
        copies, sets, epochs = [], [], []
        for _ in range(6):
            installed = _addresses(tr) if tr._last_state else set()
            tr.train()
            entry = call_log()[-1]
            copies.append([(c["reused_bytes"], c["bytes"])
                           for c in _copies(entry)])
            sets.append(_addresses(tr))
            epochs.append(tr._last_state["epoch"])
            snaps = [s["attrs"] for s in entry["spans"]
                     if s["name"] == "train.snapshot"]
            assert [s["deferred"] for s in snaps] == (
                [0] if len(sets) == 1 else [] if len(sets) == 2 else [1])
            if len(sets) > 2:       # written beside: never the installed
                assert not installed & sets[-1]
            if len(sets) == 2:
                # the second call held its state and pulled nothing; it
                # returned with the set the NEXT pull lands in whole, so
                # no pull beside an epoch finds its set half made
                assert all(s.reserve.ready == s.reserve.bytes.nbytes
                           for s in tr._owned)
        assert _bits(tr._last_state) != _workers_snapshot(tr)  # a call old
        assert tr._pending is None      # ... until asked (state_dict)
        assert tr._last_state["epoch"] == 6
    finally:
        tr.shutdown(force=True)
    assert epochs == [1, 1, 2, 3, 4, 5]
    # call 1 pulls at once, call 2 not at all, call 3 into the other
    # set: every pull goes into bytes the Trainer had reserved
    size = copies[0][0][1]
    assert copies == [[(size, size)], [], [(size, size)]] + [
        [(size, size)]] * 3
    assert sets[0] == sets[1] == sets[3] == sets[5]
    assert sets[2] == sets[4] and not sets[0] & sets[2]


def test_snapshot_is_whole_owned_and_leaves_the_arena(warm):
    for _ in range(3):
        used = _store_used()
        warm.train()
        kept = (warm._last_state, warm._last_shards)
        assert _bits(kept) == _workers_snapshot(warm)
        for path, x in _arrays(kept).items():
            assert _is_the_trainers(warm, x), path
            assert x.flags.writeable and x.flags.c_contiguous, path
        # the return's arena block went with the views (the owner's
        # delete rides the raylet: give it a moment)
        deadline = time.monotonic() + 10
        while _store_used() > used and time.monotonic() < deadline:
            time.sleep(0.05)
        assert _store_used() <= used


@pytest.mark.parametrize("fail_at", [0, 1, 3])
def test_failed_copy_leaves_the_previous_snapshot_installed(
        warm, monkeypatch, fail_at):
    before_state, before_shards = warm._last_state, warm._last_shards
    before = _bits((before_state, before_shards))
    real, calls = np.copyto, []

    def copyto(dst, src, *a, **kw):
        calls.append(dst)
        if len(calls) == fail_at + 1:
            raise MemoryError("injected: the copy-out's leaf failed")
        return real(dst, src, *a, **kw)

    with monkeypatch.context() as m:
        m.setattr(np, "copyto", copyto)
        with pytest.raises(MemoryError, match="injected"):
            warm.train()
    assert len(calls) == fail_at + 1    # raised inside the copy-out
    # the previous snapshot: the same objects, not a byte moved
    # (sharded, fail_at 3: the state's copy was whole, the shards' not —
    # neither is installed)
    assert warm._last_state is before_state
    assert warm._last_shards is before_shards
    assert _bits((warm._last_state, warm._last_shards)) == before
    installed = {id(x) for x in _arrays((before_state,
                                         before_shards)).values()}
    assert not installed & {id(d) for d in calls}
    # ... and the trainer goes on: the half-written spare is a
    # destination again, never a snapshot
    warm.train()
    assert (_bits((warm._last_state, warm._last_shards))
            == _workers_snapshot(warm))
    assert all(c["reused_bytes"] == c["bytes"]
               for c in _copies(call_log()[-1]))


@pytest.mark.parametrize("how", ["load_state_dict", "load"])
def test_callers_arrays_are_never_a_destination(warm, tmp_path, how):
    if how == "load":
        path = warm.save(str(tmp_path / "ckpt"))
        warm.load(path)         # sharded: a manifest, installed as is
        theirs = (warm._last_state, warm._last_shards)
    else:
        state = jax.tree.map(
            lambda x: np.array(x) if isinstance(x, np.ndarray) else x,
            warm.state_dict())
        warm.load_state_dict(state)
        assert warm._last_state is state
        theirs = state
    before = _bits(theirs)
    for _ in range(3):
        warm.train()
    assert _bits(theirs) == before
    ours = _arrays((warm._last_state, warm._last_shards)).values()
    for x in _arrays(theirs).values():
        assert not any(np.shares_memory(x, y) for y in ours)
    assert all(c["reused_bytes"] == c["bytes"]
               for c in _copies(call_log()[-1]))


def test_restore_keeps_no_reference_to_the_snapshot(warm):
    """Two calls on, the retired snapshot's arrays are overwritten: the
    elastic restore must be done with them when its wait returns."""
    warm.train()
    kept = list(_arrays((warm._last_state, warm._last_shards)).values())
    before = [sys.getrefcount(x) for x in kept]
    warm._restore_state()
    assert [sys.getrefcount(x) for x in kept] == before


def test_resize_reallocates_the_shards_then_reuses(ray_start_shared):
    tr = _make(sharded=True)
    try:
        tr.train()
        tr.train()
        tr._num_workers = 1
        tr._resize_worker_group()
        assert tr.num_workers == 1
        reused = []
        for _ in range(3):
            tr.train()
            state, shards = _copies(call_log()[-1])
            # the params kept their shapes: reused straight through
            assert state["reused_bytes"] == state["bytes"] > 0
            reused.append(shards["reused_bytes"])
            assert (_bits((tr._last_state, tr._last_shards))
                    == _workers_snapshot(tr))
        # one shard of the whole where two halves were: both spares
        # are of the old geometry (all that fits is adam's step count,
        # a 0-d int32 in any geometry), then the new sets take over
        assert reused == [4, 4, shards["bytes"]]
    finally:
        tr.shutdown(force=True)


# ---------------------------------------------------------------------
# `_own` alone: which leaf of a spare is a destination
# ---------------------------------------------------------------------

def _own_traced(snapshot, spare):
    ctx = tracing.new_context()
    with tracing.open_tree(ctx) as rows, tracing.use(ctx):
        out = trainer_mod._own(snapshot, spare)
    (row,) = [r for r in rows if r[0] == COPY]
    return out, {k: row[3][k] for k in ("bytes", "reused_bytes")}


def _snapshot():
    w = np.arange(12, dtype=np.float32).reshape(3, 4)
    w.setflags(write=False)             # as the arena's views are
    return {"params": {"w": w, "b": np.ones(4, np.float32)},
            "opt": [np.full(4, 2.0, np.float32)], "epoch": 3}


def test_own_copies_into_a_matching_spare():
    spare = trainer_mod._own(_snapshot(), None)
    for x in _arrays(spare).values():
        x.fill(-1)
    out, counts = _own_traced(_snapshot(), spare)
    assert _bits(out) == _bits(_snapshot()) and out["epoch"] == 3
    assert counts["bytes"] == counts["reused_bytes"] == 48 + 16 + 16
    for path, x in _arrays(out).items():
        assert x is _arrays(spare)[path]
    # without a spare: the same bits, fresh arrays
    out, counts = _own_traced(_snapshot(), None)
    assert _bits(out) == _bits(_snapshot())
    assert (counts["bytes"], counts["reused_bytes"]) == (80, 0)
    assert all(x.flags.owndata and x.flags.writeable
               for x in _arrays(out).values())


def _spare_with(change):
    spare = trainer_mod._own(_snapshot(), None)
    for x in _arrays(spare).values():
        x.fill(-1)
    change(spare)
    return spare


def _moved(spare):
    spare["params"]["v"] = spare["params"].pop("w")


def _readonly(spare):
    spare["params"]["w"].setflags(write=False)


def _a_view(spare):
    spare["params"]["w"] = np.full((6, 4), -1, np.float32)[:3]


def _strided(spare):
    spare["params"]["w"] = np.asfortranarray(spare["params"]["w"])


@pytest.mark.parametrize("change", [
    lambda s: s["params"].__setitem__("w", np.full((4, 3), -1, np.float32)),
    lambda s: s["params"].__setitem__("w", np.full((3, 4), -1, np.float64)),
    _moved,
    lambda s: s["params"].__setitem__("w", "not an array"),
    _readonly, _a_view, _strided,
], ids=["shape", "dtype", "position", "non-array", "read-only", "view",
        "strides"])
def test_own_allocates_the_leaf_that_does_not_match(change):
    spare = _spare_with(change)
    reusable = (spare["params"]["b"], spare["opt"][0])
    untouched = {p: x for p, x in _arrays(spare).items()
                 if not any(x is y for y in reusable)}
    before = _bits(untouched)
    out, counts = _own_traced(_snapshot(), spare)
    assert _bits(out) == _bits(_snapshot())
    # `w` is new memory; its neighbours went into the spare's leaves
    assert counts == {"bytes": 80, "reused_bytes": 32}
    w = out["params"]["w"]
    assert w.flags.owndata and w.flags.writeable
    assert not any(np.shares_memory(w, x) for x in _arrays(spare).values())
    assert _bits(untouched) == before
    assert out["params"]["b"] is spare["params"]["b"]
    assert out["opt"][0] is spare["opt"][0]


def test_own_reuses_a_leaf_in_the_layout_it_arrives_in():
    """On a TPU some leaves arrive transposed (F-ordered): `np.array`
    keeps the layout, so the spare has it too and is reused."""
    snap = {"w": np.asfortranarray(
        np.arange(12, dtype=np.float32).reshape(3, 4))}
    spare = trainer_mod._own(snap, None)
    assert spare["w"].flags.f_contiguous and not spare["w"].flags.c_contiguous
    spare["w"].fill(-1)
    out, counts = _own_traced(snap, spare)
    assert out["w"] is spare["w"] and _bits(out) == _bits(snap)
    assert counts == {"bytes": 48, "reused_bytes": 48}
    # ... and a C-ordered leaf does not go into it
    out, counts = _own_traced({"w": np.ascontiguousarray(snap["w"])}, spare)
    assert out["w"] is not spare["w"] and out["w"].flags.c_contiguous
    assert counts == {"bytes": 48, "reused_bytes": 0}


def test_own_passes_non_array_leaves_through():
    snap = {"epoch": 7, "name": "x", "span": (0, 4), "none": None,
            "scalar": np.float32(2.5), "a": np.zeros(3)}
    spare = {"epoch": np.zeros(()), "name": np.zeros(1), "span": (1, 2),
             "none": None, "scalar": np.zeros((), np.float32),
             "a": np.zeros(3)}
    out, counts = _own_traced(snap, spare)
    assert out["epoch"] == 7 and out["name"] == "x"
    assert out["span"] == (0, 4) and out["none"] is None
    assert out["scalar"] is snap["scalar"]
    assert out["a"] is spare["a"]
    assert counts == {"bytes": 24, "reused_bytes": 24}
