"""One span tree per `Trainer.train()` call (train/trainer.py,
_private/tracing.py): the call is ONE trace rooted at `train.call`,
crossing the driver and the worker, whole in `call_log()` when the call
returns (the worker's spans ride the task replies) and in the GCS trace
table after the flush. A worker group's start is a tree of its own,
rooted at `train.start`, in `start_log()`. CPU, tiny models."""

import numpy as np
import pytest

import ray_tpu
from benchmark import boundary_path, span_log
from ray_tpu._private import serialization, tracing
from ray_tpu.train import Trainer, TrainingOperator, call_log, start_log
from ray_tpu.train import trainer as trainer_mod
from tests.conftest import scale_timeout
from tests.test_observability import (_assert_connected, _tree_of,
                                      _wait_spans)

# every span of a call, by the name ARCHITECTURE.md's catalogue lists
CALL_SPANS = {"train.call", "train.epoch", "train.dispatch", "train.sync",
              "train.snapshot", "train.snapshot.wait", "train.snapshot.d2h",
              "object.return_put", "object.get", "train.snapshot.copy"}
LEAF = "train.snapshot.d2h.leaf"


class WideOperator(TrainingOperator):
    """One 1.2 MB weight under adam: a 3.6 MB snapshot (a plasma return)
    with three leaves above the 1 MiB the fine level gives a span."""

    def setup(self, config):
        import jax.numpy as jnp
        import optax

        def model_init(rng):
            return {"w": jnp.zeros((300_000,)), "b": jnp.zeros((4,))}

        def loss_fn(params, batch):
            x, y = batch
            return jnp.mean((x * params["w"][:4] + params["b"] - y) ** 2)

        self.register(model_init=model_init, loss_fn=loss_fn,
                      optimizer=optax.adam(1e-3))
        x = np.ones((8, 4), np.float32)
        self.register_data(train_loader=[(x, 2 * x)] * 4)


class ShardOperator(TrainingOperator):
    """(512, 4) weights under adam, for the sharded (ZeRO) trainer."""

    def setup(self, config):
        import jax.numpy as jnp
        import optax

        def loss_fn(params, batch):
            x, y = batch
            return jnp.mean((x @ params["w"] - y) ** 2)

        self.register(model_init=lambda rng: {"w": jnp.zeros((512, 4))},
                      loss_fn=loss_fn, optimizer=optax.adam(1e-3))
        x = np.ones((8, 512), np.float32) / 4.0
        self.register_data(train_loader=[(x, np.ones((8, 4), np.float32))]
                           * 2)


class NoOp:
    """The least a TrainWorker needs of an operator: no jax at all."""

    def __init__(self, config, world_rank, world_size, group_name=None):
        pass

    def train_epoch(self, num_steps=None, profile_dir=None):
        return {"num_samples": 0}

    def state_dict(self):
        return {"epoch": 0}


def _names(entry):
    return [s["name"] for s in entry["spans"]]


def _ids(log):
    return [entry["trace_id"] for entry in log]


def _one(entry, name):
    (span,) = [s for s in entry["spans"] if s["name"] == name]
    return span


@pytest.fixture(scope="module")
def ray_start_shared():
    """The shared cluster with one DECLARED chip: a worker leased it is
    spawned for the lease (no pool, no earlier task), so a start's tree
    holds the worker's whole life."""
    ray_tpu.init(num_cpus=8, num_tpus=1)
    try:
        yield
    finally:
        ray_tpu.shutdown()


def _leaf_bytes(tree) -> int:
    import jax

    return sum(x.nbytes for x in jax.tree.leaves(tree)
               if isinstance(x, np.ndarray))


@pytest.fixture(scope="module")
def wide(ray_start_shared):
    tr = Trainer(WideOperator, num_workers=1)
    try:
        yield tr
    finally:
        tr.shutdown(force=True)


def test_one_call_is_one_tree_across_driver_and_worker(wide):
    before = len(call_log())
    wide.train(num_steps=2)
    assert len(call_log()) == before + 1
    entry = call_log()[-1]
    # whole when train() returns: no wait on the GCS flush
    assert CALL_SPANS <= set(_names(entry)), _names(entry)
    ids = {s["span"] for s in entry["spans"]}
    roots = [s for s in entry["spans"] if s["parent"] not in ids]
    assert [r["name"] for r in roots] == ["train.call"]
    assert roots[0]["attrs"] == {"num_steps": 2, "workers": 1,
                                 "call": wide._calls}   # counted from 1
    assert wide._calls >= 1
    dispatch = next(s for s in entry["spans"]
                    if s["name"] == "train.dispatch")
    held = dispatch["attrs"]["state_bytes"]   # whole, on the one device
    first = dispatch["attrs"]["first_dispatch_s"]  # of the loop's seconds
    assert 0 < first <= dispatch["end"] - dispatch["start"]
    assert held > 0 and dispatch["attrs"] == {
        "first_dispatch_s": first,
        "steps": 2, "samples": 16, "chips": 1, "state_bytes": held,
        "state_bytes_fullest_chip": held, "state_bytes_split_leading": 0}
    snapshot = next(s for s in entry["spans"]
                    if s["name"] == "train.snapshot")
    # this call's own state, pulled after its epoch (nothing is held)
    assert snapshot["attrs"] == {"deferred": 0, "of_call": wide._calls,
                                 "pieces": 1, "bytes": held}
    epoch = next(s for s in entry["spans"] if s["name"] == "train.epoch")
    assert epoch["attrs"] == {"attempts": 1}
    assert LEAF not in _names(entry)   # the fine level is off

    # the same tree through the GCS trace table, by process
    tid = entry["trace_id"]

    def whole(spans):
        tree = _tree_of(spans, tid)
        return tree if len(tree) >= len(entry["spans"]) else None

    tree = _wait_spans(whole, timeout=scale_timeout(30))
    assert _assert_connected(tree)["event_type"] == "train.call"
    where = {t["event_type"]: t["component_type"] for t in tree}
    for name in ("train.call", "train.epoch", "train.snapshot",
                 "object.get", "train.snapshot.copy", "task.e2e"):
        assert where[name] == "driver", (name, where[name])
    for name in ("train.dispatch", "train.sync", "train.snapshot.d2h",
                 "object.return_put", "task"):
        assert where[name] == "worker", (name, where[name])
    # ... and in the Perfetto export, with flow arrows into the worker
    export = ray_tpu.timeline()
    worker_sids = {t["extra_data"]["sid"] for t in tree
                   if t["component_type"] == "worker"}
    finishes = {e["id"] for e in export if e.get("ph") == "f"}
    assert worker_sids <= finishes


def test_parts_of_a_call_add_up(wide):
    for _ in range(2):
        out = wide.train(num_steps=3)
        entry = call_log()[-1]
        parts = span_log.split(entry)
        root = next(s for s in entry["spans"] if s["name"] == "train.call")
        call_s = root["end"] - root["start"]
        for key in ("d2h_s", "put_s", "get_s", "copy_s", "hop_s"):
            assert parts[key] >= 0, (key, parts)
        assert abs(parts["d2h_s"] + parts["put_s"] + parts["get_s"]
                   + parts["copy_s"] + parts["hop_s"]
                   - (call_s - parts["epoch_s"])) < 1e-3
        # the span's epoch is the operator's own (samples over its rate)
        assert abs(parts["epoch_s"]
                   - out["num_samples"] / out["samples_per_s"]) < 1e-3
        # every leaf span lies inside the call, after the epoch
        for s in entry["spans"]:
            if s["name"] in span_log.LEAVES:
                assert root["start"] <= s["start"] <= s["end"] <= root["end"]
        assert parts["bytes"] == _leaf_bytes(wide._last_state)
        copy = next(s for s in entry["spans"]
                    if s["name"] == "train.snapshot.copy")
        assert copy["attrs"]["bytes"] == parts["bytes"]
        # all of it went into bytes the Trainer had before the state
        # came (a retired snapshot's buffers, or its set's reservation),
        # and the span says how long it waited for them to be resident
        assert copy["attrs"]["reused_bytes"] == parts["bytes"]
        assert copy["attrs"]["reserve_wait_s"] >= 0.0
        # the same boundary cut along the driver's thread: with ONE
        # piece the wait is at most the worker's d2h and put and the
        # hops (all of them on the chip, where the driver is in the wait
        # before the worker has the task; here it may lose the CPU first)
        path = boundary_path.call_path(entry)
        assert path["pieces"] == 1
        assert path["boundary_s"] == pytest.approx(call_s - parts["epoch_s"])
        assert path["get_s"] == parts["get_s"]
        assert path["copy_s"] == parts["copy_s"]
        assert 0 <= path["wait_s"] <= (parts["d2h_s"] + parts["put_s"]
                                       + parts["hop_s"] + 1e-3)
        assert path["hops_s"] >= 0
        assert path["link_wait_s"] + path["start_s"] <= parts["d2h_s"]


def test_leaf_spans_only_when_the_call_is_traced(wide, tmp_path):
    # off: at the default rate the head sampler picks one call in a
    # hundred, and that call has leaf spans
    ray_tpu.set_trace_sampling(0.0)
    try:
        wide.train(num_steps=1)
        assert LEAF not in _names(call_log()[-1])
        wide.train(num_steps=1, profile_dir=str(tmp_path))
        entry = call_log()[-1]
        leaves = [s for s in entry["spans"] if s["name"] == LEAF]
        # w and adam's two moments of it; b and the counters are too small
        assert [s["attrs"] for s in leaves] == [
            {"bytes": 1_200_000, "dtype": "float32", "shape": [300_000]}] * 3
        d2h = next(s for s in entry["spans"]
                   if s["name"] == "train.snapshot.d2h")
        assert all(s["parent"] == d2h["span"] for s in leaves)
        # the session bracketed the worker's side of the call, and ended
        assert list(tmp_path.rglob("*.xplane.pb"))
        tasks = {s["attrs"]["name"] for s in entry["spans"]
                 if s["name"] == "task"}
        assert {"TrainWorker.start_profile",
                "TrainWorker.stop_profile"} <= tasks
        wide.train(num_steps=1)
        assert LEAF not in _names(call_log()[-1])
        # head sampling turns the fine level on as well
        ray_tpu.set_trace_sampling(1.0)
        wide.train(num_steps=1)
        assert _names(call_log()[-1]).count(LEAF) == 3
    finally:
        ray_tpu.set_trace_sampling(0.01)


def test_call_log_is_a_bounded_ring(ray_start_shared):
    tr = Trainer(NoOp, num_workers=1)
    try:
        for _ in range(300):
            tr.train()
    finally:
        tr.shutdown(force=True)
    assert len(call_log()) == trainer_mod.CALL_LOG_MAX == 256
    # a small state rides the reply inline: no object-plane span
    assert not {"object.return_put", "object.get"} & set(
        _names(call_log()[-1]))


def test_return_put_only_for_plasma_returns(ray_start_shared):
    @ray_tpu.remote
    def zeros(n):
        return np.zeros(n, np.uint8)

    ctx = tracing.new_context()
    with tracing.open_tree(ctx) as rows, tracing.use(ctx):
        small = ray_tpu.get(zeros.remote(1000), timeout=60)
        names_small = [r[0] for r in rows]
        big = ray_tpu.get(zeros.remote(2_000_000), timeout=60)
    assert "task" in names_small        # the worker's spans came back
    assert "object.return_put" not in names_small
    assert "object.get" not in names_small
    puts = [r for r in rows if r[0] == "object.return_put"]
    gets = [r for r in rows if r[0] == "object.get"]
    assert len(puts) == len(gets) == 1
    size = serialization.total_size(*serialization.serialize(big))
    assert puts[0][3]["bytes"] == gets[0][3]["bytes"] == size > big.nbytes
    assert small.nbytes == 1000


def test_spans_dropped_counts_what_a_reply_left_out(ray_start_shared):
    """A reply carries at most `REPLY_SPANS_MAX` rows; the owner's
    `task.e2e` says how many it left out, so a truncated tree says so."""
    @ray_tpu.remote
    def spans(n):
        for _ in range(n):
            with tracing.span("test.leaf", tracing.child_of_current()):
                pass
        return n

    ctx = tracing.new_context()
    with tracing.open_tree(ctx) as rows, tracing.use(ctx):
        assert ray_tpu.get(spans.remote(10), timeout=60) == 10
        few = list(rows)
        del rows[:]
        assert ray_tpu.get(spans.remote(300), timeout=60) == 300

    def read(tree):
        (e2e,) = [r for r in tree if r[0] == "task.e2e"]
        return (e2e[3]["spans_dropped"],
                sum(r[0] == "test.leaf" for r in tree),
                sum(r[0] == "task" for r in tree))

    assert read(few) == (0, 10, 1)
    # 300 leaves and the `task` span itself, which closes last
    assert read(rows) == (301 - tracing.REPLY_SPANS_MAX,
                          tracing.REPLY_SPANS_MAX, 0)


def test_sharded_call_carries_the_shard_pulls(ray_start_shared):
    tr = Trainer(ShardOperator, num_workers=2, sharded=True)
    try:
        tr.train()
        entry = call_log()[-1]
    finally:
        tr.shutdown(force=True)
    ids = {s["span"] for s in entry["spans"]}
    assert [s["name"] for s in entry["spans"]
            if s["parent"] not in ids] == ["train.call"]
    pulls = [s for s in span_log.under(entry, "task", "train.snapshot")
             if s["attrs"]["name"] == "TrainWorker.opt_shard_state"]
    assert len(pulls) == 2
    d2h = span_log.under(entry, "train.snapshot.d2h", "train.snapshot")
    # rank 0's state (with its shard) and one pull a rank
    assert len(d2h) == 3
    for pull in pulls:
        assert any(s["parent"] == pull["span"] for s in d2h)
    # the state's copy-out and the shards'
    assert _names(entry).count("train.snapshot.copy") == 2
    # ... of which only the state's is a piece: the readers of the
    # boundary's path leave the shards' pull to the hops
    path = boundary_path.call_path(entry)
    assert path["pieces"] == 1 and path["hops_s"] > 0
    assert _names(entry).count("train.dispatch") == 2


# ---------------------------------------------------------------------------
# a worker group's start: one tree, rooted at `train.start`
# ---------------------------------------------------------------------------

# the worker's life up to its first call, then that call
START_ORDER = ["worker.spawn", "worker.boot", "worker.actor_init",
               "train.setup"]
SETUP_PARTS = ["train.setup.backend", "train.setup.user",
               "train.setup.init", "train.setup.place"]


def _in_order_inside(entry, names, root):
    spans = [_one(entry, n) for n in names]
    assert root["start"] <= spans[0]["start"]
    assert spans[-1]["end"] <= root["end"]
    for a, b in zip(spans, spans[1:]):
        assert a["start"] <= a["end"] <= b["start"], (a["name"], b["name"])
    return spans


def test_a_start_is_one_tree_in_a_log_of_its_own(ray_start_shared):
    calls, starts = _ids(call_log()), _ids(start_log())
    tr = Trainer(WideOperator, num_workers=1, use_tpu=True)
    try:
        # ONE entry, and not among the calls: readers find a call in
        # `call_log()` by its position (the ring of calls may be full)
        # (the ring of starts may be full too: its oldest entry then left)
        kept = starts[-(trainer_mod.START_LOG_MAX - 1):]
        assert _ids(start_log())[:-1] == kept
        assert _ids(call_log()) == calls
        entry = start_log()[-1]
        tr.train(num_steps=1)
        assert _ids(start_log())[:-1] == kept
        assert _ids(call_log())[:-1] in (calls, calls[1:])  # a full ring
        first_call = call_log()[-1]
        assert first_call["trace_id"] not in calls + _ids(start_log())
    finally:
        tr.shutdown(force=True)
    ids = {s["span"] for s in entry["spans"]}
    roots = [s for s in entry["spans"] if s["parent"] not in ids]
    assert [r["name"] for r in roots] == ["train.start"]
    root = roots[0]
    assert root["attrs"] == {"generation": 1, "workers": 1, "restored": 0}
    assert "train.start.restore" not in _names(entry)
    spawn, boot, init, setup = _in_order_inside(entry, START_ORDER, root)
    assert spawn["end"] == boot["start"]    # one stamp: `main` entered
    assert spawn["attrs"]["flavor"] == boot["attrs"]["flavor"] == "tpu"
    assert spawn["attrs"]["pid"] == boot["attrs"]["pid"] > 0
    assert init["attrs"]["name"] == "TrainWorker"
    assert 0 <= init["attrs"]["load_s"] <= init["end"] - init["start"]
    # what the process did before it had a context hangs under the
    # first traced task it ran, beside that task's own spans
    task = next(s for s in entry["spans"] if s["name"] == "task"
                and s["attrs"]["name"] == "TrainWorker.setup_operator")
    assert {s["parent"] for s in (spawn, boot, init, setup)} == {
        task["span"]}
    # inside `train.setup`, its parts tile it: the backend, the user's
    # `setup` on both sides of `register`, the state made and placed
    parts = sorted((s for s in entry["spans"] if s["name"] in SETUP_PARTS),
                   key=lambda s: s["start"])
    assert [s["name"] for s in parts] == [
        "train.setup.backend", "train.setup.user", "train.setup.init",
        "train.setup.place", "train.setup.user"]
    assert {s["parent"] for s in parts} == {setup["span"]}
    for a, b in zip(parts, parts[1:]):
        assert a["end"] <= b["start"] + 1e-6
    named = sum(s["end"] - s["start"] for s in parts)
    assert named >= 0.95 * (setup["end"] - setup["start"])
    backend, _, made, placed, _ = parts
    assert backend["attrs"] == {"platform": "cpu", "devices": 8}
    weights = 300_000 * 4 + 4 * 4
    # each closes on a wait for the device, and says how long it waited
    assert made["attrs"] == {"bytes": weights,
                             "wait_s": made["attrs"]["wait_s"]}
    assert 0 <= made["attrs"]["wait_s"] <= made["end"] - made["start"]
    # adam: two moments a weight and a count beside them
    assert placed["attrs"] == {"state_bytes": 3 * weights + 4,
                               "wait_s": placed["attrs"]["wait_s"]}
    # the first call's tree holds the step's first dispatch: ONE
    # `jax.compile` with jax's own timings, and no `compile.*` span
    compiles = [s for s in first_call["spans"]
                if s["name"].startswith(("compile.", "jax.compile"))]
    assert [(s["name"], s["attrs"]["key"]) for s in compiles] == [
        ("jax.compile", "train.step:fused:8x4,8x4")]
    assert {"trace_s", "lower_s", "backend_s", "persistent_hit",
            "programs"} <= set(compiles[0]["attrs"])
    dispatch = _one(first_call, "train.dispatch")
    took = dispatch["end"] - dispatch["start"]
    assert 0 < dispatch["attrs"]["first_dispatch_s"] <= took


def test_a_restart_is_a_second_tree_with_the_restore(ray_start_shared):
    tr = Trainer(WideOperator, num_workers=1, use_tpu=True, max_retries=2)
    try:
        tr.train(num_steps=1)
        last_call, starts = call_log()[-1]["trace_id"], _ids(start_log())
        state_bytes = _leaf_bytes(tr._last_state)
        ray_tpu.kill(tr.workers[0])
        tr.train(num_steps=1)
        assert _ids(call_log())[-2] == last_call    # one call more
        # one start more (in a ring that may have been full)
        assert _ids(start_log())[:-1] == starts[
            -(trainer_mod.START_LOG_MAX - 1):]
        entry, call = start_log()[-1], call_log()[-1]
    finally:
        tr.shutdown(force=True)
    root = _one(entry, "train.start")
    # a tree of its own, which names the call that restarted the group
    assert root["parent"] is None and root["attrs"] == {
        "generation": 2, "workers": 1, "restored": 1,
        "in_call": call["trace_id"]}
    assert entry["trace_id"] != call["trace_id"]
    assert not {"train.start", "train.setup", "worker.spawn"} & set(
        _names(call))
    assert _one(call, "train.epoch")["attrs"] == {"attempts": 2}
    _, _, _, setup = _in_order_inside(entry, START_ORDER, root)
    restore = _one(entry, "train.start.restore")
    assert restore["parent"] == root["span"]
    assert restore["attrs"] == {"bytes": state_bytes}
    assert setup["end"] <= restore["start"] <= restore["end"] <= root["end"]
    # the pieces it pushed are its children's children
    loads = [s for s in span_log.under(entry, "task", "train.start.restore")
             if s["attrs"]["name"] == "TrainWorker.load_state_piece"]
    assert loads


def test_a_late_row_is_kept_in_a_tree_that_has_closed():
    """`tracing.record_late`: a span another thread ends after the
    tree that began it has closed (`train.snapshot.reserve` under
    `train.start`) is still that tree's — once, open tree or not."""
    root = tracing.new_context()
    with tracing.open_tree(root) as rows:
        early = tracing.child(root)
        tracing.record_late(rows, "early", 1.0, 2.0, early, {"set": 0})
    late = tracing.child(root)
    tracing.record_late(rows, "late", 1.5, 3.0, late, {"set": 1})
    assert [(r[0], r[1], r[2], r[3]["set"]) for r in rows] == [
        ("early", 1.0, 2.0, 0), ("late", 1.5, 3.0, 1)]
    for row, ctx in zip(rows, (early, late)):
        assert row[3]["tid"] == root.trace_id.hex()
        assert row[3]["sid"] == ctx.span_id.hex()
        assert row[3]["psid"] == root.span_id.hex()


def test_a_start_with_no_training_operator_reserves_nothing(
        ray_start_shared):
    """An operator that cannot say how large its state is (not a
    `TrainingOperator`): no reservation, no thread, no span — its
    snapshots are allocated as they arrive."""
    tr = Trainer(NoOp, num_workers=1)
    try:
        assert tr._reserver is None
        assert all(s.reserve is None for s in tr._owned)
        assert "train.snapshot.reserve" not in _names(start_log()[-1])
    finally:
        tr.shutdown(force=True)


@pytest.fixture
def no_pending_rows():
    """`tracing._pending` is the PROCESS's: a test that ran before in
    this pytest process (another of this file, or of another file under
    `--dist loadfile`) may have left rows there — a core worker's
    `_before_user_code`, a boot stamp. Empty for the test, put back
    after it."""
    kept = tracing._pending[:]
    del tracing._pending[:]
    try:
        yield
    finally:
        tracing._pending[:] = kept


def test_pending_rows_go_home_with_the_first_traced_task(no_pending_rows):
    """What a process did before it had a trace context (`tracing.
    pending`) becomes children of the first traced task it runs: once,
    bounded, and not of an untraced task."""
    assert not tracing._pending
    tracing.pending("worker.boot", 1.0, 2.5, {"pid": 7})
    with tracing.collect_reply(None) as rows:
        pass
    assert rows is None and len(tracing._pending) == 1
    ctx = tracing.new_context()
    with tracing.collect_reply(ctx) as rows:
        pass
    assert [(r[0], r[1], r[2]) for r in rows] == [("worker.boot", 1.0, 2.5)]
    assert rows[0][3]["psid"] == ctx.span_id.hex()
    assert rows[0][3]["tid"] == ctx.trace_id.hex()
    assert rows[0][3]["pid"] == 7
    with tracing.collect_reply(tracing.new_context()) as again:
        pass
    assert not again and not tracing._pending
    for i in range(3 * tracing.PENDING_MAX):
        tracing.pending("worker.boot", 0.0, float(i))
    assert len(tracing._pending) == tracing.PENDING_MAX
    del tracing._pending[:]


def test_the_chip_wait_is_a_pending_span_with_what_it_found(
        no_pending_rows):
    from types import SimpleNamespace

    from ray_tpu._private import accelerator
    from ray_tpu._private.core_worker import CoreWorker

    probes = iter([["/dev/vfio/0", "/dev/vfio/1"], ["/dev/vfio/1"], []])

    def chip_wait():
        facts = {}
        accelerator.wait_for_chips(lambda: next(probes), pause=0.01,
                                   facts=facts)
        return facts

    assert not tracing._pending
    worker = SimpleNamespace(before_user_code=chip_wait)
    try:
        CoreWorker._before_user_code(worker)
        CoreWorker._before_user_code(worker)    # once
        ((name, start, end, facts),) = tracing._pending
    finally:
        del tracing._pending[:]
    assert name == "worker.chip_wait" and facts["held"] == 2
    assert 0.02 <= facts["waited_s"] <= end - start + 1e-3
