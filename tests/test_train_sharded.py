"""ZeRO-sharded elastic training (train/sharding.py, operator sharded
update, ingest pipeline, FSDP mesh mode).

Bit-exactness strategy: every operator here feeds RANK-IDENTICAL dyadic
data (values on the 1/8 grid) through optax.sgd(0.125, momentum=0.5) —
power-of-two scales make every f32 op exact, and identical per-rank
grads make the allreduce mean a fixed point ((g+g)/2 == g), so the loss
trajectory is invariant to world size. That lets a plain replicated
no-resize run serve as the control for BOTH the sharded update and the
elastic N->N-1->N resize sequence: any divergence is a real bug in the
reducescatter/shard-apply/allgather schedule or the reshard math, never
floating-point noise."""

import os
import pickle

import numpy as np
import pytest

import ray_tpu
from ray_tpu import exceptions as exc
from ray_tpu._private import failpoints as fp
from ray_tpu.collective.types import QUANT_BLOCK
from ray_tpu.train import IngestSpec, Trainer, TrainingOperator
from ray_tpu.train import ingest as ingestlib
from ray_tpu.train import sharding as shardlib


def _dyadic_data(n=32, d=4):
    # (5i + 7j) % 16 keeps rows distinct (5 is coprime to 16); /4 puts
    # every entry on the dyadic quarter grid in [-2, 1.75]
    X = np.array([[((5 * i + 7 * j) % 16 - 8) / 4.0 for j in range(d)]
                  for i in range(n)], dtype=np.float32)
    w_true = np.array([1.0, -2.0, 0.5, 0.25], dtype=np.float32)
    return X, X @ w_true


class DyadicOperator(TrainingOperator):
    """y = x @ w + b regression on rank-identical dyadic data."""

    def setup(self, config):
        import jax.numpy as jnp
        import optax

        def model_init(rng):
            return {"w": jnp.zeros(4), "b": jnp.zeros(())}

        def loss_fn(params, batch):
            x, y = batch
            return jnp.mean((x @ params["w"] + params["b"] - y) ** 2)

        self.register(model_init=model_init, loss_fn=loss_fn,
                      optimizer=optax.sgd(0.125, momentum=0.5))
        X, y = _dyadic_data()
        bs = 8
        batches = [(X[i:i + bs], y[i:i + bs]) for i in range(0, len(X), bs)]
        self.register_data(train_loader=batches, validation_loader=batches)


class WideAdamOperator(TrainingOperator):
    """(512, 4) weight matrix under adam — big enough that the 2-moment
    optimizer state dominates and the sharded gauge must read ~1/N."""

    def setup(self, config):
        import jax.numpy as jnp
        import optax

        def model_init(rng):
            return {"w": jnp.zeros((512, 4))}

        def loss_fn(params, batch):
            x, y = batch
            return jnp.mean((x @ params["w"] - y) ** 2)

        self.register(model_init=model_init, loss_fn=loss_fn,
                      optimizer=optax.adam(1e-3))
        x = np.ones((8, 512), np.float32) / 4.0
        y = np.ones((8, 4), np.float32)
        self.register_data(train_loader=[(x, y)] * 2,
                           validation_loader=[(x, y)])


class MatOperator(TrainingOperator):
    """(768, 32) = 24576 params: divisible by world*QUANT_BLOCK for
    world=3, so the int8 quantized reducescatter fast path engages."""

    def setup(self, config):
        import jax.numpy as jnp
        import optax

        def model_init(rng):
            return {"w": jnp.zeros((768, 32))}

        def loss_fn(params, batch):
            x, y = batch
            return jnp.mean((x @ params["w"] - y) ** 2)

        self.register(model_init=model_init, loss_fn=loss_fn,
                      optimizer=optax.sgd(0.0625))
        x = np.array([[((5 * i + 7 * j) % 16 - 8) / 8.0
                       for j in range(768)] for i in range(8)], np.float32)
        y = np.array([[((i + k) % 8 - 4) / 4.0 for k in range(32)]
                      for i in range(8)], np.float32)
        self.register_data(train_loader=[(x, y)] * 2,
                           validation_loader=[(x, y)])


def _ingest_dataset_fn(shard_index, num_shards, config):
    """Module-level (cloudpickles cheap) — same batches DyadicOperator
    registers in-memory, so stream-fed losses must match exactly."""
    X, y = _dyadic_data()
    bs = 8
    return [(X[i:i + bs], y[i:i + bs]) for i in range(0, len(X), bs)]


def _params(tr):
    import jax

    return [np.asarray(l) for l in jax.tree.leaves(tr.state_dict()["params"])]


def _assert_params_equal(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# shard math (no cluster)
# ---------------------------------------------------------------------------


def test_padded_numel_and_spans():
    assert shardlib.padded_numel(1000, 3) == 3 * QUANT_BLOCK * 2
    assert shardlib.padded_numel(1, 1) == QUANT_BLOCK
    assert shardlib.padded_numel(4 * QUANT_BLOCK, 4) == 4 * QUANT_BLOCK
    with pytest.raises(ValueError):
        shardlib.padded_numel(10, 0)
    spans = shardlib.shard_spans(1000, 3)
    assert spans[0][0] == 0 and spans[-1][1] == shardlib.padded_numel(1000, 3)
    sizes = {hi - lo for lo, hi in spans}
    assert len(sizes) == 1  # uniform
    assert next(iter(sizes)) % QUANT_BLOCK == 0  # block-aligned
    for (_, hi), (lo, _) in zip(spans, spans[1:]):
        assert hi == lo  # contiguous cover
    # identical to np.array_split over the padded bucket
    pad = shardlib.padded_numel(1000, 3)
    np_sizes = [c.size for c in np.array_split(np.zeros(pad), 3)]
    assert np_sizes == [hi - lo for lo, hi in spans]


def _fake_shards(numel, world, seed_base=0):
    """Shard set with one partitioned (momentum-like) leaf holding
    globally-increasing values (zero in the pad region, per the
    contract) and one replicated scalar leaf."""
    pad = shardlib.padded_numel(numel, world)
    full = np.zeros(pad, np.float32)
    full[:numel] = np.arange(numel, dtype=np.float32) + seed_base
    s = pad // world
    return [{"rank": r, "world_size": world, "span": (r * s, (r + 1) * s),
             "numel": numel, "pad_numel": pad,
             "leaves": [full[r * s:(r + 1) * s].copy(),
                        np.asarray(7.0, np.float32)]}
            for r in range(world)], full


def test_merge_and_reshard_roundtrip():
    numel = 1000
    shards, full = _fake_shards(numel, 3)
    merged = shardlib.merge_opt_shards(shards)
    np.testing.assert_array_equal(merged[0], full)
    assert float(merged[1]) == 7.0
    # 3 -> 2 -> 3 reshard preserves the real content exactly
    two = shardlib.reshard_opt_shards(shards, 2)
    assert [s["span"] for s in two] == shardlib.shard_spans(numel, 2)
    back = shardlib.reshard_opt_shards(two, 3)
    for orig, rt in zip(shards, back):
        assert orig["span"] == rt["span"]
        np.testing.assert_array_equal(orig["leaves"][0], rt["leaves"][0])
    # reshard to world 1 == the trimmed full vector, padded to 1-world pad
    one = shardlib.reshard_opt_shards(shards, 1)
    assert len(one) == 1 and one[0]["pad_numel"] == shardlib.padded_numel(
        numel, 1)
    np.testing.assert_array_equal(one[0]["leaves"][0][:numel], full[:numel])
    assert not one[0]["leaves"][0][numel:].any()


def test_merge_rejects_bad_rank_set():
    shards, _ = _fake_shards(1000, 3)
    with pytest.raises(ValueError):
        shardlib.merge_opt_shards([shards[0], shards[2]])
    with pytest.raises(ValueError):
        shardlib.merge_opt_shards([])


@pytest.mark.parametrize("fsdp, shape, spec", [
    # a stack of layers: never along the axis the layer scan walks
    (4, (36, 1280, 3840), (None, "fsdp", None)),
    (4, (8, 64), (None, "fsdp")),             # stacked norms and biases
    (4, (8, 6, 64), (None, None, "fsdp")),    # the earliest LATER one
    # the leading dimension only when no later one divides
    (4, (8, 3), ("fsdp", None)),
    (4, (50257, 1280), (None, "fsdp")),       # GPT-2's embedding
    (4, (1024, 1280), (None, "fsdp")),        # ... and its positions
    (4, (3, 3, 64, 64), (None, None, "fsdp", None)),    # a conv kernel
    (4, (2048, 1000), (None, "fsdp")),
    (4, (4,), ("fsdp",)),                     # one dimension: that one
    (4, (6,), ()),
    (4, (3, 5), ()),                          # nothing divides: whole
    (4, (2, 3), ()),                          # ... 2 < 4 is not a split
    (4, (), ()),
    (2, (36, 1280, 3840), (None, "fsdp", None)),
    (1, (36, 1280, 3840), ()),                # nothing to split over
    (1, (4,), ()),
])
def test_fsdp_param_spec_rules(fsdp, shape, spec):
    import types

    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel import mesh as meshlib

    mesh = types.SimpleNamespace(shape={"fsdp": fsdp})
    specs = meshlib.fsdp_param_specs(
        {"leaf": np.zeros(shape), "nested": [np.zeros(shape)]}, mesh)
    assert specs == {"leaf": P(*spec), "nested": [P(*spec)]}


def test_trainer_mode_validation():
    with pytest.raises(ValueError, match="mutually exclusive"):
        Trainer(DyadicOperator, num_workers=1, sharded=True,
                mesh_mode="fsdp")
    with pytest.raises(ValueError, match="unknown mesh_mode"):
        Trainer(DyadicOperator, num_workers=1, mesh_mode="tensor")
    with pytest.raises(ValueError, match="multihost"):
        Trainer(DyadicOperator, num_workers=2, mesh_mode="fsdp")
    with pytest.raises(ValueError, match="HOST collective"):
        Trainer(DyadicOperator, num_workers=2, sharded=True,
                config={"multihost": True})


def test_hist_quantile():
    assert ingestlib.hist_quantile({"count": 0, "counts": [],
                                    "boundaries": []}, 0.5) == 0.0
    snap = {"count": 10, "counts": [8, 1, 1, 0], "boundaries": [1, 2, 3]}
    assert ingestlib.hist_quantile(snap, 0.5) == 1
    assert ingestlib.hist_quantile(snap, 0.95) == 3


# ---------------------------------------------------------------------------
# sharded update: bit-exact trajectory, memory, int8 wire
# ---------------------------------------------------------------------------


def test_sharded_bit_exact_vs_replicated(ray_start_shared):
    ctl = Trainer(DyadicOperator, num_workers=2)
    try:
        ctl_losses = [ctl.train()["train_loss"] for _ in range(3)]
        ctl_params = _params(ctl)
    finally:
        ctl.shutdown(force=True)
    assert ctl_losses[-1] < ctl_losses[0]  # actually learning

    tr = Trainer(DyadicOperator, num_workers=2, sharded=True)
    try:
        losses = [tr.train()["train_loss"] for _ in range(3)]
        sh_params = _params(tr)
        # every rank holds bitwise-identical params after allgather
        states = ray_tpu.get([w.state_dict.remote() for w in tr.workers])
    finally:
        tr.shutdown(force=True)
    assert losses == ctl_losses
    _assert_params_equal(sh_params, ctl_params)
    import jax

    for st in states[1:]:
        _assert_params_equal([np.asarray(l) for l in
                              jax.tree.leaves(states[0]["params"])],
                             [np.asarray(l) for l in
                              jax.tree.leaves(st["params"])])


class RoomyWideAdam(WideAdamOperator):
    """On devices with room for a second copy of the state (the room
    rule's seam: tests/test_train_deferred.py)."""

    def _device_memory(self):
        return [{"bytes_limit": 1 << 34, "peak_bytes_in_use": 1 << 30}]


@pytest.mark.parametrize("sharded", [True, False],
                         ids=["zero", "replicated"])
def test_a_group_of_several_workers_never_defers_its_pull(ray_start_shared,
                                                          sharded):
    """A rank of a host-collective group does not own its whole state
    (ZeRO: the optimizer's shards come from every worker): whatever room
    its device has, nothing is held and every call pulls its own state,
    whole, after its epoch — the pull is deferred whole or not at all,
    and here not at all."""
    from ray_tpu.train import call_log

    tr = Trainer(RoomyWideAdam, num_workers=2, sharded=sharded)
    try:
        for call in (1, 2, 3):
            out = tr.train(reduce_results=False)
            assert all("held_epoch" not in r for r in out)
            assert tr._pending is None and tr._snapshot_of == call
            assert tr._last_state["epoch"] == call
            entry = call_log()[-1]
            names = [s["name"] for s in entry["spans"]]
            assert "train.hold" not in names
            (snap,) = [s["attrs"] for s in entry["spans"]
                       if s["name"] == "train.snapshot"]
            assert snap["deferred"] == 0 and snap["of_call"] == call
            if sharded:
                assert len(tr._last_shards) == 2
    finally:
        tr.shutdown(force=True)


def test_sharded_optimizer_memory_gauge(ray_start_shared):
    def gauge(tr):
        return max(ray_tpu.get(
            [w.read_counter.remote("train.optim_shard_bytes")
             for w in tr.workers]))

    rep = Trainer(WideAdamOperator, num_workers=2)
    try:
        rep.train()
        rep_bytes = gauge(rep)
    finally:
        rep.shutdown(force=True)
    sh = Trainer(WideAdamOperator, num_workers=2, sharded=True)
    try:
        sh.train()
        sh_bytes = gauge(sh)
    finally:
        sh.shutdown(force=True)
    # adam on 2048 params: two f32 moments each; the shard holds half
    assert rep_bytes > 0 and sh_bytes > 0
    assert sh_bytes <= 0.6 * rep_bytes, (sh_bytes, rep_bytes)


def test_int8_wire_savings_and_rank_consistency(ray_start_shared):
    import jax

    tr = Trainer(MatOperator, num_workers=3, sharded=True,
                 quantize="int8", collective_transport="ring")
    try:
        first = tr.train()
        last = tr.train()
        saved = ray_tpu.get(
            [w.read_counter.remote("collective.quantized_bytes_saved_total")
             for w in tr.workers])
        states = ray_tpu.get([w.state_dict.remote() for w in tr.workers])
    finally:
        tr.shutdown(force=True)
    # int8 is lossy on the grad wire but the param allgather relays the
    # exact updated shard bytes: every rank must end bit-identical
    assert all(s > 0 for s in saved), saved
    base = [np.asarray(l) for l in jax.tree.leaves(states[0]["params"])]
    for st in states[1:]:
        _assert_params_equal(
            base, [np.asarray(l) for l in jax.tree.leaves(st["params"])])
    assert last["train_loss"] < first["train_loss"]


# ---------------------------------------------------------------------------
# elastic: resize mid-run, no-op resize, sharded checkpoints
# ---------------------------------------------------------------------------


def test_elastic_resize_bit_exact(ray_start_shared):
    ctl = Trainer(DyadicOperator, num_workers=2)
    try:
        ctl_losses = [ctl.train()["train_loss"] for _ in range(3)]
        ctl_params = _params(ctl)
    finally:
        ctl.shutdown(force=True)

    tr = Trainer(DyadicOperator, num_workers=2, sharded=True)
    try:
        losses = [tr.train()["train_loss"]]
        fp.arm("train.reshard", "delay", ms=0)  # count reshard events
        try:
            tr._num_workers = 1
            tr._resize_worker_group()
            assert tr.num_workers == 1
            losses.append(tr.train()["train_loss"])
            tr._num_workers = 2
            tr._resize_worker_group()
            assert tr.num_workers == 2
            losses.append(tr.train()["train_loss"])
            assert fp.hits("train.reshard") >= 2  # 2->1 and 1->2 resharded
        finally:
            fp.reset()
        params = _params(tr)
    finally:
        tr.shutdown(force=True)
    # rank-identical dyadic data makes the trajectory world-size
    # invariant, so the no-resize replicated control IS the oracle for
    # the resized sharded run — equality must be exact
    assert losses == ctl_losses
    _assert_params_equal(params, ctl_params)


def test_noop_resize_keeps_generation(ray_start_shared):
    tr = Trainer(DyadicOperator, num_workers=2, sharded=True)
    try:
        tr.train()
        before = list(tr.workers)
        tr._resize_worker_group()  # gang intact at full strength: no-op
        assert all(a is b for a, b in zip(before, tr.workers))
        assert len(tr.workers) == 2
        tr.train()  # and it still trains
    finally:
        tr.shutdown(force=True)


def test_sharded_checkpoint_roundtrip(ray_start_shared, tmp_path):
    path = str(tmp_path / "ckpt")
    tr = Trainer(DyadicOperator, num_workers=2, sharded=True)
    try:
        tr.train()
        tr.save(path)
        ref_loss = tr.train()["train_loss"]
        ref_params = _params(tr)
    finally:
        tr.shutdown(force=True)

    for f in ("", ".params", ".shard0", ".shard1"):
        assert os.path.exists(path + f), f
    with open(path, "rb") as f:
        man = pickle.load(f)
    assert man["format"] == "ray_tpu.sharded_ckpt"
    assert man["world_size"] == 2
    assert man["spans"] == shardlib.shard_spans(man["numel"], 2)

    # load reshards 2 saved shards into a 1-worker trainer; continuing
    # must reproduce the reference trajectory exactly
    tr1 = Trainer(DyadicOperator, num_workers=1, sharded=True)
    try:
        tr1.load(path)
        loss = tr1.train()["train_loss"]
        params = _params(tr1)
    finally:
        tr1.shutdown(force=True)
    assert loss == ref_loss
    _assert_params_equal(params, ref_params)

    # a sharded manifest cannot load into a replicated trainer
    rep = Trainer(DyadicOperator, num_workers=1)
    try:
        with pytest.raises(ValueError, match="sharded"):
            rep.load(path)
    finally:
        rep.shutdown(force=True)


# ---------------------------------------------------------------------------
# streaming ingest: equivalence, failpoint, chaos
# ---------------------------------------------------------------------------


def test_ingest_stream_matches_in_memory(ray_start_shared):
    ctl = Trainer(DyadicOperator, num_workers=2)
    try:
        ctl_losses = [ctl.train()["train_loss"] for _ in range(2)]
    finally:
        ctl.shutdown(force=True)

    tr = Trainer(DyadicOperator, num_workers=2, sharded=True,
                 ingest=IngestSpec(_ingest_dataset_fn))
    try:
        assert len(tr._ingest_actors) == 2
        losses = [tr.train()["train_loss"] for _ in range(2)]
        waits = ray_tpu.get(
            [w.read_metric.remote("train.ingest_wait_s")
             for w in tr.workers])
    finally:
        tr.shutdown(force=True)
    assert losses == ctl_losses  # stream-fed batches are the same bytes
    # every worker actually pulled through the stream (4 batches/epoch)
    assert all(s and s["count"] >= 8 for s in waits), waits


def test_ingest_failpoint_typed_error_then_recovers(ray_start_shared):
    tr = Trainer(DyadicOperator, num_workers=2, sharded=True,
                 ingest=IngestSpec(_ingest_dataset_fn))
    try:
        first = tr.train()["train_loss"]
        fp.arm_cluster("train.ingest_batch=raise(nth=2)")
        try:
            # cluster arming rides pubsub: wait for the spec to land in
            # the dataset actor processes before relying on it
            import time

            deadline = time.time() + 15
            while time.time() < deadline:
                snaps = ray_tpu.get([a.failpoints.remote()
                                     for a in tr._ingest_actors])
                if all("train.ingest_batch" in s for s in snaps):
                    break
                time.sleep(0.1)
            else:
                pytest.fail("failpoint spec never reached ingest actors")
            with pytest.raises(exc.TaskError):
                tr.train()
        finally:
            fp.disarm_cluster()
        # the retried epoch rebuilds the stream iterator (fresh gen) and
        # completes; trajectory keeps descending
        out = tr.train()
        assert out["train_loss"] < first
    finally:
        tr.shutdown(force=True)


def test_chaos_kill_worker_and_ingest_actor(ray_start_shared):
    tr = Trainer(DyadicOperator, num_workers=2, sharded=True,
                 ingest=IngestSpec(_ingest_dataset_fn), max_retries=3)
    try:
        tr.train()
        ray_tpu.kill(tr._ingest_actors[1])
        ray_tpu.kill(tr.workers[0])
        # the gang scan treats the dead DatasetShard like a dead worker:
        # train() either completes after an in-call re-gang or surfaces
        # a typed error — never a hang or an untyped crash
        try:
            out = tr.train()
        except (exc.ActorDiedError, exc.WorkerCrashedError, exc.TaskError,
                exc.GetTimeoutError):
            out = tr.train()
        assert "train_loss" in out
        assert tr.num_workers >= 1
        assert len(tr._ingest_actors) == tr.num_workers
        # the re-ganged group keeps working
        out2 = tr.train()
        assert "train_loss" in out2
    finally:
        tr.shutdown(force=True)


# ---------------------------------------------------------------------------
# FSDP mesh mode
# ---------------------------------------------------------------------------


def test_fsdp_mesh_mode_smoke(ray_start_shared):
    tr = Trainer(DyadicOperator, num_workers=1, mesh_mode="fsdp")
    try:
        first = tr.train()["train_loss"]
        for _ in range(3):
            last = tr.train()["train_loss"]
    finally:
        tr.shutdown(force=True)
    assert last < first * 0.5


def test_mesh_step_compiles_once_and_restores_onto_the_mesh():
    """Mesh mode hands its state back laid out exactly as it took it
    (adam's count, which optax makes on the default device, included),
    so only the first step compiles — and a restored state goes back
    onto the mesh instead of whole onto the first device."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    class MeshAdam(WideAdamOperator):
        def register(self, **kw):
            mesh = Mesh(np.array(jax.devices()).reshape(2, 4),
                        ("data", "fsdp"))
            super().register(mesh=mesh, param_spec={"w": P("fsdp", None)},
                             batch_spec=P("data"), **kw)

    built = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **kw: built.append(event)
        if event.endswith("backend_compile_duration") else None)
    op = MeshAdam({}, 0, 1)
    batch = next(iter(op._train_loader))

    def layout():
        return jax.tree.map(lambda x: x.sharding,
                            (op.params, op.model_state, op.opt_state))

    registered = layout()
    assert {type(s).__name__ for s in jax.tree.leaves(registered)} == {
        "NamedSharding"}
    op.train_batch(batch)
    assert built  # the first step compiles...
    saved = op.state_dict()
    del built[:]
    losses = [op.train_batch(batch)["train_loss"] for _ in range(2)]
    assert not built, built  # ...and only the first
    assert layout() == registered
    op.load_state_dict(saved)
    assert layout() == registered
    del built[:]
    assert op.train_batch(batch)["train_loss"] == losses[0]
    assert not built, built


# ---------------------------------------------------------------------------
# CI gate: recorded paired-arm bench (reads MICROBENCH.json; no
# benchmarking in CI — same pattern as the serve_mixed gate)
# ---------------------------------------------------------------------------


def test_microbench_train_sharded_gate():
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    doc = json.load(open(os.path.join(root, "MICROBENCH.json")))
    rows = {r["name"]: r for r in doc["results"]}
    for name in ("train_sharded replicated", "train_sharded zero",
                 "train_sharded zero_int8", "train_ingest off",
                 "train_ingest on depth2"):
        assert name in rows, f"missing {name!r} row in MICROBENCH.json"
    rep, zero = rows["train_sharded replicated"], rows["train_sharded zero"]
    # ZeRO's whole point: per-worker optimizer state shrinks ~world x
    assert zero["optim_state_bytes_per_worker"] < \
        rep["optim_state_bytes_per_worker"], (zero, rep)
    # int8 grad wire: recorded savings counter vs the exact-wire bytes
    # the same schedule would have moved (counter-verified ~4x => the
    # saved fraction must be at least 70%)
    q = rows["train_sharded zero_int8"]
    assert q["wire_saved_bytes"] > 0
    assert q["wire_saved_bytes"] / q["wire_exact_bytes"] >= 0.7, q
    # double-buffered ingest at depth 2 hides input time: the recorded
    # p50 wait must be ~zero (first bucket of the latency histogram)
    ing = rows["train_ingest on depth2"]
    assert ing["ingest_wait_count"] > 0
    assert ing["ingest_wait_p50_s"] <= 0.005, ing
