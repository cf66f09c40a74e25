"""The compile seam: one object (`profiling.CompileProbe`) records the
first dispatch of every program the runtime jits, and ONE cache keeps
executables across processes — JAX's.

This file took the place of the export cache's tests (PR 59). What each
of those guarded that still exists, and where it is held now:

* key schema / quantize modes never share an executable — the in-process
  keys of `_DeviceOps` (`test_device_ops_keys_differ_by`); across
  processes JAX keys on the program itself.
* a miss records a compile exactly once, with jax's own timings —
  `test_first_dispatch_is_one_jax_compile` over the four sites.
* the listener lives only inside a resolution —
  `test_listener_lives_only_inside_a_first_dispatch`.
* a resolution is spans of the ambient trace —
  `test_a_train_calls_tree_has_one_jax_compile_and_no_compile_span`.
* the step is traced once (no fingerprint, no export) —
  `test_the_loss_is_traced_once_in_a_first_call`.
* a donating seam fails or serves with its inputs intact —
  `test_a_donating_step_and_its_text`.
* a failure degrades, never breaks (the load failpoint) —
  `test_a_failed_first_dispatch_is_retried_and_recorded_once`.
* `state()` in a process's snapshot, the cold finding —
  `test_a_snapshot_has_jax_compiles_and_no_compile_cache`.
* the gang restart / the MICROBENCH row: a restarted process finds its
  executables — `test_a_second_process_loads_from_jaxs_cache`, counted
  live.
"""

import json
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu._private import profiling, tracing
from ray_tpu.train import Trainer, TrainingOperator, call_log
from tests.conftest import scale_timeout

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spans_of(fn):
    """`fn()` inside a trace: the spans it recorded, (name, attributes
    less the ids) in order."""
    root = tracing.new_context()
    with tracing.open_tree(root) as rows, tracing.use(root):
        fn()
    return [(name, {k: v for k, v in fields.items()
                    if k not in ("tid", "sid", "psid")})
            for name, _, _, fields in rows]


def _listeners():
    from jax._src import monitoring

    return monitoring.get_event_duration_listeners().count(
        profiling._on_jax_duration)


TRACED = []     # one entry a Python run of `Small`'s loss function


class Small(TrainingOperator):
    """Two (16, 16) weights under adam."""

    def setup(self, config):
        import optax

        def model_init(rng):
            a, b = jax.random.split(rng)
            return {"a": jax.random.normal(a, (16, 16)) / 4,
                    "b": jax.random.normal(b, (16, 16)) / 4}

        def loss_fn(params, batch):
            TRACED.append(1)
            return jnp.mean(jnp.tanh(batch @ params["a"]) @ params["b"])

        self.register(model_init=model_init, loss_fn=loss_fn,
                      optimizer=optax.adam(1e-2))
        batch = np.ones((4, 16), np.float32)
        self.register_data(train_loader=[batch] * 2,
                           validation_loader=[batch])


# ---------------------------------------------------------------------
# (a) the four sites: a new key's first dispatch is ONE `jax.compile`
# ---------------------------------------------------------------------

def _site_train_step():
    op = Small({}, 0, 1)
    return (lambda: op.train_epoch(num_steps=1)), "train.step:fused:4x16"


def _site_eval():
    op = Small({}, 0, 1)
    return (lambda: op.validate(num_steps=1)), "train.step:eval:4x16"


def _site_collective():
    from jax.sharding import Mesh

    from ray_tpu.collective.backends.xla_backend import _DeviceOps
    from ray_tpu.collective.types import ReduceOp

    ops = _DeviceOps(Mesh(np.array(jax.devices("cpu")[:1]), ("hosts",)),
                     "hosts", 1)
    garr = jnp.ones((1, 48), jnp.float32)
    return (lambda: ops.allreduce(garr, ReduceOp.SUM)), \
        "collective:ar:exact:sum:float32:48:hosts:1"


def _site_kv_update():
    from ray_tpu.serve.kv_cache import PagedKVCache

    kv = PagedKVCache(8, 4, 4, name="kv:seam_test", backend="jax")
    kv.alloc_table("seq")
    return (lambda: kv.append("seq", np.ones((1, 4), np.float32))), \
        "serve.kv_update:8x4x4"


@pytest.mark.parametrize("site", [_site_train_step, _site_eval,
                                  _site_collective, _site_kv_update])
def test_first_dispatch_is_one_jax_compile(site):
    """What the export cache's miss path guarded: a new key's first
    dispatch records exactly one `jax.compile` under the seam's key,
    with what jax itself timed inside it; a second call records none,
    and nothing named `compile.*` exists."""
    call, key = site()
    total = profiling.M_COMPILES.snapshot()["value"]
    first = _spans_of(call)
    compiles = [(n, a) for n, a in first
                if n.startswith(("compile.", "jax.compile"))]
    assert [(n, a["key"]) for n, a in compiles] == [("jax.compile", key)]
    timed = compiles[0][1]
    assert timed["programs"] >= 1 and timed["backend_s"] > 0
    assert timed["trace_s"] >= 0 and timed["lower_s"] >= 0
    assert timed["persistent_hit"] == 0     # the test tree: cache off
    assert profiling.M_COMPILES.snapshot()["value"] == total + 1
    assert profiling.compile_state()["last_key"] == key
    assert [n for n, _ in _spans_of(call)
            if n.startswith(("compile.", "jax.compile"))] == []
    assert profiling.M_COMPILES.snapshot()["value"] == total + 1


# ---------------------------------------------------------------------
# (b) the jax.monitoring listener lives only inside a first dispatch
# ---------------------------------------------------------------------

@pytest.mark.parametrize("case", ["one_thread", "two_threads", "nested"])
def test_listener_lives_only_inside_a_first_dispatch(case):
    """The one `jax.monitoring` listener is registered while a first
    dispatch is open — once, however many are open, in whatever thread,
    one inside another — and gone afterwards; each dispatch keeps its
    own timings."""
    live = []

    def fn(a):
        live.append(_listeners())       # while the program is traced
        return a * 2.0 + 1.0

    x = jnp.ones((8,), jnp.float32)
    assert _listeners() == 0
    if case == "one_thread":
        probe = profiling.CompileProbe("unit:one", jax.jit(fn))
        spans = _spans_of(lambda: probe(x))
        assert [a["key"] for _, a in spans] == ["unit:one"]
        probe(x)                        # resolved: no listener again
    elif case == "two_threads":
        inside, go = threading.Barrier(2), threading.Event()

        def slow(i):
            def fn(a):
                live.append(_listeners())
                inside.wait(timeout=scale_timeout(60))  # both traces open
                return a - float(i)
            return fn       # (two jits of ONE function trace in turn)

        probes = [profiling.CompileProbe(f"unit:thread{i}",
                                         jax.jit(slow(i)))
                  for i in range(2)]
        out = [None, None]

        def run(i):
            go.wait(timeout=scale_timeout(60))
            out[i] = np.asarray(probes[i](x))

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        go.set()
        for t in threads:
            t.join(timeout=scale_timeout(120))
            assert not t.is_alive()
        assert [o[0] for o in out] == [1.0, 0.0]
    else:
        inner = profiling.CompileProbe("unit:inner", jax.jit(fn))

        def outer_fn(a):
            live.append(_listeners())
            # a seam resolving inside another's trace: the collective
            # inside a step, say
            return inner(a) + 1.0

        outer = profiling.CompileProbe("unit:outer", jax.jit(outer_fn))
        spans = _spans_of(lambda: outer(x))
        assert sorted(a["key"] for _, a in spans) == [
            "unit:inner", "unit:outer"]
        by_key = {a["key"]: a for _, a in spans}
        # the outer's timings are its own, not the sum with the inner's
        assert by_key["unit:outer"]["programs"] == 1
    assert live and set(live) == {1}
    assert _listeners() == 0


# ---------------------------------------------------------------------
# (c) a traced train() call, as benchmark/start_log.py reads it
# ---------------------------------------------------------------------

def test_a_train_calls_tree_has_one_jax_compile_and_no_compile_span(
        ray_start_shared):
    """`first_call_entry` takes a first call whose `jax.compile` carries
    `backend_s`; `first_step_trace_s` / `first_step_load_s` sum
    `trace_s` + `lower_s` / `backend_s` and read absent `compile.*`
    spans as 0."""
    tr = Trainer(Small, num_workers=1)
    try:
        tr.train(num_steps=2)
        first = call_log()[-1]
        tr.train(num_steps=2)
        second = call_log()[-1]
    finally:
        tr.shutdown(force=True)
    assert not [s["name"] for s in first["spans"] + second["spans"]
                if s["name"].startswith("compile.")]
    (compiled,) = [s for s in first["spans"] if s["name"] == "jax.compile"]
    assert compiled["attrs"]["key"] == "train.step:fused:4x16"
    assert {"trace_s", "lower_s", "backend_s", "persistent_hit",
            "programs"} <= set(compiled["attrs"])
    took = compiled["end"] - compiled["start"]
    assert 0 < compiled["attrs"]["backend_s"] <= took
    assert not [s for s in second["spans"] if s["name"] == "jax.compile"]


# ---------------------------------------------------------------------
# (d), (e) the step: traced once, donating, its text consumes nothing
# ---------------------------------------------------------------------

def test_the_loss_is_traced_once_in_a_first_call():
    """Nothing calls `make_jaxpr` or an export on the step: a first
    epoch runs the loss function's Python once, a second never."""
    op = Small({}, 0, 1)
    del TRACED[:]
    op.train_epoch(num_steps=2)
    assert TRACED == [1]
    op.train_epoch(num_steps=2)
    assert TRACED == [1]


def test_a_donating_step_and_its_text():
    """The fused step donates its state; its first dispatch goes through
    the probe with the buffers it was given, and `compiled_step_text`
    lowers and compiles without consuming one."""
    op = Small({}, 0, 1)
    batch = np.ones((4, 16), np.float32)
    before = jax.tree.leaves((op.params, op.opt_state))
    text = op.compiled_step_text(batch)
    assert "HloModule" in text
    assert not any(x.is_deleted() for x in before)
    (key, probe), = op._step_cache.items()
    assert key == ("fused", "4x16") and probe.donate_argnums == (0, 2)
    assert probe.key == "train.step:fused:4x16"
    op.train_epoch(num_steps=1)             # the first dispatch: donates
    assert all(x.is_deleted() for x in before)
    after = jax.tree.leaves((op.params, op.opt_state))
    assert op.compiled_step_text(batch) == text
    assert not any(x.is_deleted() for x in after)
    assert list(op._step_cache) == [("fused", "4x16")]


# ---------------------------------------------------------------------
# (f) `_DeviceOps`: two ops never share a program
# ---------------------------------------------------------------------

@pytest.mark.parametrize("differ", ["op", "dtype", "quantize"])
def test_device_ops_keys_differ_by(differ):
    """Two collective ops that differ in the reduction, the dtype or the
    wire format resolve to different in-process keys, each recorded as a
    compile of its own (an int8-ring executable used for an exact op
    would silently corrupt results)."""
    from jax.sharding import Mesh

    from ray_tpu.collective.backends.xla_backend import _DeviceOps
    from ray_tpu.collective.types import QUANT_BLOCK, ReduceOp

    ops = _DeviceOps(Mesh(np.array(jax.devices("cpu")[:1]), ("hosts",)),
                     "hosts", 1)
    n = QUANT_BLOCK * 2     # a valid layout for the exact and int8 rings
    garr = jnp.ones((1, n), jnp.float32)
    ops.allreduce(garr, ReduceOp.SUM)
    if differ == "op":
        ops.allreduce(garr, ReduceOp.MAX)
    elif differ == "dtype":
        ops.allreduce(garr.astype(jnp.bfloat16), ReduceOp.SUM)
    else:
        ops.allreduce_quantized(garr, ReduceOp.SUM)
    first, second = ops._cache
    assert first != second
    assert ops._cache[first].key != ops._cache[second].key
    ops.allreduce(garr, ReduceOp.SUM)       # a seen key: the same probe
    assert len(ops._cache) == 2


# ---------------------------------------------------------------------
# (g) a failed first dispatch
# ---------------------------------------------------------------------

def test_a_failed_first_dispatch_is_retried_and_recorded_once():
    """A first dispatch that raises proved no compile: nothing is
    recorded, no listener is left behind, and the retry is timed and
    recorded as the first."""
    fail = [True]

    def fn(a):
        if fail[0]:
            raise MemoryError("a transient failure while tracing")
        return a + 1.0

    x = jnp.ones((8,), jnp.float32)
    probe = profiling.CompileProbe("unit:retry", jax.jit(fn))
    total = profiling.M_COMPILES.snapshot()["value"]
    with pytest.raises(MemoryError):
        _spans_of(lambda: probe(x))
    assert profiling.M_COMPILES.snapshot()["value"] == total
    assert _listeners() == 0
    fail[0] = False
    spans = _spans_of(lambda: probe(x))
    assert [(n, a["key"]) for n, a in spans] == [
        ("jax.compile", "unit:retry")]
    assert profiling.M_COMPILES.snapshot()["value"] == total + 1
    assert _spans_of(lambda: probe(x)) == []


# ---------------------------------------------------------------------
# (h) a process's debug snapshot
# ---------------------------------------------------------------------

def test_a_snapshot_has_jax_compiles_and_no_compile_cache(ray_start_shared):
    from ray_tpu._private import debug_state, global_state

    probe = profiling.CompileProbe("unit:snapshot",
                                   jax.jit(lambda a: a * 3.0))
    probe(jnp.ones((4,), jnp.float32))
    snap = global_state.require_core_worker().debug_state()
    assert snap["jax_compiles"]["total"] >= 1
    assert snap["jax_compiles"]["last_key"] == "unit:snapshot"
    assert "compile_cache" not in snap
    # ... and the doctor has no finding about a cache of the repo's own
    assert not [f for f in debug_state.diagnose({"driver": snap}, {})
                if f["kind"].startswith("compile_cache")]


# ---------------------------------------------------------------------
# (i) the cache that stays: a second process finds the executable
# ---------------------------------------------------------------------

_RESTARTED = """
import json, sys
from ray_tpu._private import compile_cache
where = compile_cache.enable_persistent_cache()
import jax, jax.numpy as jnp
# the CPU backend compiles this in milliseconds: persist it all the same
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from ray_tpu._private import profiling, tracing

def step(w, x):
    return jnp.tanh(x @ w).sum()

probe = profiling.CompileProbe("unit:restart", jax.jit(jax.grad(step)))
root = tracing.new_context()
with tracing.open_tree(root) as rows, tracing.use(root):
    probe(jnp.ones((32, 32)), jnp.ones((4, 32)))
(name, _, _, fields), = rows
print("RESULT", json.dumps({"where": where, "name": name, "attrs": {
    k: v for k, v in fields.items() if k not in ("tid", "sid", "psid")}}))
"""


def test_a_second_process_loads_from_jaxs_cache(tmp_path):
    """In place of the gang-restart gate and the recorded MICROBENCH
    row, a live count: two processes on one JAX_COMPILATION_CACHE_DIR —
    the first compiles (`persistent_hit` 0), the second traces, lowers
    and LOADS (`persistent_hit` 1), each recorded as one `jax.compile`."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_ENABLE_COMPILATION_CACHE"}
    env.update(PYTHONPATH=REPO, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    seen = []
    for _ in range(2):
        out = subprocess.run([sys.executable, "-c", _RESTARTED], env=env,
                             capture_output=True, text=True,
                             timeout=scale_timeout(180))
        line = [l for l in out.stdout.splitlines()
                if l.startswith("RESULT ")]
        assert line, out.stdout + out.stderr
        seen.append(json.loads(line[-1][len("RESULT "):]))
    for row in seen:
        assert row["where"] == str(tmp_path)
        assert row["name"] == "jax.compile"
        assert row["attrs"]["key"] == "unit:restart"
        assert row["attrs"]["trace_s"] > 0 and row["attrs"]["programs"] >= 1
    assert [row["attrs"]["persistent_hit"] for row in seen] == [0, 1]
    assert seen[1]["attrs"]["cache_retrieval_s"] >= 0
    assert "cache_retrieval_s" not in seen[0]["attrs"]
    assert os.listdir(tmp_path)
