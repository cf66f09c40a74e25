"""The readers of the set-up's two trees (``start_log.py`` and the
``worker_*``, ``*_init_s``, ``first_step_*``, ``epoch_first_dispatch_s``
metrics) on a synthetic ``host`` record and synthetic logs: no runtime,
no chip."""

import pytest

# the window's synthetic call log is `test_span_metrics.py`'s
from test_span_metrics import _read, window  # noqa: F401


START_READERS = ("worker_spawn_s", "chip_wait_s", "backend_init_s",
                 "state_init_s", "worker_start_named_share")
FIRST_READERS = ("first_step_trace_s", "first_step_load_s",
                 "first_step_named_share")


def _span(kind, start, end, **attrs):
    return {"name": kind, "start": start, "end": end, "span": kind + str(start),
            "parent": None, "attrs": attrs}


def _start_tree(t0=50.0):
    """A worker start of 20 s: 1 s of scheduling before the `Popen`,
    the worker's life, `train.setup`, and 0.4 s nobody names (0.1 after
    the boot, 0.1 before the first call, 0.2 of reply)."""
    spans = [
        _span("train.start", t0, t0 + 20, generation=1, workers=1,
              restored=0),
        _span("worker.spawn", t0 + 1, t0 + 3, flavor="tpu", pid=7),
        _span("worker.boot", t0 + 3, t0 + 3.5, flavor="tpu", pid=7),
        _span("worker.actor_init", t0 + 3.6, t0 + 7, load_s=2.0),
        # the wait runs inside the actor's creation task
        _span("worker.chip_wait", t0 + 5.6, t0 + 5.9, waited_s=0.3, held=1),
        _span("train.setup", t0 + 7.1, t0 + 19.8),
        _span("train.setup.backend", t0 + 7.1, t0 + 15.1, platform="tpu",
              devices=1),
        _span("train.setup.user", t0 + 15.1, t0 + 16.1),
        _span("train.setup.init", t0 + 16.1, t0 + 18.1, bytes=10),
        _span("train.setup.place", t0 + 18.1, t0 + 19.6, state_bytes=30),
        _span("train.setup.user", t0 + 19.6, t0 + 19.8)]
    return {"trace_id": "start", "spans": spans}


def _first_call_tree(t0=80.0):
    """A first call of 10 s: a program the export cache holds (hit,
    load) and an AdamW step it cannot hold (export raises, the plain jit
    traces, lowers and loads), the epoch's sync, the first pull, and
    0.5 s nobody names."""
    key = {"key": "train.step:fused:x"}
    spans = [
        _span("train.call", t0, t0 + 10, num_steps=1, workers=1, call=1),
        _span("compile.fingerprint", t0 + 0.1, t0 + 0.6, **key),
        _span("compile.lookup", t0 + 0.6, t0 + 0.7, hit=0, bytes=0, **key),
        _span("compile.export", t0 + 0.7, t0 + 1.7, error=1, bytes=0, **key),
        _span("jax.compile", t0 + 1.7, t0 + 4.7, trace_s=0.75,
              lower_s=0.25, backend_s=1.5, cache_retrieval_s=1.25,
              persistent_hit=1, programs=1, **key),
        _span("compile.lookup", t0 + 4.7, t0 + 4.8, hit=1, bytes=9),
        _span("compile.load", t0 + 4.8, t0 + 5.3, ok=1, backend_s=0.4),
        _span("train.dispatch", t0 + 0.05, t0 + 5.3, steps=1),
        _span("train.sync", t0 + 5.3, t0 + 5.8),
        _span("train.snapshot", t0 + 5.9, t0 + 9.9, deferred=0, of_call=1)]
    return {"trace_id": "first", "spans": spans}


@pytest.fixture
def setup_logs(monkeypatch):
    """A host record and the two logs to go with it; returns (host,
    start log, call log), the logs lists the readers see live."""
    starts, calls = [_start_tree()], [_first_call_tree()]
    host = {"phases": {"worker_start_s": 20.05}, "attempted": 1,
            "first": {"wall_s": 10.0004}, "calls": []}
    import ray_tpu.train

    monkeypatch.setattr(ray_tpu.train, "start_log", lambda: list(starts),
                        raising=False)
    monkeypatch.setattr(ray_tpu.train, "call_log", lambda: list(calls),
                        raising=False)
    return host, starts, calls


def test_the_starts_tree_by_part(setup_logs):
    host, _, _ = setup_logs
    assert _read("worker_spawn_s", host) == pytest.approx(3.5)
    assert _read("chip_wait_s", host) == pytest.approx(0.3)
    assert _read("backend_init_s", host) == pytest.approx(8.0)
    assert _read("state_init_s", host) == pytest.approx(2.0 + 1.5)
    # the union: the wait lies inside the constructor's span and counts
    # once; 0.1 + 0.1 + 0.2 s of 20 have no name
    assert _read("worker_start_named_share", host) == pytest.approx(98.0)


def test_the_first_calls_tree_by_part(setup_logs):
    host, _, _ = setup_logs
    # fingerprint + export + jax's own trace and lowering of the plain jit
    assert _read("first_step_trace_s", host) == pytest.approx(
        0.5 + 1.0 + 0.75 + 0.25)
    # the export cache's load + jax's compile-or-load of the plain jit
    # (a load's own `backend_s` is inside its span: not counted twice)
    assert _read("first_step_load_s", host) == pytest.approx(0.5 + 1.5)
    assert _read("first_step_named_share", host) == pytest.approx(
        100 * (5.2 + 0.5 + 4.0) / 10)


@pytest.mark.parametrize("readers, what", [
    (START_READERS, "no_start_log"), (START_READERS, "no_spans"),
    (START_READERS, "a_restart"), (START_READERS, "clock_apart"),
    (FIRST_READERS, "no_call_log"), (FIRST_READERS, "no_spans"),
    (FIRST_READERS, "ring_dropped"), (FIRST_READERS, "clock_apart")])
def test_setup_readers_give_none(setup_logs, monkeypatch, readers, what):
    """None on a program without the log or the spans (the parent), and
    where a root and the run's own clock part by more than 1 %."""
    host, starts, calls = setup_logs
    import ray_tpu.train

    if what == "no_start_log":
        monkeypatch.delattr(ray_tpu.train, "start_log")
    elif what == "no_call_log":
        monkeypatch.delattr(ray_tpu.train, "call_log")
    elif what == "no_spans" and readers is START_READERS:
        # the parent's start, had it a log: a root and nothing named
        starts[0]["spans"] = starts[0]["spans"][:1]
    elif what == "no_spans":
        # the parent's first call: `jax.compile` without jax's timings
        calls[0]["spans"] = [
            dict(s, attrs={"key": "k", "compile_s": 3.0})   # as it was
            if s["name"] == "jax.compile" else s
            for s in calls[0]["spans"] if not s["name"].startswith("compile.")]
    elif what == "a_restart":
        starts.append(_start_tree(90.0))
    elif what == "ring_dropped":
        host["attempted"] = 300
    elif readers is START_READERS:
        host["phases"]["worker_start_s"] = 20.0 * 1.011
        assert _read("worker_spawn_s", dict(
            host, phases={"worker_start_s": 20.0 * 1.009})) is not None
    else:
        host["first"]["wall_s"] = 10.0 * 1.011
        assert _read("first_step_load_s", dict(
            host, first={"wall_s": 10.0 * 1.009})) is not None
    for name in readers:
        assert _read(name, host) is None, name


def test_epoch_first_dispatch_is_the_windows_median(window):
    host, log = window
    assert _read("epoch_first_dispatch_s", host) is None   # the parent's span
    for entry, first in zip(log, (9.0, 9.0, 0.07, 0.02, 0.03)):
        for span in entry["spans"]:
            if span["name"] == "train.dispatch":
                span["attrs"]["first_dispatch_s"] = first
    assert _read("epoch_first_dispatch_s", host) == pytest.approx(0.03)
    host["calls"][1]["wall_s"] += 0.0011     # the position rule
    assert _read("epoch_first_dispatch_s", host) is None
