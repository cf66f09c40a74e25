"""The readers of the program's call log (``span_log.py`` and the
``snapshot_*``, ``call_hop_s``, ``idle_gap_named_share`` metrics) on a
synthetic ``host`` record and a synthetic log: no runtime, no chip."""

import pytest

from benchmark import manifest, span_log

READERS = ("snapshot_d2h_s", "snapshot_put_s", "snapshot_get_s",
           "snapshot_copy_s", "call_hop_s", "snapshot_gib",
           "idle_gap_named_share")


def _call(t0, epoch, d2h, put, get, copy, hop=0.02, size=2**30):
    """One call's tree as `call_log()` gives it, starting at `t0`: half
    of `hop` before the epoch, half between put and get."""
    spans, t = [], t0 + hop / 2

    def add(kind, start, end, span, parent, **attrs):
        spans.append({"name": kind, "start": start, "end": end,
                      "span": span, "parent": parent, "attrs": attrs})

    add("train.epoch", t, t + epoch, "ep", "root")
    add("task", t, t + epoch, "t1", "ep", name="TrainWorker.train_epoch")
    add("train.dispatch", t, t + epoch - 0.5, "di", "t1", steps=2)
    add("train.sync", t + epoch - 0.5, t + epoch, "sy", "t1")
    t += epoch
    snap = t
    add("train.snapshot.d2h", t, t + d2h, "dh", "t2", bytes=size, leaves=3)
    add("object.return_put", t + d2h, t + d2h + put, "pu", "t2",
        bytes=size + 100)
    add("task", t, t + d2h + put, "t2", "e2", name="TrainWorker.state_dict")
    t += d2h + put + hop / 2
    add("task.e2e", snap, t, "e2", "sn")
    add("object.get", t, t + get, "ge", "sn", bytes=size + 100)
    add("train.snapshot.copy", t + get, t + get + copy, "co", "sn",
        bytes=size)
    t += get + copy
    add("train.snapshot", snap, t, "sn", "root")
    # an object.get that is NOT the snapshot's (the epoch's result)
    add("object.get", t0, t0 + 0.001, "g0", "ep", bytes=5)
    add("train.call", t0, t, "root", None, num_steps=2, workers=1)
    return {"trace_id": f"{t0}", "spans": spans}, t - t0


@pytest.fixture
def window(monkeypatch):
    """A host record of three window calls after `first` and `warm`, and
    the log to go with it; returns (host, log)."""
    parts = [(1.0, 0.8, 0.6, 0.01, 0.5), (1.0, 0.9, 0.4, 0.01, 0.5),
             (1.0, 1.0, 0.5, 0.03, 0.7)]
    log, calls, t = [], [], 100.0
    for i, (epoch, d2h, put, get, copy) in enumerate(
            [(0.5, 0.1, 0.1, 0.0, 0.1)] * 2 + parts):
        entry, wall = _call(t, epoch, d2h, put, get, copy)
        log.append(entry)
        if i >= 2:
            calls.append({"wall_s": wall, "epoch_s": epoch})
        t += wall + 0.001          # the benchmark's own loop between calls
    host = {"calls": calls, "attempted": len(log)}
    import ray_tpu.train

    monkeypatch.setattr(ray_tpu.train, "call_log", lambda: list(log),
                        raising=False)
    return host, log


def _read(name, host):
    return manifest.module("layer_metrics", name).read(host, None)


def test_medians_over_the_windows_calls(window):
    host, _ = window
    assert _read("snapshot_d2h_s", host) == pytest.approx(0.9)
    assert _read("snapshot_put_s", host) == pytest.approx(0.5)
    assert _read("snapshot_get_s", host) == pytest.approx(0.01)
    assert _read("snapshot_copy_s", host) == pytest.approx(0.5)
    assert _read("call_hop_s", host) == pytest.approx(0.02)
    assert _read("snapshot_gib", host) == 1.0
    # each call: the five parts are the boundary the driver's clock sees
    for call, entry in zip(host["calls"], span_log.window_entries(host)):
        p = span_log.split(entry)
        assert p["d2h_s"] + p["put_s"] + p["get_s"] + p["copy_s"] \
            + p["hop_s"] == pytest.approx(call["wall_s"] - call["epoch_s"])


def test_the_one_millisecond_match_rule(window):
    host, _ = window
    host["calls"][1]["wall_s"] += 0.0009
    assert _read("snapshot_d2h_s", host) is not None
    host["calls"][1]["wall_s"] += 0.0002
    for name in READERS:
        assert _read(name, host) is None, name


def test_positions_follow_the_ring(window):
    host, log = window
    host["attempted"] += 1          # the traced call came after
    assert _read("snapshot_put_s", host) is None   # ... and is not in the log
    entry, _ = _call(500.0, 1.0, 0.1, 0.1, 0.0, 0.1)
    log.append(entry)
    assert _read("snapshot_put_s", host) == pytest.approx(0.5)
    del log[0]                      # the ring dropped `first`
    assert _read("snapshot_put_s", host) == pytest.approx(0.5)
    del log[0:2]                    # ... and a window call: no match
    assert _read("snapshot_put_s", host) is None


def test_none_without_a_log(window, monkeypatch):
    host, _ = window
    import ray_tpu.train

    monkeypatch.delattr(ray_tpu.train, "call_log")
    for name in READERS:
        assert _read(name, host) is None, name
    monkeypatch.setattr(ray_tpu.train, "call_log", lambda: [],
                        raising=False)
    for name in READERS:
        assert _read(name, host) is None, name
    assert _read("snapshot_d2h_s", {"calls": [], "attempted": 2}) is None


def test_gap_union_arithmetic(window):
    assert span_log.covered([(0, 2), (1, 3), (5, 6)], 0.5, 5.5) == 3.0
    assert span_log.covered([(0, 1)], 2, 3) == 0.0
    assert span_log.covered([(0, 10), (2, 3)], 1, 4) == 3.0
    host, _ = window
    # gap k: from sync's end through d2h, put, hop/2, get, copy, the
    # loop's 1 ms and the next call's hop/2 (of which 1 ms is the
    # `object.get` of another object); the leaves are named
    leaves = (0.8 + 0.6 + 0.01 + 0.5) + (0.9 + 0.4 + 0.01 + 0.5)
    named = leaves + 2 * 0.001
    gap = leaves + 2 * (0.01 + 0.001 + 0.01)
    assert _read("idle_gap_named_share", host) == pytest.approx(
        100 * named / gap)
    host["calls"] = host["calls"][:1]     # one call: no gap to share out
    assert _read("idle_gap_named_share", host) is None


def test_kernel_shares_read_named_mosaic_operations():
    trace = {"busy_s": 2.0, "mosaic_s": 0.5,
             "mosaic_ops": ["flash_fwd.13", "flash_fwd.14", "layernorm.26",
                            "jvp_layernorm_.1"],
             "op_self_s": {"flash_fwd.13": 0.2, "flash_fwd.14": 0.1,
                           "layernorm.26": 0.15, "jvp_layernorm_.1": 0.05,
                           "copy-start.104": 0.3, "fusion.1": 1.2}}
    assert _read_trace("flash_fwd_time_share", trace) == pytest.approx(15.0)
    assert _read_trace("layernorm_time_share", trace) == pytest.approx(10.0)
    # kernels without a name (the parent's closed_call.N): nothing to read
    unnamed = dict(trace, mosaic_ops=["closed_call.19"],
                   op_self_s={"closed_call.19": 0.5})
    for name in ("flash_fwd_time_share", "layernorm_time_share"):
        assert _read_trace(name, unnamed) is None
        assert _read_trace(name, None) is None


def _read_trace(name, trace):
    return manifest.module("layer_metrics", name).read({}, trace)
