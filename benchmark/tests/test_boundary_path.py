"""``boundary_path.py`` and its six readers on a synthetic call log of
two-piece calls whose worker and driver spans OVERLAP (what
``span_log.split`` cannot add up), and the pure parts of
``tools/spans_on_trace.py``: no runtime, no chip."""

import pytest

from benchmark import boundary_path, manifest, span_log
from benchmark.tools import spans_on_trace

PIECE = "TrainWorker.state_piece"


def _call(t0, epoch=1.0, copy=(0.2, 0.1), writes=2, new=True):
    """One call's tree from `t0`: a 10 ms hop, the epoch, then two
    pieces. The worker brings a piece in 0.3 s of d2h (0.01 starting,
    0.25 on the link, 0.03 joining) and 0.1 s of put, stands still for
    0.05 s, then brings the next; the driver gets each in 0.01 s and
    copies it in `copy[i]` s while the worker is at the next piece.
    `new=False`: the tree as the parent of the PR recorded it."""
    spans = []

    def add(kind, start, end, span, parent, **attrs):
        if not new:
            attrs = {k: v for k, v in attrs.items() if k in (
                "bytes", "name", "pieces", "reused_bytes", "steps")}
        spans.append({"name": kind, "start": start, "end": end,
                      "span": span, "parent": parent, "attrs": attrs})

    t = t0 + 0.01
    add("train.epoch", t, t + epoch, "ep", "root")
    add("task", t, t + epoch, "t0", "ep", name="TrainWorker.train_epoch")
    add("train.dispatch", t, t + epoch - 0.5, "di", "t0", steps=2)
    add("train.sync", t + epoch - 0.5, t + epoch, "sy", "t0")
    snap = worker = driver = t + epoch
    for i in range(2):
        add("task", worker, worker + 0.4, f"t{i + 1}", f"e{i}", name=PIECE)
        add("train.snapshot.d2h", worker, worker + 0.3, f"d{i}",
            f"t{i + 1}", bytes=2**30, piece=i, start_s=0.01, wait_s=0.25,
            join_s=0.03)
        add("object.return_put", worker + 0.3, worker + 0.4, f"p{i}",
            f"t{i + 1}", bytes=2**30)
        add("task.e2e", snap, worker + 0.401, f"e{i}", "sn", name=PIECE)
        put = worker + 0.4
        worker = put + 0.05
        wait_end = max(driver, put) + 0.011
        if new:
            add("train.snapshot.wait", driver, wait_end, f"w{i}", "sn",
                piece=i)
        add("object.get", wait_end - 0.01, wait_end, f"g{i}",
            f"w{i}" if new else "sn", bytes=2**30)
        add("train.snapshot.copy", wait_end, wait_end + copy[i], f"c{i}",
            "sn", bytes=2**30, reused_bytes=2**30, piece=i,
            dest_writes=writes)
        driver = wait_end + copy[i]
    add("train.snapshot", snap, driver, "sn", "root", pieces=2, bytes=2**31)
    add("train.call", t0, driver, "root", None)
    return {"trace_id": str(t0), "spans": spans}, driver - t0


def _run(monkeypatch, new=True):
    """`first`, `warm`, then a window of four calls: two second writes
    (slow copies), two steady ones."""
    shapes = [((0.5, 0.4), 0), ((0.5, 0.4), 0), ((0.6, 0.5), 1),
              ((0.7, 0.5), 1), ((0.2, 0.1), 2), ((0.2, 0.1), 2)]
    log, walls, t = [], [], 50.0
    for copy, writes in shapes:
        entry, wall = _call(t, copy=copy, writes=writes, new=new)
        log.append(entry)
        walls.append(wall)
        t += wall + 0.002
    import ray_tpu.train

    monkeypatch.setattr(ray_tpu.train, "call_log", lambda: list(log),
                        raising=False)
    return {"attempted": 6, "first": {"wall_s": walls[0]},
            "calls": [{"wall_s": w, "epoch_s": 1.0} for w in walls[2:]]}


def _read(name, host):
    return manifest.module("layer_metrics", name).read(host, None)


def test_one_call_along_the_drivers_thread():
    entry, wall = _call(10.0)
    path = boundary_path.call_path(entry)
    # piece 0: the driver waits out the worker's 0.4 s (+ 1 ms of reply);
    # piece 1 is put at 0.85 s, 0.239 s after the first copy ended
    assert path["wait_s"] == pytest.approx(0.401 + 0.240)
    assert path["get_s"] == pytest.approx(0.02)
    assert path["copy_s"] == pytest.approx(0.3)
    assert path["hops_s"] == pytest.approx(0.01)
    assert path["boundary_s"] == pytest.approx(wall - 1.0)
    assert path["link_wait_s"] == pytest.approx(0.5)
    assert path["join_s"] == pytest.approx(0.06)
    assert path["start_s"] == pytest.approx(0.02)
    assert path["d2h_s"] == pytest.approx(0.6)
    assert path["starved_s"] == pytest.approx(0.05)
    assert path["dest_writes"] == [2] and path["pieces"] == 2
    # the leaf spans of both processes are MORE than the boundary: why
    # the older `snapshot_*` readers leave multi-piece cells out
    leaves = span_log.split(entry)
    assert leaves["d2h_s"] + leaves["put_s"] + leaves["get_s"] \
        + leaves["copy_s"] > path["boundary_s"] + 0.1
    assert leaves["hop_s"] < 0


def test_the_six_readers_over_a_window(monkeypatch):
    host = _run(monkeypatch)
    # waits: a slow copy hides the next piece's whole chain but 1 ms
    assert _read("boundary_wait_s", host) == pytest.approx(
        ((0.401 + 0.001) + (0.401 + 0.240)) / 2)
    assert _read("snapshot_link_wait_s", host) == pytest.approx(0.5)
    assert _read("snapshot_join_s", host) == pytest.approx(0.06)
    assert _read("snapshot_worker_starved_s", host) == pytest.approx(0.05)
    # the window's first two calls only: 1.1 and 1.2 s
    assert _read("snapshot_copy_rewrite_s", host) == pytest.approx(1.15)
    assert manifest.module("layer_metrics", "snapshot_copy_s").read(
        host, None) == pytest.approx((0.3 + 1.1) / 2)
    # the first call's whole pull: two chains' worth, then its last copy
    assert _read("first_pull_s", host) == pytest.approx(
        0.411 + 0.5 + 0.011 + 0.4)
    for p, call in zip(boundary_path.window_paths(host), host["calls"]):
        assert p["wait_s"] + p["get_s"] + p["copy_s"] + p["hops_s"] \
            == pytest.approx(call["wall_s"] - call["epoch_s"])


def test_the_parents_trees_give_none(monkeypatch):
    host = _run(monkeypatch, new=False)
    for name in ("boundary_wait_s", "snapshot_link_wait_s",
                 "snapshot_join_s", "snapshot_worker_starved_s",
                 "snapshot_copy_rewrite_s"):
        assert _read(name, host) is None, name
    assert _read("first_pull_s", host) > 1.0    # PR 25's span: there
    assert _read("first_pull_s", dict(host, attempted=7)) is None
    assert _read("first_pull_s", {"attempted": 6, "calls": []}) is None
    host["first"]["wall_s"] += 0.01             # not the first call's
    assert _read("first_pull_s", host) is None


def test_spans_on_trace_pairs_and_covers():
    entry, _ = _call(1000.0)
    offset = 990.0      # span clock − profiler clock
    worker = [s for s in entry["spans"]
              if s["name"] in ("train.sync", "train.snapshot.d2h")]
    notes = {}
    for s in worker:
        notes.setdefault(s["name"], []).append(
            (s["start"] - offset, s["end"] - offset - 1e-5))
    notes["train.dispatch"] = []        # not as often as in the log: out
    pairs = spans_on_trace.offsets(entry, notes)
    assert [r["name"] for r in pairs] == ["train.sync"] + [
        "train.snapshot.d2h"] * 2
    assert all(r["offset_s"] == pytest.approx(offset) for r in pairs)
    # the boundary, by the innermost span of each thread
    (sync,) = [s for s in entry["spans"] if s["name"] == "train.sync"]
    (root,) = [s for s in entry["spans"] if s["name"] == "train.call"]
    driver = spans_on_trace.innermost(
        entry, spans_on_trace.DRIVER, sync["end"], root["end"])
    assert driver == pytest.approx({
        "train.snapshot.wait": 0.401 + 0.240, "object.get": 0.02,
        "train.snapshot.copy": 0.3})
    lane = spans_on_trace.innermost(
        entry, spans_on_trace.WORKER, sync["end"], root["end"])
    # (slivers of float rounding between a task and its children aside)
    assert {k: v for k, v in lane.items() if v > 1e-9} == pytest.approx({
        "train.snapshot.d2h": 0.6, "object.return_put": 0.2,
        "(none)": 0.05 + root["end"] - sync["end"] - 0.85})
