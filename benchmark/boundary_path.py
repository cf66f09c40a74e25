"""The ``train()`` boundary as WALL time, whatever the number of pieces:
its critical path read from the call's span tree (``span_log.py``'s
window, ``ray_tpu.train.call_log()``).

``span_log.split`` sums leaf spans of two processes, which overlap once
a state crosses in several pieces (the worker brings piece k + 1 while
the driver copies piece k). Here the boundary is cut along ONE thread,
the driver's: under ``train.snapshot`` it does, a piece,
``train.snapshot.wait`` (blocked in ``ray_tpu.get`` until the worker has
put the piece; ``object.get`` is its child) and ``train.snapshot.copy``.
Those spans follow one another and never overlap, so

    boundary = wait (self) + get + copy + hops

where the wait's self time is the part in which the WORKER's chain
(device→host, put) was on the critical path, and the hops are what is
left of ``train.call`` beside the worker's epoch: the epoch's actor
round trip, the submits, tree flattening, ``_reduce``. The worker's side
is read from the counts it keeps on ``train.snapshot.d2h`` (``start_s``,
``wait_s``, ``join_s``) and from its lane's ``task`` spans.

The readers ``boundary_wait_s``, ``snapshot_link_wait_s``,
``snapshot_join_s``, ``snapshot_worker_starved_s``,
``snapshot_copy_rewrite_s`` and ``first_pull_s`` share this file. A
program whose trees lack the spans or counts (the parent of the PR that
added them) gives None everywhere."""

from __future__ import annotations

import statistics

from benchmark import span_log
from benchmark.span_log import covered, under, window_entries

PIECE_TASK = "TrainWorker.state_piece"


def _seconds(span) -> float:
    return span["end"] - span["start"]


def _one(entry, name) -> dict:
    (span,) = [s for s in entry["spans"] if s["name"] == name]
    return span


def pieces(entry) -> list[dict]:
    """One row a piece of the call's snapshot, by the `piece` its spans
    carry: the driver's `wait_s` (self: less the `object.get` beneath
    it), `get_s`, `copy_s` and the copy's `dest_writes`; the worker's
    `d2h` attributes. KeyError on a tree without `train.snapshot.wait`
    or `piece` (a program before them)."""
    waits = under(entry, "train.snapshot.wait", "train.snapshot")
    if not waits:
        raise KeyError("train.snapshot.wait")
    rows = {}
    for wait in waits:
        gets = [s for s in entry["spans"] if s["name"] == "object.get"
                and s["parent"] == wait["span"]]
        get_s = sum(map(_seconds, gets))
        rows[wait["attrs"]["piece"]] = {
            "piece": wait["attrs"]["piece"],
            "wait_s": _seconds(wait) - get_s, "get_s": get_s}
    for copy in under(entry, "train.snapshot.copy", "train.snapshot"):
        if "piece" in copy["attrs"]:    # not the optimizer shards' copy
            rows[copy["attrs"]["piece"]].update(
                copy_s=_seconds(copy),
                dest_writes=copy["attrs"]["dest_writes"],
                copy_bytes=copy["attrs"]["bytes"])
    for d2h in under(entry, "train.snapshot.d2h", "train.snapshot"):
        if "piece" in d2h["attrs"]:
            rows[d2h["attrs"]["piece"]]["d2h"] = dict(
                d2h["attrs"], seconds=_seconds(d2h))
    return [rows[k] for k in sorted(rows)]


def worker_starved_s(entry) -> float | None:
    """The worker waiting to be asked: on its lane, from the first
    `state_piece` task's start to the last `object.return_put`'s end
    (the last task's, where no piece was large enough for a put), the
    time in which neither a `state_piece` task nor a put ran."""
    tasks = [s for s in under(entry, "task", "train.snapshot")
             if s["attrs"].get("name") == PIECE_TASK]
    if not tasks:
        return None
    puts = under(entry, "object.return_put", "train.snapshot")
    lo = min(s["start"] for s in tasks)
    hi = max(s["end"] for s in (puts or tasks))
    busy = covered([(s["start"], s["end"]) for s in tasks + puts], lo, hi)
    return hi - lo - busy


def call_path(entry) -> dict:
    """One call's boundary along the driver's thread, in seconds:
    `wait_s` + `get_s` + `copy_s` + `hops_s` = `boundary_s`, which is
    `train.call` less the worker's epoch. Beside them what the worker's
    chain spent where (`link_wait_s`, `join_s`, `start_s` of `d2h_s`;
    `starved_s`) and the call's `dest_writes` (the values of its copies
    that moved bytes)."""
    root = _one(entry, "train.call")
    lo, hi = span_log.epoch_interval(entry)
    rows = pieces(entry)
    path = {key: sum(r[key] for r in rows)
            for key in ("wait_s", "get_s", "copy_s")}
    path["boundary_s"] = _seconds(root) - (hi - lo)
    path["hops_s"] = path["boundary_s"] - sum(
        path[k] for k in ("wait_s", "get_s", "copy_s"))
    path["epoch_s"] = hi - lo
    path["pieces"] = len(rows)
    d2h = [r["d2h"] for r in rows]
    path["d2h_s"] = sum(d["seconds"] for d in d2h)
    for key, attr in (("link_wait_s", "wait_s"), ("join_s", "join_s"),
                      ("start_s", "start_s")):
        path[key] = sum(d[attr] for d in d2h)
    path["starved_s"] = worker_starved_s(entry)
    path["dest_writes"] = sorted({r["dest_writes"] for r in rows
                                  if r["copy_bytes"]})
    return path


def window_paths(host) -> list[dict] | None:
    """`call_path` of each of the window's calls, in order; None without
    a log, on a mismatch (`span_log.window_entries`) or on trees that
    lack the spans."""
    entries = window_entries(host)
    if not entries:
        return None
    try:
        return [call_path(e) for e in entries]
    except (KeyError, ValueError, IndexError):
        return None


def window_median(host, key, dest_writes=None) -> float | None:
    """The median of one part of `call_path` over the window's calls,
    or over those whose copies all went into buffers written
    `dest_writes` times before; None if there is none."""
    paths = window_paths(host)
    if paths is None:
        return None
    values = [p[key] for p in paths
              if dest_writes is None or p["dest_writes"] == [dest_writes]]
    if not values or any(v is None for v in values):
        return None
    return statistics.median(values)


def first_pull_s(host) -> float | None:
    """`train.snapshot` of the run's first `train()` call — the pull
    into buffers and pages nobody has written, inside `first_step_s` —
    from the log's first entry; None if the ring has dropped it or its
    `train.call` is not the `first` call's (`span_log.MATCH_S`)."""
    try:
        from ray_tpu.train import call_log
    except ImportError:
        return None
    log = call_log()
    if not log or host["attempted"] != len(log) or "first" not in host:
        return None
    try:
        root = _one(log[0], "train.call")
        if abs(_seconds(root) - host["first"]["wall_s"]) > span_log.MATCH_S:
            return None
        return _seconds(_one(log[0], "train.snapshot"))
    except ValueError:
        return None
