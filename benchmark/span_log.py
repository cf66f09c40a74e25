"""The program's own account of the window's ``train()`` calls: the span
trees ``ray_tpu.train.call_log()`` keeps in the driver process (one
clock, ``time.time()``, driver and worker on one host), matched to the
run's ``host["calls"]`` by position. The ``snapshot_*``, ``call_hop_s``
and ``idle_gap_named_share`` readers share this file. A program without
the log (the parent of the PR that added it) gives None everywhere."""

from __future__ import annotations

import statistics

MATCH_S = 1e-3     # a matched entry's train.call against the call's wall_s
LEAVES = ("train.snapshot.d2h", "object.return_put", "object.get",
          "train.snapshot.copy")


def window_entries(host) -> list | None:
    """The log's entries of the window's calls, in order. The run makes
    its calls through one Trainer: `first`, `warm`, the window's, then
    the traced one — so the window starts at position 2 (less what the
    log's ring has dropped). None without a log, or if an entry's
    `train.call` differs from its call's `wall_s` by more than 1 ms."""
    try:
        from ray_tpu.train import call_log
    except ImportError:
        return None
    log, calls = call_log(), host["calls"]
    dropped = host["attempted"] - len(log)
    first = 2 - dropped
    if not calls or dropped < 0 or first < 0 or first + len(calls) > len(log):
        return None
    entries = log[first:first + len(calls)]
    for entry, call in zip(entries, calls):
        root = _named(entry, "train.call")
        if len(root) != 1 or abs(
                root[0]["end"] - root[0]["start"] - call["wall_s"]) > MATCH_S:
            return None
    return entries


def _named(entry, name) -> list:
    return [s for s in entry["spans"] if s["name"] == name]


def _seconds(spans) -> float:
    return sum(s["end"] - s["start"] for s in spans)


def under(entry, name, ancestor) -> list:
    """The entry's spans called `name` below a span called `ancestor`."""
    by_id = {s["span"]: s for s in entry["spans"]}

    def below(s):
        while s is not None:
            if s["name"] == ancestor:
                return True
            s = by_id.get(s["parent"])
        return False

    return [s for s in _named(entry, name) if below(s)]


def epoch_interval(entry) -> tuple[float, float]:
    """The worker's epoch: `train.dispatch` start to `train.sync` end
    (the device works from the first dispatch until the drain returns)."""
    return (min(s["start"] for s in _named(entry, "train.dispatch")),
            max(s["end"] for s in _named(entry, "train.sync")))


def split(entry) -> dict:
    """One call's seconds by part. `hop_s` is what is left of
    `train.call` after the worker's epoch and the four leaf spans of the
    snapshot: actor hops, reply waits, `_reduce`. (One worker: several
    workers' spans would overlap in time and their sum overcount.)"""
    root = _named(entry, "train.call")[0]
    start, end = epoch_interval(entry)
    d2h = under(entry, "train.snapshot.d2h", "train.snapshot")
    parts = {
        "d2h_s": _seconds(d2h),
        "put_s": _seconds(under(entry, "object.return_put",
                                "train.snapshot")),
        "get_s": _seconds(under(entry, "object.get", "train.snapshot")),
        "copy_s": _seconds(_named(entry, "train.snapshot.copy")),
    }
    parts["hop_s"] = (root["end"] - root["start"] - (end - start)
                      - sum(parts.values()))
    parts["epoch_s"] = end - start
    parts["bytes"] = sum(s["attrs"].get("bytes", 0) for s in d2h)
    return parts


def window_median(host, key) -> float | None:
    """The median of one part of `split` over the window's calls."""
    entries = window_entries(host)
    if not entries:
        return None
    try:
        return statistics.median(split(e)[key] for e in entries)
    except (ValueError, IndexError, KeyError):
        return None     # a tree without the spans: nothing to read


def covered(intervals, lo: float, hi: float) -> float:
    """Length of the union of `intervals`, clipped to [lo, hi]."""
    total, cursor = 0.0, lo
    for a, b in sorted(intervals):
        a, b = max(a, cursor), min(b, hi)
        if b > a:
            total += b - a
            cursor = b
    return total


def idle_gap_named_share(host) -> float | None:
    """Over the window's consecutive calls: how much of the device's
    idle gap — [`train.sync` end of call k, `train.dispatch` start of
    call k + 1] — lies inside one of the four leaf spans, in percent."""
    entries = window_entries(host)
    if not entries or len(entries) < 2:
        return None
    try:
        named = gap = 0.0
        for this, following in zip(entries, entries[1:]):
            lo, hi = epoch_interval(this)[1], epoch_interval(following)[0]
            leaves = [(s["start"], s["end"]) for e in (this, following)
                      for s in e["spans"] if s["name"] in LEAVES]
            named += covered(leaves, lo, hi)
            gap += hi - lo
    except ValueError:
        return None
    return 100.0 * named / gap if gap > 0 else None
