"""Builder's tool: the program's spans and the device trace on ONE clock.

    BENCH_KEEP_TRACE=<dir> python3 benchmark/tools/run_with_log.py <log.json> \\
        --workload <cell> --seed <n> --seconds <s> --trace 1
    python3 benchmark/tools/spans_on_trace.py <dir>/<host>.xplane.pb <log.json> [out.json]

The traced call is the run's last ``train()`` call: the log's last
entry. Its worker-side spans are also ``TraceAnnotation``s on the
trace's ``/host:CPU`` plane (``_private/tracing.py``), stamped by the
profiler; the log's spans are stamped with ``time.time()``. This tool

1. pairs the two by span name and order and reports the OFFSET between
   the clocks (span stamp − profiler stamp) at ``train.sync``, which is
   in both, and its spread over every pair of the call — whether one
   number puts the spans on the trace's clock;
2. prints every idle gap of a device above 1 ms inside the traced call
   (before the first operation, between operations, and from the last
   operation to the call's end: the ``train()`` boundary) with the
   driver's and the worker's innermost spans that cover it.

Needs no backend. Readers of the benchmark get the reduced trace, not
the file, so this stays a tool until a ``benchmark`` issue lets
``breakdown.idle_gaps`` name the boundary's parts."""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import trace_reduce  # noqa: E402

HOST_PLANE = "/host:CPU"
MIN_GAP_S = 1e-3
# one thread each: spans of these names nest and never overlap
DRIVER = ("train.call", "train.epoch", "train.snapshot",
          "train.snapshot.wait", "object.get", "train.snapshot.copy")
WORKER = ("task", "train.dispatch", "train.sync", "train.snapshot.d2h",
          "train.snapshot.d2h.leaf", "object.return_put")
# a span back-dated to work done before its annotation opens
# (`_pack_returns`: the serialise): its offset reads that work, not the
# clocks, and stays out of the spread
BACKDATED = ("object.return_put",)


def annotations(data) -> dict:
    """The host plane's events by name, each list in time order, as
    (start, end) in seconds on the profiler's clock."""
    found: dict = {}
    for plane in data.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("train.", "object.")):
                    found.setdefault(e.name, []).append(
                        (e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9))
    return {name: sorted(rows) for name, rows in found.items()}


def offsets(entry, notes) -> list[dict]:
    """One row a span of the traced call that is in the trace too,
    paired by name and order (only names that occur equally often):
    `offset_s` = the span's `time.time()` start − the annotation's."""
    rows = []
    for name, events in notes.items():
        spans = sorted((s for s in entry["spans"] if s["name"] == name),
                       key=lambda s: s["start"])
        if len(spans) != len(events):
            continue
        for span, (start, end) in zip(spans, events):
            rows.append({
                "name": name, "at_s": start,
                "offset_s": span["start"] - start,
                # the two clocks' rates: the same interval on each
                "span_s": span["end"] - span["start"],
                "annotation_s": end - start})
    return sorted(rows, key=lambda r: r["at_s"])


def device_gaps(plane, lo: float, hi: float) -> list[tuple]:
    """The intervals of [lo, hi] (profiler clock, seconds) longer than
    `MIN_GAP_S` in which no operation ran on `plane`."""
    lines = {line.name: line for line in plane.lines}
    busy = sorted((e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9)
                  for e in lines[trace_reduce.OPS_LINE].events
                  if e.duration_ns > 0)
    gaps, cursor = [], lo
    for start, end in busy + [(hi, hi)]:
        if start - cursor > MIN_GAP_S:
            gaps.append((cursor, min(start, hi)))
        cursor = max(cursor, end)
        if cursor >= hi:
            break
    return gaps


def innermost(entry, names, lo: float, hi: float) -> dict:
    """Seconds of [lo, hi] (the spans' clock) by the innermost span of
    one thread's `names` that covers each instant; what no span covers
    goes under `(none)`."""
    spans = [s for s in entry["spans"] if s["name"] in names
             and s["end"] > lo and s["start"] < hi]
    cuts = sorted({lo, hi} | {t for s in spans for t in (s["start"], s["end"])
                              if lo < t < hi})
    out: dict = {}
    for a, b in zip(cuts, cuts[1:]):
        over = [s for s in spans if s["start"] <= a and s["end"] >= b]
        # on one thread the innermost is the one that started last (of
        # two that started together, the one that ended first)
        inner = max(over, key=lambda s: (s["start"], -s["end"]),
                    default=None)
        name = inner["name"] if inner else "(none)"
        if name == "task":
            name = "task " + inner["attrs"].get("name", "?")
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def look(trace_path: str, log_path: str, device: int = 0) -> dict:
    from jax.profiler import ProfileData

    with open(log_path) as f:
        entry = json.load(f)[-1]
    data = ProfileData.from_file(trace_path)
    pairs = offsets(entry, annotations(data))
    anchor = [r for r in pairs if r["name"] == "train.sync"]
    if not anchor:
        return {"error": "no train.sync in both the log's last call and "
                         "the trace: not the traced call's log?"}
    offset = anchor[0]["offset_s"]
    clocks = [r for r in pairs if r["name"] not in BACKDATED]
    values = [r["offset_s"] for r in clocks]
    out = {
        "offset_s": offset, "pairs": len(pairs),
        "offset_min_s": min(values), "offset_max_s": max(values),
        "offset_spread_s": max(values) - min(values),
        "constant_to_1ms": max(values) - min(values) <= 1e-3,
        # first against last pair of the call: a drift of the clocks
        "offset_drift_s": clocks[-1]["offset_s"] - clocks[0]["offset_s"],
        "call_s": clocks[-1]["at_s"] - clocks[0]["at_s"],
        "by_name": {}, "gaps": []}
    for r in pairs:
        row = out["by_name"].setdefault(
            r["name"], {"count": 0, "min_s": r["offset_s"],
                        "max_s": r["offset_s"], "longer_by_s": 0.0})
        row["count"] += 1
        row["min_s"] = min(row["min_s"], r["offset_s"])
        row["max_s"] = max(row["max_s"], r["offset_s"])
        # how much longer the spans read than their annotations
        row["longer_by_s"] += r["span_s"] - r["annotation_s"]
    (root,) = [s for s in entry["spans"] if s["name"] == "train.call"]
    planes = [p for p in data.planes
              if p.name == f"/device:TPU:{device}"]
    if not planes:
        out["error"] = f"no plane /device:TPU:{device} in the trace"
        return out
    for lo, hi in device_gaps(planes[0], root["start"] - offset,
                              root["end"] - offset):
        out["gaps"].append({
            "from_s": lo + offset - root["start"], "seconds": hi - lo,
            "driver": innermost(entry, DRIVER, lo + offset, hi + offset),
            "worker": innermost(entry, WORKER, lo + offset, hi + offset)})
    return out


def _parts(cover: dict) -> str:
    return ", ".join(f"{name} {s:.4f}" for name, s in sorted(
        cover.items(), key=lambda x: -x[1]) if s >= 5e-5)


if __name__ == "__main__":
    result = look(sys.argv[1], sys.argv[2])
    if len(sys.argv) > 3:
        with open(sys.argv[3], "w") as f:
            json.dump(result, f, indent=1)
    gaps = result.pop("gaps", [])
    print(json.dumps(result, indent=1))
    print(f"device idle gaps above {MIN_GAP_S * 1e3:g} ms in the traced "
          "call (seconds from train.call's start):")
    for g in gaps:
        print(f"  at {g['from_s']:9.4f}  {g['seconds']:9.4f} s\n"
              f"      driver: {_parts(g['driver'])}\n"
              f"      worker: {_parts(g['worker'])}")
