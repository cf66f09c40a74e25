"""From a profiler trace (``.xplane.pb``) to three numbers and two
lists: device busy time (the union of the intervals in which an
operation ran), each operation's own time by name, and the time of
Mosaic kernels (``tpu_custom_call``).

Reads with ``jax.profiler.ProfileData`` alone, which needs no backend:
the benchmark's driver process reduces the trace the chip-owning worker
wrote. Checked on a recorded trace by ``benchmark/tests/
test_trace_reduce.py``."""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
STEPS_LINE = "Steps"
MODULES_LINE = "XLA Modules"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_MOSAIC = ("tpu_custom_call", "mosaic", "pallas")


def find_xplane(trace_dir: str) -> str | None:
    """The newest ``.xplane.pb`` under a ``profile_dir``."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def short_name(name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``: what the
    trace calls the operation, without its operands."""
    head = name.split(" = ", 1)[0].strip()
    return head.lstrip("%")[:80] or "?"


def union_and_self(intervals):
    """`intervals`: (start, end, key), nesting allowed (a while loop's
    event spans its body's). Returns (busy, {key: own time}, gaps): busy
    is the length of the union; every instant of it is given to the
    latest-started interval that covers it, so the own times sum to
    busy; gaps are (length, key before, key after), longest first."""
    own: dict = {}
    gaps = []
    busy = 0
    stack: list = []      # (end, key) of open intervals, in start order
    cursor = None         # everything before it is accounted for
    last_key = None

    def account(to):
        nonlocal cursor, busy, last_key
        while True:
            while stack and stack[-1][0] <= cursor:
                last_key = stack.pop()[1]
            if not stack or cursor >= to:
                return
            end, key = stack[-1]
            upto = min(end, to)
            own[key] = own.get(key, 0) + (upto - cursor)
            busy += upto - cursor
            cursor = upto

    for start, end, key in sorted(intervals, key=lambda x: (x[0], -x[1])):
        if cursor is None:
            cursor = start
        account(start)
        if not stack and start > cursor:
            gaps.append((start - cursor, last_key, key))
            cursor = start
        stack.append((end, key))
    if cursor is not None:
        account(float("inf"))
    gaps.sort(key=lambda g: -g[0])
    return busy, own, gaps


def _is_mosaic(event) -> bool:
    text = event.name.lower()
    if any(m in text for m in _MOSAIC):
        return True
    try:
        stats = list(event.stats)
    except Exception:
        return False
    return any(isinstance(v, str) and any(m in v.lower() for m in _MOSAIC)
               for _, v in stats)


def reduce_plane(plane) -> dict | None:
    lines = {line.name: line for line in plane.lines}
    if OPS_LINE not in lines:
        return None
    intervals, mosaic = [], {}    # one look at an operation's stats
    for e in lines[OPS_LINE].events:
        if e.duration_ns <= 0:
            continue
        key = short_name(e.name)
        if key not in mosaic:
            mosaic[key] = _is_mosaic(e)
        intervals.append((e.start_ns, e.start_ns + e.duration_ns, key))
    mosaic_keys = {k for k, v in mosaic.items() if v}
    if not intervals:
        return None
    busy, own, gaps = union_and_self(intervals)
    first = min(i[0] for i in intervals)
    last = max(i[1] for i in intervals)

    def count(name):
        return sum(1 for _ in lines[name].events) if name in lines else 0

    return {
        "span_s": (last - first) / 1e9,
        "busy_s": busy / 1e9,
        "op_self_s": {k: v / 1e9 for k, v in own.items()},
        "mosaic_s": sum(own.get(k, 0) for k in mosaic_keys) / 1e9,
        "mosaic_ops": sorted(mosaic_keys),
        "gaps": [(g / 1e9, a, b) for g, a, b in gaps[:10]],
        "steps": count(STEPS_LINE), "modules": count(MODULES_LINE),
        "events": len(intervals),
    }


def reduce_trace(path: str) -> dict | None:
    """The device planes of one trace, averaged over the chips. None
    where no operation ran on a device."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = [r for r in (reduce_plane(p) for p in data.planes
                          if _DEVICE_PLANE.match(p.name)) if r]
    if not planes:
        return None
    n = len(planes)
    ops: dict = {}
    for r in planes:
        for k, v in r["op_self_s"].items():
            ops[k] = ops.get(k, 0.0) + v / n
    top = planes[0]
    return {
        "devices": n,
        "span_s": sum(r["span_s"] for r in planes) / n,
        "busy_s": sum(r["busy_s"] for r in planes) / n,
        "mosaic_s": sum(r["mosaic_s"] for r in planes) / n,
        "mosaic_ops": top["mosaic_ops"],
        "op_self_s": ops,
        "gaps": top["gaps"],
        "steps": top["steps"], "modules": top["modules"],
        "events": top["events"],
    }
