"""Builder's tool: where do the program's names surface in a kept trace?

    BENCH_KEEP_TRACE=<dir> python3 benchmark/run.py ... --trace 1
    python3 benchmark/tools/trace_names.py <dir>/vm.xplane.pb [out.json]

Prints (and writes, if asked) one JSON object: the planes and their
lines; for the device's ``XLA Ops`` line every Mosaic operation's event
name with its stats, and for each of `NAMES` the event names and the
stat keys in which it occurs (with one example value each); for the
host planes the program's own spans (``train.*``, ``object.*``: the
``TraceAnnotation``s of `_private/tracing.py`) with their line, count
and seconds. Needs no backend."""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import trace_reduce  # noqa: E402

NAMES = ("flash_fwd", "layernorm", "rmsnorm", "forward_backward",
         "optimizer", "attention", "mlp", "logits_loss")
SPANS = ("train.", "object.")


def _stats(event) -> dict:
    try:
        return {str(k): v for k, v in event.stats}
    except Exception:
        return {}


def look(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"planes": {}, "mosaic_ops": {}, "names": {n: {
        "event_names": [], "stat_keys": {}} for n in NAMES},
        "host_spans": {}}
    for plane in data.planes:
        lines = list(plane.lines)
        out["planes"][plane.name] = [line.name for line in lines][:40]
        for line in lines:
            device_ops = (trace_reduce._DEVICE_PLANE.match(plane.name)
                          and line.name == trace_reduce.OPS_LINE)
            for e in line.events:
                if device_ops:
                    _device_event(e, out)
                elif e.name.startswith(SPANS):
                    row = out["host_spans"].setdefault(
                        e.name, {"plane": plane.name, "line": line.name,
                                 "count": 0, "seconds": 0.0})
                    row["count"] += 1
                    row["seconds"] += e.duration_ns / 1e9
    return out


def _device_event(e, out) -> None:
    key = trace_reduce.short_name(e.name)
    stats = None
    if trace_reduce._is_mosaic(e) and key not in out["mosaic_ops"]:
        stats = _stats(e)
        out["mosaic_ops"][key] = {k: str(v)[:300] for k, v in stats.items()}
    for name, found in out["names"].items():
        if name in e.name and len(found["event_names"]) < 4 \
                and key not in found["event_names"]:
            found["event_names"].append(key)
        if len(found["stat_keys"]) >= 6:
            continue
        stats = _stats(e) if stats is None else stats
        for k, v in stats.items():
            if isinstance(v, str) and name in v and k not in found["stat_keys"]:
                found["stat_keys"][k] = v[:300]


def look_raw(path: str) -> dict | None:
    """The same question put to the raw protobuf, where TensorFlow's
    copy of its schema is installed: `ProfileData` shows an event's own
    stats, not those of its metadata record (XEventMetadata)."""
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError:
        return None
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    found = {n: {} for n in NAMES}
    for plane in space.planes:
        if not trace_reduce._DEVICE_PLANE.match(plane.name):
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        for meta in plane.event_metadata.values():
            fields = {"metadata.name": meta.name,
                      "metadata.display_name": meta.display_name}
            for st in meta.stats:
                value = st.str_value or (
                    plane.stat_metadata[st.ref_value].name
                    if st.ref_value else "")
                fields["metadata.stat:" + stat_names.get(
                    st.metadata_id, "?")] = value
            for name, where in found.items():
                for field, value in fields.items():
                    if name in value and field not in where:
                        where[field] = value[:300]
    return found


if __name__ == "__main__":
    result = look(sys.argv[1])
    result["raw_names"] = look_raw(sys.argv[1])
    text = json.dumps(result, indent=1, default=str)
    if len(sys.argv) > 2:
        with open(sys.argv[2], "w") as f:
            f.write(text)
    print(text[-12000:])
