"""BENCHMARK.json and the data files it names. Everything that belongs
to one configuration, one cell or one per-layer metric sits in a file
of its own, found here by the name in the manifest:

    configs/<config>.json      workloads/<cell>.json
    families/<family>.py       families/<family>_reference.py
    layer_metrics/<metric>.py  peaks.json
"""

from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class ManifestError(Exception):
    pass


def _load(path: str, what: str) -> dict:
    if not os.path.isfile(path):
        raise ManifestError(f"{what}: no file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def manifest() -> dict:
    return _load(os.path.join(ROOT, "BENCHMARK.json"), "the manifest")


def module(kind: str, name: str):
    """`benchmark.<kind>.<name>`, or a ManifestError that names it."""
    if not os.path.isfile(os.path.join(HERE, kind, name + ".py")):
        raise ManifestError(f"no benchmark/{kind}/{name}.py")
    return importlib.import_module(f"benchmark.{kind}.{name}")


def config_file(name: str) -> dict:
    model = _load(os.path.join(HERE, "configs", name + ".json"),
                  f"configuration {name!r}")
    for suffix in ("", "_reference"):
        module("families", model["family"] + suffix)
    return model


def workload_file(name: str) -> dict:
    return _load(os.path.join(HERE, "workloads", name + ".json"),
                 f"cell {name!r}")


def metric_reported(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def cell(name: str, rehearse: bool = False) -> dict:
    """Everything one run needs, read from the manifest and the files it
    names. A cell, family or metric that has no file is an error."""
    m = manifest()
    entry = next((w for w in m["workloads"] if w["name"] == name), None)
    if entry is None:
        raise ManifestError(
            f"no cell {name!r} in BENCHMARK.json (cells: "
            f"{[w['name'] for w in m['workloads']]})")
    workload = workload_file(name)
    if workload["config"] != entry["config"] \
            or workload["chips"] != entry["chips"]:
        raise ManifestError(f"cell {name!r}: its file and the manifest "
                            "disagree on config or chips")
    config_name = entry["config"]
    if rehearse:  # tiny sizes for the CPU; never a device metric
        workload = dict(workload, **workload["rehearsal"])
        config_name = workload["config"]
    elif not any(c["name"] == config_name for c in m["configs"]):
        raise ManifestError(f"cell {name!r}: configuration "
                            f"{config_name!r} is not in BENCHMARK.json")
    model = config_file(config_name)
    per_layer = [x for x in m["per_layer"] if metric_reported(x, name)]
    return {
        "name": name, "chips": entry["chips"], "model": model,
        "workload": workload,
        "family": module("families", model["family"]),
        "end_to_end": [x for x in m["end_to_end"]
                       if metric_reported(x, name)],
        "per_layer": per_layer,
        "readers": {x["name"]: module("layer_metrics", x["name"]).read
                    for x in per_layer},
    }


def peaks(device_kind: str) -> dict:
    table = _load(os.path.join(HERE, "peaks.json"), "the table of peaks")
    if device_kind not in table or device_kind.startswith("_"):
        raise ManifestError(f"device kind {device_kind!r} is not in "
                            "benchmark/peaks.json")
    return table[device_kind]
