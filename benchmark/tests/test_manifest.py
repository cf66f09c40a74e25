"""BENCHMARK.json against the files it names and the contract's rules
on names, units and ``moves``."""

import json
import os
import re

import pytest

from benchmark import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return manifest.manifest()


def test_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert bench["paths"] == ["benchmark"]
    assert len(json.dumps(bench)) < 64 * 1024
    fours = sum(w["chips"] == 4 for w in bench["workloads"])
    assert fours <= max(1, len(bench["workloads"]) // 4)


def test_every_cell_configuration_and_metric_has_its_file(bench):
    for w in bench["workloads"]:
        cell = manifest.cell(w["name"])
        assert cell["chips"] == w["chips"] in (1, 4)
        assert set(cell["readers"]) == {m["name"]
                                        for m in cell["per_layer"]}
        assert cell["workload"]["rehearsal"]["config"]
        manifest.cell(w["name"], rehearse=True)
    used = {w["config"] for w in bench["workloads"]}
    files = set()
    for c in bench["configs"]:
        assert c["name"] in used, f"configuration {c['name']} has no cell"
        assert c["file"].startswith("benchmark/configs/")
        assert os.path.isfile(os.path.join(manifest.ROOT, c["file"]))
        model = manifest.config_file(c["name"])
        assert model["source"] == c["source"]
        assert model["reduced"] == c["reduced"]
        files.add(c["file"])
    assert len(files) == len(bench["configs"])
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_names_units_and_text(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    for entry in bench["workloads"] + bench["configs"]:
        assert NAME.match(entry["name"])
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    for w in bench["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
    assert "setup_s" in {m["name"] for m in bench["end_to_end"]}


def test_moves_names_an_end_to_end_metric_of_the_same_cells(bench):
    cells = [w["name"] for w in bench["workloads"]]
    end = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in end, m
        for cell in cells:
            if manifest.metric_reported(m, cell):
                assert manifest.metric_reported(end[m["moves"]], cell), (
                    m["name"], cell)
    for cell in cells:   # setup_s, one more end to end, one per layer
        reported = [m for m in bench["end_to_end"]
                    if manifest.metric_reported(m, cell)]
        assert len(reported) >= 2
        assert any(manifest.metric_reported(m, cell)
                   for m in bench["per_layer"])


def test_unknown_names_fail_loudly(tmp_path, monkeypatch):
    with pytest.raises(manifest.ManifestError, match="no cell"):
        manifest.cell("no_such_cell")
    with pytest.raises(manifest.ManifestError, match="layer_metrics"):
        manifest.module("layer_metrics", "no_such_metric")
    # a configuration file that names a family with no file
    monkeypatch.setattr(manifest, "HERE", str(tmp_path))
    os.makedirs(tmp_path / "configs")
    (tmp_path / "configs" / "odd.json").write_text(
        json.dumps({"family": "no_such_family"}))
    with pytest.raises(manifest.ManifestError, match="families"):
        manifest.config_file("odd")
    with pytest.raises(manifest.ManifestError, match="peaks"):
        manifest.peaks("TPU v9 imaginary")


def test_peaks_table():
    assert manifest.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(manifest.ManifestError):
        manifest.peaks("cpu")
