"""The two readers of the recurrent mixers' convolution
(``mixer_conv_time_share``, ``mixer_conv_roofline_share``): found by the
manifest in the three cells that have such a mixer and nowhere else, the
bytes they count by hand from each cell's model file, their readings on
a synthetic trace of each cell, and what a program that leaves the
convolution to XLA gives them (nothing, without raising)."""

import sys

import pytest

from benchmark import manifest
from benchmark.layer_metrics import mixer_conv_roofline_share as share

METRICS = ("mixer_conv_time_share", "mixer_conv_roofline_share")
# cell -> (conv layers, channels): [q | k | v] of 32 heads of 128; 16 key
# and 32 value heads of 128; 64 heads of 64 and two of 8 groups of 128
CELLS = {"kimilinear_ep32_seq8k": (4, 3 * 4096),
         "qwen3next_ep16_seq8k": (3, 2 * 2048 + 4096),
         "nemotron3_ep16_seq8k": (4, 4096 + 2 * 1024)}


def test_manifest_finds_the_metrics_in_the_three_cells_only():
    for entry in manifest.manifest()["workloads"]:
        readers = set(manifest.cell(entry["name"])["readers"])
        assert (set(METRICS) <= readers) == (entry["name"] in CELLS)
        assert (not set(METRICS) & readers) == (entry["name"] not in CELLS)


@pytest.mark.parametrize("name", list(CELLS))
def test_bytes_are_seven_arrays_a_layer_and_step(name):
    cell = manifest.cell(name)
    cfg = cell["family"].model_cfg(cell["model"])
    layers, channels = CELLS[name]
    flops, nbytes = share.flops_bytes(cfg, cell["workload"], 3)
    tokens = cell["workload"]["batch"] * cell["workload"]["seq"] * 3
    assert tokens == 2 * 8192 * 3
    assert nbytes == 7 * 2 * tokens * layers * channels
    assert flops / 197e12 < 0.05 * nbytes / 819e9      # the bytes bound it


@pytest.fixture
def traced(monkeypatch, request):
    """A synthetic trace reduction and log of one traced call of the
    cell: 8 steps, the two kernels together at 60 % of the memory roof."""
    name = request.param
    cell = manifest.cell(name)
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", name])
    log = [{"trace_id": "0", "spans": [
        {"name": "train.dispatch", "start": 0.0, "end": 1.0, "span": "d",
         "parent": None, "attrs": {"steps": 8}}]}]
    import ray_tpu.train

    monkeypatch.setattr(ray_tpu.train, "call_log", lambda: list(log),
                        raising=False)
    cfg = cell["family"].model_cfg(cell["model"])
    _, nbytes = share.flops_bytes(cfg, cell["workload"], 8)
    ops = {"mixer_conv.4": 0.5 * nbytes / 0.6 / 819e9,
           "mixer_conv_bwd.5": 0.5 * nbytes / 0.6 / 819e9,
           "short_conv.6": 0.25, "fusion.9": 1.0}
    trace = {"busy_s": sum(ops.values()), "op_self_s": ops,
             "mosaic_ops": [k for k in ops if k != "fusion.9"]}
    return {"peaks": manifest.peaks("TPU v5 lite")}, trace


def _read(name, host, trace):
    return manifest.module("layer_metrics", name).read(host, trace)


@pytest.mark.parametrize("traced", list(CELLS), indirect=True)
def test_readers_on_a_synthetic_trace(traced):
    host, trace = traced
    ops = trace["op_self_s"]
    assert _read("mixer_conv_roofline_share", host, trace) \
        == pytest.approx(60.0)
    assert _read("mixer_conv_time_share", host, trace) == pytest.approx(
        100 * (ops["mixer_conv.4"] + ops["mixer_conv_bwd.5"])
        / trace["busy_s"])


@pytest.mark.parametrize("traced", ["kimilinear_ep32_seq8k"], indirect=True)
def test_readers_give_none_where_there_is_nothing_to_read(traced,
                                                          monkeypatch):
    """The parent of the PR that added the kernels: its trace names no
    `mixer_conv` (`short_conv` is another operator's), or there is no
    trace, or no log to take the steps from."""
    host, trace = traced
    bare = {"busy_s": 1.0, "op_self_s": {"fusion.9": 0.75, "short_conv.6":
                                          0.25},
            "mosaic_ops": ["short_conv.6"]}
    for name in METRICS:
        assert _read(name, host, bare) is None
        assert _read(name, host, None) is None
    import ray_tpu.train

    monkeypatch.setattr(ray_tpu.train, "call_log", lambda: [], raising=False)
    assert _read("mixer_conv_roofline_share", host, trace) is None
    monkeypatch.setattr(sys, "argv", ["run.py"])
    assert _read("mixer_conv_roofline_share", host, trace) is None
