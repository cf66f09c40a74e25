"""``index_kl_time_share``: found by the manifest for the one cell that
runs the kernel, and its reader on made-up traces — a block that keeps
nothing across its remat (two operations), one that keeps what the pass
made (one), a program without the kernel (nothing, without raising)."""

import pytest

from benchmark import manifest

NAME = "index_kl_time_share"


def _read(trace):
    return manifest.module("layer_metrics", NAME).read({}, trace)


def _trace(ops):
    return {"busy_s": sum(ops.values()), "op_self_s": ops,
            "mosaic_ops": [k for k in ops if not k.startswith("fusion")]}


def test_the_entry_lists_the_cell_that_runs_the_kernel():
    entry = next(m for m in manifest.manifest()["per_layer"]
                 if m["name"] == NAME)
    beside = next(m for m in manifest.manifest()["per_layer"]
                  if m["name"] == "index_time_share")
    assert {k: v for k, v in entry.items() if k != "name"} \
        == {k: v for k, v in beside.items() if k != "name"}
    for w in manifest.manifest()["workloads"]:
        assert (NAME in manifest.cell(w["name"])["readers"]) \
            == (w["name"] == "keye_ep8_seq16k")


@pytest.mark.parametrize("ops, want", [
    ({"index_kl.26": 0.61, "index_kl.27": 0.61, "index_scores.15": 0.07,
      "flash_fwd.26": 0.5, "fusion.9": 3.96}, 100 * 1.22 / 5.75),
    ({"index_kl.26": 0.61, "index_scores.15": 0.07, "flash_fwd.26": 0.5,
      "fusion.9": 3.96}, 100 * 0.61 / 5.14),
    ({"index_scores.15": 0.07, "flash_fwd.26": 0.5, "fusion.9": 1.0}, None),
], ids=["two_passes", "one_pass", "no_kernel"])
def test_reader_on_a_made_up_trace(ops, want):
    got = _read(_trace(ops))
    assert got is None if want is None else got == pytest.approx(want)


def test_no_trace_and_an_idle_one_give_nothing():
    assert _read(None) is None
    assert _read({"busy_s": 0.0, "op_self_s": {}, "mosaic_ops": []}) is None
