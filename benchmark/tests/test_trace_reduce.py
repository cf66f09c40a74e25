"""The reducer on the one recorded device trace the repo has
(``profiles/r50``: ResNet-50, 5 steps, from the old chip set-up — good
as a fixture, no number from it carries) and on synthetic intervals."""

import glob
import os

import pytest

from benchmark import trace_reduce
from benchmark.manifest import ROOT


def test_union_of_two_overlapping_intervals():
    busy, own, gaps = trace_reduce.union_and_self(
        [(0, 10, "a"), (5, 15, "b")])
    assert busy == 15
    assert own == {"a": 5, "b": 10}
    assert gaps == []


def test_nesting_gap_and_own_times_sum_to_busy():
    busy, own, gaps = trace_reduce.union_and_self(
        [(20, 30, "while"), (22, 24, "body"), (0, 10, "a"), (40, 41, "e"),
         (26, 30, "body")])
    assert busy == 21
    assert own == {"a": 10, "while": 4, "body": 6, "e": 1}
    assert sum(own.values()) == busy
    assert gaps[0] == (10, "a", "while") and gaps[1][0] == 10


def test_short_name():
    assert trace_reduce.short_name(
        "%fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop"
    ) == "fusion.3"
    assert trace_reduce.short_name("custom-call.7") == "custom-call.7"


@pytest.fixture(scope="module")
def recorded():
    found = glob.glob(os.path.join(ROOT, "profiles", "r50", "**",
                                   "*.xplane.pb"), recursive=True)
    if not found:
        pytest.skip("the recorded trace profiles/r50 is not there")
    return trace_reduce.reduce_trace(found[0])


def test_recorded_trace_device_plane_and_steps(recorded):
    assert recorded is not None and recorded["devices"] == 1
    assert recorded["steps"] == 5 and recorded["modules"] == 5


def test_recorded_trace_busy_share_and_op_totals(recorded):
    assert 0.0 < recorded["busy_s"] / recorded["span_s"] <= 1.0
    assert sum(recorded["op_self_s"].values()) == pytest.approx(
        recorded["busy_s"], rel=1e-9)
    # ResNet-50 with XLA's batch-norm has no Mosaic kernel
    assert recorded["mosaic_s"] == 0.0 and recorded["mosaic_ops"] == []
    # five steps of about 97 ms of device time each
    assert 0.4 < recorded["busy_s"] < 0.6


def test_find_xplane(tmp_path):
    assert trace_reduce.find_xplane(str(tmp_path)) is None
    assert trace_reduce.find_xplane(
        os.path.join(ROOT, "profiles", "r50")).endswith(".xplane.pb")
