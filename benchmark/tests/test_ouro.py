"""The ``ouro`` family, its cell and its per-layer metrics: found by the
manifest, the configuration's numbers against the catalog's, the
parameter count reckoned again from the built tree, the plain reference
against the program at the tiny preset, the counts behind
``flops_per_sample`` and the attention kernels' operations and bytes by
hand, the cell's CPU rehearsal end to end with the counter readers on
ITS log and the trace readers on a synthetic trace beside it, and what a
program from before the loop gives them (nothing, without raising)."""

import dataclasses
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from benchmark import manifest
from benchmark.common import key_seed

CELL, CONFIG = "ouro_d8_loop4_seq4k", "ouro_2_6b_d8"
KERNEL_METRICS = ("ouro_attention_time_share",
                  "ouro_attention_fwd_roofline_share",
                  "ouro_attention_bwd_roofline_share")
COUNTERS = ("loop_exit_entropy_share", "loop_last_over_first_nll",
            "loop_exit_mass_last_share")
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}


def test_manifest_finds_cell_family_and_metrics():
    cell = manifest.cell(CELL)
    assert cell["chips"] == 1 and cell["model"]["family"] == "ouro"
    mine = {*KERNEL_METRICS, *COUNTERS}
    assert mine <= set(cell["readers"])
    for other in (w["name"] for w in manifest.manifest()["workloads"]):
        if other != CELL:
            assert not mine & set(manifest.cell(other)["readers"])
    # every metric without a list of cells reads on this cell too
    assert {"mfu", "mosaic_time_share", "boundary_wait_s", "peak_hbm_gib",
            "device_idle_share", "worker_samples_per_s"} <= set(
                cell["readers"])
    model, entry = cell["model"], next(
        c for c in manifest.manifest()["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == model["reduced"] == ["num_hidden_layers"]
    assert model["published"] == {"num_hidden_layers": 48}
    assert entry["source"] == model["source"] \
        and "six pipeline stages" in model["deployment"]
    cfg = cell["family"].model_cfg(model)
    assert cfg.kinds == (("full", "dense"),) * 8
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.d_dense, cfg.vocab_size, cfg.rope_theta, cfg.rms_eps) == (
                2048, 16, 16, 128, 5632, 49152, 1e6, 1e-6)
    assert cfg.rotary == ("full",) and not cfg.qk_norm and not cfg.tied_head \
        and not cfg.head_rows and cfg.activation == "silu" and cfg.gated
    assert (cfg.loops, cfg.sandwich, cfg.exit_gate, cfg.exit_beta) == (
        4, True, True, 0.1) and cfg.moe_layers == 0 and cfg.held == (0, 0)
    assert cfg.remat == model["remat"] is True
    workload = cell["workload"]
    assert workload["seq"] == 4096 and workload["steps_per_call"] == 4 \
        and workload["trace_steps"] == 4
    assert workload["batch"] == max(
        int(b) for b, gib in workload["aot_step_GiB"].items()
        if gib is not None and gib <= 13.5)


def test_configuration_keeps_the_catalogs_numbers():
    """Every number of the source's config under the same key, but the
    one `reduced`; the nested group copied whole."""
    model = manifest.config_file(CONFIG)
    for key, value in PUBLISHED.items():
        if key in model["reduced"]:
            assert model["published"][key] == value and model[key] != value
        else:
            assert model[key] == value, key
    assert model["num_hidden_layers"] == 8 and 48 % 8 == 0
    for word in ("sandwich norms", "final norm", "between walks", "gate",
                 "loss", "positions", "bias", "sequence length",
                 "initialisation", "optimizer", "precision", "remat"):
        assert word in model["assumed"], word
    assert model["exit_beta"] == 0.1


def test_parameter_count_is_the_files():
    cell = manifest.cell(CELL)
    p = cell["family"].pieces(cell["model"], dict(cell["workload"], batch=1,
                                                  seq=64), 3)
    params, state = jax.eval_shape(p.model_init, jax.random.key(0))
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == 612_438_017
    assert f"{n:,}".replace(",", " ") in cell["model"]["parameters"]
    layer = sum(x.size for x in jax.tree.leaves(params["layers"])) // 8
    assert layer == 51_388_416 == 4 * 2048 * 2048 + 3 * 2048 * 5632 \
        + 4 * 2048
    assert params["head"].shape == params["embed"].shape[::-1] \
        == (2048, 49152)
    assert sum(x.size for x in jax.tree.leaves(params["exit_gate"])) == 2049
    assert set(state) == {"epoch_counters"} \
        and len(state["epoch_counters"]) == 10


def test_reference_matches_program_loss_at_the_tiny_preset():
    from benchmark.families import ouro, ouro_reference
    from ray_tpu.models import decoder

    model = manifest.config_file("ouro_tiny")
    workload = {"batch": 2, "seq": 64}
    for seed in (1, 2 ** 31 + 11):
        p = ouro.pieces(model, workload, seed)
        init = p.model_init(jax.random.key(key_seed(seed)))
        got = float(p.loss_fn(*init, p.batch)[0])       # bf16 compute
        want = ouro_reference.loss(init, p.batch, model)
        assert abs(got - want) <= 2e-3 * abs(want)
        cfg = dataclasses.replace(ouro.model_cfg(model), dtype=jnp.float32)
        exact = float(decoder.stateful_loss(*init, p.batch, cfg)[0])
        assert abs(exact - want) <= 3e-6 * abs(want)


def test_flops_and_bytes_are_the_issues_reckoning():
    cell = manifest.cell(CELL)
    family, model = cell["family"], cell["model"]
    assert family.layer_passes(model) == 32
    token = family.forward_flops_per_token(model, 4096)
    assert token == {
        "projections": 32 * 2 * 4 * 2048 * 2048,
        "attention": 32 * 4 * 16 * 128 * 4097 / 2,
        "mlp": 32 * 2 * 3 * 2048 * 5632,
        "vocabulary": 4 * 2 * 2048 * 49152, "gate": 3 * 2 * 2048}
    # the issue's 13.9 GFLOP a token, forward x 3: layer products 9.87,
    # four head passes 2.42, attention inside the mask 1.61
    assert round(3 * (token["projections"] + token["mlp"]) / 1e9, 2) == 9.87
    assert round(3 * token["vocabulary"] / 1e9, 2) == 2.42
    assert round(3 * token["attention"] / 1e9, 2) == 1.61
    sample = family.flops_per_sample(model, {"seq": 4096})
    by_hand = 3 * (4096 * (token["projections"] + token["attention"]
                           + token["mlp"])
                   + 4095 * (token["vocabulary"] + token["gate"]))
    assert sample == by_hand and round(sample / 4096 / 1e9, 1) == 13.9
    # ONE walk of the head would read a quarter of the vocabulary's part
    assert token["vocabulary"] == 4 * 2 * 2048 * 49152
    workload = {"batch": 2, "seq": 4096}
    both = family.attention_flops_bytes(model, workload, 4)
    scores = 2 * 16 * 4096 * 4097 / 2
    assert both["fwd"][0] == 2 * 32 * 4 * scores * 4 * 128   # remat: twice
    assert both["bwd"][0] == 32 * 4 * scores * 10 * 128
    assert both["fwd"][1] == 2 * 128 * 8192 * (64 * 128 * 2 + 4 * 16)
    assert both["bwd"][1] == 128 * 8192 * (112 * 128 * 2 + 8 * 16)
    half = family.attention_flops_bytes(model, workload, 4, passes=16)
    assert half["bwd"][0] * 2 == both["bwd"][0]


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """The cell's CPU rehearsal, end to end through run.py, and the
    program's call log of it."""
    # one CPU device, as a run of the command by hand has
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    log = tmp_path_factory.mktemp("ouro") / "log.json"
    out = subprocess.run(
        [sys.executable, "benchmark/tools/run_with_log.py", str(log),
         "--workload", CELL, "--seed", str(2 ** 31 + 9), "--seconds", "2",
         "--trace", "1", "--rehearse-cpu"], cwd=manifest.ROOT,
        capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    return (json.loads(out.stdout.strip().splitlines()[-1]),
            json.loads(log.read_text()))


def test_cell_rehearses_on_the_cpu_to_its_end(rehearsed):
    line, log = rehearsed
    checks = line["checks"]
    assert checks["losses_finite"] and checks["matches_reference"] \
        and checks["no_call_failed"] and checks["loss_fell"]
    assert line["rehearsal"] and not line["correct"] and not line["metrics"]
    spans = {s["name"]: s["attrs"] for s in log[-1]["spans"]}
    assert {k: spans["train.dispatch"][k]
            for k in ("loops", "layer_passes", "head_passes")} == {
                "loops": 3, "layer_passes": 6, "head_passes": 3}
    assert {k for k in spans["train.sync"]} >= {
        "loop_nll_1", "loop_nll_3", "exit_mass_1", "exit_mass_3",
        "exit_entropy", "loop_targets"}


def _read(name, host, trace):
    return manifest.module("layer_metrics", name).read(host, trace)


@pytest.fixture
def traced(rehearsed, monkeypatch):
    """A host record and the call log as the rehearsal left them (the
    window's calls matched by their wall seconds), and a reduced trace
    as a traced run on the chip would leave it, with times set so that
    the kernels sit at known parts of their rooflines. The dispatch
    span's facts are set to the cell's own (the rehearsal's are the tiny
    preset's)."""
    import ray_tpu.train

    line, log = rehearsed
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", CELL])
    log = json.loads(json.dumps(log))
    monkeypatch.setattr(ray_tpu.train, "call_log", lambda: list(log),
                        raising=False)
    window = log[2:2 + line["window"]["calls"]]
    roots = [next(s for s in e["spans"] if s["name"] == "train.call")
             for e in window]
    host = {"calls": [{"wall_s": r["end"] - r["start"]} for r in roots],
            "attempted": len(log), "peaks": manifest.peaks("TPU v5 lite")}
    for span in log[-1]["spans"]:
        if span["name"] == "train.dispatch":
            span["attrs"].update(steps=4, loops=4, layer_passes=32,
                                 head_passes=4)
    cell = manifest.cell(CELL)
    both = cell["family"].attention_flops_bytes(
        cell["model"], cell["workload"], 4, 32)
    # the forward at 40 % of the compute roof (two calls), the backward
    # at 50 %
    ops = {"flash_fwd.1": 0.5 * both["fwd"][0] / 0.4 / 197e12,
           "flash_fwd.2": 0.5 * both["fwd"][0] / 0.4 / 197e12,
           "flash_bwd_fused.3": both["bwd"][0] / 0.5 / 197e12,
           "fusion.9": 1.0}
    trace = {"busy_s": sum(ops.values()), "op_self_s": ops,
             "mosaic_ops": [k for k in ops if k != "fusion.9"]}
    return host, trace, log


def test_every_new_reader_returns_a_number(traced):
    host, trace, log = traced
    ops = trace["op_self_s"]
    assert _read(KERNEL_METRICS[1], host, trace) == pytest.approx(40.0)
    assert _read(KERNEL_METRICS[2], host, trace) == pytest.approx(50.0)
    assert _read(KERNEL_METRICS[0], host, trace) == pytest.approx(
        100 * sum(v for k, v in ops.items() if "flash" in k)
        / trace["busy_s"])
    # the counters, from the rehearsal's own spans: three walks there
    sync = [s["attrs"] for e in log[2:2 + len(host["calls"])]
            for s in e["spans"] if s["name"] == "train.sync"]
    assert sync and all(a["loop_targets"] == 2 * 2 * 63 for a in sync)
    entropy = _read(COUNTERS[0], host, trace)
    assert 0.0 < entropy <= 100.0
    assert entropy == pytest.approx(sorted(
        100 * a["exit_entropy"] / (a["loop_targets"] * math.log(3))
        for a in sync)[len(sync) // 2], rel=0.05)
    ratio = _read(COUNTERS[1], host, trace)
    assert 0.5 < ratio < 1.5
    last = _read(COUNTERS[2], host, trace)
    assert 0.0 < last < 100.0
    # a gate at one half: p = (1/2, 1/4, 1/4) over three walks
    assert last == pytest.approx(25.0, abs=5.0)
    assert entropy == pytest.approx(
        100 * 1.5 * math.log(2) / math.log(3), abs=5.0)


def test_readers_give_none_where_there_is_nothing_to_read(traced,
                                                          monkeypatch):
    """A program without the kernels, the counters, the span's
    `layer_passes` or the log (the parent of the PR that added them)
    leaves the metrics out and does not raise."""
    import ray_tpu.train

    host, trace, log = traced
    bare = {"busy_s": 1.0, "op_self_s": {"fusion.9": 1.0}, "mosaic_ops": []}
    for name in KERNEL_METRICS:
        assert _read(name, host, bare) is None
        assert _read(name, host, None) is None
    for entry in log:
        for span in entry["spans"]:
            for key in ("layer_passes", "loop_targets"):
                span["attrs"].pop(key, None)
    for name in (*KERNEL_METRICS[1:], *COUNTERS):
        assert _read(name, host, trace) is None
    monkeypatch.setattr(ray_tpu.train, "call_log", lambda: [], raising=False)
    for name in (*KERNEL_METRICS[1:], *COUNTERS):
        assert _read(name, host, trace) is None
