"""The ``laguna`` family, its cell and its per-layer metrics: found by the
manifest, the configuration's numbers against the catalog's, the
parameter count reckoned again from the built tree, the plain reference
against the program at the tiny preset, the counts behind
``flops_per_sample`` and the kernels' operations and bytes by hand, the
cell's CPU rehearsal end to end with the counter readers on ITS log and
the trace readers on a synthetic trace beside it, and what a program
from before the kinds gives them (nothing, without raising)."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from benchmark import manifest
from benchmark.common import key_seed

CELL, CONFIG = "laguna_d5_seq8k", "laguna_xs2_d5"
KERNEL_METRICS = ("laguna_attention_time_share",
                  "laguna_attention_fwd_roofline_share",
                  "laguna_attention_bwd_roofline_share",
                  "laguna_expert_matmul_time_share",
                  "laguna_expert_matmul_roofline_share")
SPAN_METRICS = ("laguna_window_tiles_filled_share", "laguna_gate_open_share",
                "laguna_expert_rows_filled_share",
                "laguna_expert_load_max_over_mean")
KINDS = ["full_attention"] + ["sliding_attention"] * 3
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
    "intermediate_size": 8192, "num_hidden_layers": 40,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 262144, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
    "layer_types": KINDS * 10, "moe_apply_router_weight_on_input": False,
    "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense"] + ["sparse"] * 39,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 64, 64, 64] * 10}


def test_manifest_finds_cell_family_and_metrics():
    cell = manifest.cell(CELL)
    assert cell["chips"] == 1 and cell["model"]["family"] == "laguna"
    mine = {*KERNEL_METRICS, *SPAN_METRICS}
    assert len(mine) == 9 and mine <= set(cell["readers"])
    for other in (w["name"] for w in manifest.manifest()["workloads"]):
        if other != CELL:
            assert not mine & set(manifest.cell(other)["readers"])
    # every metric without a list of cells reads on this cell too
    assert {"mfu", "mosaic_time_share", "boundary_wait_s", "peak_hbm_gib",
            "device_idle_share", "worker_samples_per_s"} <= set(
                cell["readers"])
    model, entry = cell["model"], next(
        c for c in manifest.manifest()["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == model["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"] == model["source"] \
        and "eight chips share each layer" in model["deployment"]
    cfg = cell["family"].model_cfg(model)
    assert cfg.kinds == (("full", "dense"),) + (("window", "experts"),) * 3 \
        + (("full", "experts"),)
    full, window = dict(cfg.by_kind)["full"], dict(cfg.by_kind)["window"]
    assert (full.n_heads, full.rope_theta, full.rope_dim, full.yarn,
            full.rope_scale) == (48, 5e5, 64, (64.0, 4096, 64.0, 1.0),
                                 1.4158883083359672)
    assert (window.n_heads, window.rope_theta, window.rope_dim, window.yarn,
            window.rope_scale) == (64, 1e4, 128, None, 1.0)
    assert (cfg.d_model, cfg.n_kv_heads, cfg.head_dim, cfg.d_dense,
            cfg.d_expert, cfg.d_shared, cfg.n_experts, cfg.top_k, cfg.held,
            cfg.window, cfg.vocab_size, cfg.routed_scale, cfg.rms_eps) == (
                2048, 8, 128, 8192, 512, 512, 256, 8, (0, 32), 512, 12544,
                2.5, 1e-6)
    assert cfg.attn_gate and cfg.router_input == "mlp" \
        and cfg.routing == "softmax_topk" and cfg.activation == "silu" \
        and cfg.gated and not cfg.tied_head and not cfg.qk_norm \
        and cfg.count_rows and cfg.remat == model["remat"] is True
    workload = cell["workload"]
    assert workload["seq"] == 8192 and workload["steps_per_call"] == 8 \
        and workload["trace_steps"] == 8
    assert workload["batch"] == max(
        int(b) for b, gib in workload["aot_step_GiB"].items()
        if gib is not None and gib <= 13.5)


def test_configuration_keeps_the_catalogs_numbers():
    """Every number of the source's config under the same key, but the
    three `reduced`; the nested groups copied whole."""
    model = manifest.config_file(CONFIG)
    for key, value in PUBLISHED.items():
        if key in model["reduced"]:
            assert model["published"][key] == value and model[key] != value
        else:
            assert model[key] == value, key
    assert (model["num_hidden_layers"], model["num_experts"],
            model["vocab_size"]) == (5, 32, 100352 // 8)
    assert model["router_outputs"] == 256 and 256 // 32 == 8
    for word in ("output gate", "routing weights", "shared expert",
                 "attention", "rotary", "window", "activation", "norm",
                 "initialisation", "optimizer", "sequence length",
                 "held share", "balancing"):
        assert word in model["assumed"], word


def test_parameter_count_is_the_files():
    cell = manifest.cell(CELL)
    p = cell["family"].pieces(cell["model"], dict(cell["workload"], batch=1,
                                                  seq=64), 3)
    params, state = jax.eval_shape(p.model_init, jax.random.key(0))
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == 691_623_936
    assert f"{n:,}".replace(",", " ") in cell["model"]["parameters"]
    assert params["head"].shape == params["embed"].shape[::-1] \
        == (2048, 12544)
    assert set(state) == {"epoch_counters"} \
        and len(state["epoch_counters"]) == 14


def test_reference_matches_program_loss_at_the_tiny_preset():
    from benchmark.families import laguna, laguna_reference
    from ray_tpu.models import decoder

    model = manifest.config_file("laguna_tiny")
    workload = {"batch": 2, "seq": 64}
    for seed in (1, 2 ** 31 + 11):
        p = laguna.pieces(model, workload, seed)
        init = p.model_init(jax.random.key(key_seed(seed)))
        got = float(p.loss_fn(*init, p.batch)[0])       # bf16 compute
        want = laguna_reference.loss(init, p.batch, model)
        assert abs(got - want) <= 2e-3 * abs(want)
        cfg = dataclasses.replace(laguna.model_cfg(model), dtype=jnp.float32)
        exact = float(decoder.stateful_loss(*init, p.batch, cfg)[0])
        assert abs(exact - want) <= 3e-6 * abs(want)


def test_flops_and_bytes_are_the_issues_reckoning():
    cell = manifest.cell(CELL)
    family, model = cell["family"], cell["model"]
    assert family.moe_layers(model) == 4
    assert family.heads_of(model) == {"full": 48, "window": 64}
    token = family.forward_flops_per_token(model, 8192)
    keys = (512 * 513 / 2 + (8192 - 512) * 512) / 8192
    assert token == {
        "projections": 2 * 2048 * (
            2 * (2 * 48 * 128 + 2 * 8 * 128 + 48)
            + 3 * (2 * 64 * 128 + 2 * 8 * 128 + 64)),
        "attention_full": 2 * 4 * 48 * 128 * 8193 / 2,
        "attention_window": 3 * 4 * 64 * 128 * keys,
        "dense_mlp": 2 * 3 * 2048 * 8192,
        "shared_experts": 4 * 2 * 3 * 2048 * 512,
        "routed_experts": 4 * (1.0 * 2 * 3 * 2048 * 512 + 2 * 2048 * 256),
        "vocabulary": 2 * 2048 * 12544}
    total = sum(token.values())
    # the issue's 802 MFLOP a token forward: attention 74 % (projections
    # 43, the full layers' products 25, the window layers' 6), the dense
    # MLP 13, the vocabulary 6, shared and held routed experts 3 each
    assert round(total / 1e6) == 802
    share = {k: round(100 * v / total) for k, v in token.items()}
    assert share == {"projections": 43, "attention_full": 25,
                     "attention_window": 6, "dense_mlp": 13,
                     "shared_experts": 3, "routed_experts": 4,
                     "vocabulary": 6}
    sample = family.flops_per_sample(model, {"seq": 8192})
    assert sample == 3 * (8192 * (total - token["vocabulary"])
                          + 8191 * token["vocabulary"])
    workload = {"batch": 2, "seq": 8192}
    both = family.attention_flops_bytes(model, workload, 8)
    scores = 2 * 8192 * (2 * 48 * 8193 / 2 + 3 * 64 * keys)
    assert both["fwd"][0] == pytest.approx(2 * 8 * scores * 4 * 128)
    assert both["bwd"][0] == pytest.approx(8 * scores * 10 * 128)
    rows = 2 * 8192 * 8
    assert both["fwd"][1] == 2 * rows * (
        2 * ((2 * 48 + 16) * 128 * 2 + 4 * 48)
        + 3 * ((2 * 64 + 16) * 128 * 2 + 4 * 64))
    assert both["bwd"][1] == rows * (
        2 * ((3 * 48 + 32) * 128 * 2 + 8 * 48)
        + 3 * ((3 * 64 + 32) * 128 * 2 + 8 * 64))
    flops, nbytes = family.expert_matmul_flops_bytes(model, 1000.0, 32)
    assert flops == 4 * 2 * 1000 * 3 * 2048 * 512
    assert nbytes == 4 * 2 * 1000 * (2 * 2048 + 3 * 512) \
        + 10 * 32 * 3 * 2048 * 512 * 32


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """The cell's CPU rehearsal, end to end through run.py, and the
    program's call log of it."""
    # one CPU device, as a run of the command by hand has
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    log = tmp_path_factory.mktemp("laguna") / "log.json"
    out = subprocess.run(
        [sys.executable, "benchmark/tools/run_with_log.py", str(log),
         "--workload", CELL, "--seed", str(2 ** 31 + 9), "--seconds", "2",
         "--trace", "1", "--rehearse-cpu"], cwd=manifest.ROOT,
        capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    return (json.loads(out.stdout.strip().splitlines()[-1]),
            json.loads(log.read_text()))


def _read(name, host, trace):
    return manifest.module("layer_metrics", name).read(host, trace)


@pytest.fixture
def traced(rehearsed, monkeypatch):
    """A host record and the call log as the rehearsal left them (the
    window's calls matched by their wall seconds), and a reduced trace
    as a traced run on the chip would leave it, with times set so that
    the kernels sit at known parts of their rooflines. The traced call's
    steps are set to the cell's own."""
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
    spans = {s["name"]: s["attrs"] for s in log[-1]["spans"]}
    spans["train.dispatch"].update(steps=8)
    cell = manifest.cell(CELL)
    both = cell["family"].attention_flops_bytes(
        cell["model"], cell["workload"], 8)
    sync = spans["train.sync"]
    flops, nbytes = cell["family"].expert_matmul_flops_bytes(
        cell["model"], sync["moe_assignments_held"], sync["moe_steps"] * 4)
    # the forward at 40 % of the compute roof (two calls), the backward
    # at 50 %, the expert matmuls at a quarter of theirs (the rehearsal's
    # few rows against the cell's 32 x 3 x 2048 x 512 weights: HBM's)
    ops = {"flash_fwd.1": 0.5 * both["fwd"][0] / 0.4 / 197e12,
           "flash_fwd.2": 0.5 * both["fwd"][0] / 0.4 / 197e12,
           "flash_bwd_fused.3": both["bwd"][0] / 0.5 / 197e12,
           "moe_gmm.4": max(flops / 197e12, nbytes / 819e9) / 0.25,
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
    assert _read(KERNEL_METRICS[3], host, trace) == pytest.approx(
        100 * ops["moe_gmm.4"] / trace["busy_s"])
    assert _read(KERNEL_METRICS[4], host, trace) == pytest.approx(25.0)
    # the facts and counters, from the rehearsal's own spans
    filled = _read(SPAN_METRICS[0], host, trace)
    assert 20.0 < filled < 60.0      # a window of 24 under 16 x 32 tiles
    gate = _read(SPAN_METRICS[1], host, trace)
    assert gate == pytest.approx(50.0, abs=2.0)
    assert 0.0 < _read(SPAN_METRICS[2], host, trace) <= 100.0
    assert _read(SPAN_METRICS[3], host, trace) >= 1.0


def test_readers_give_none_where_there_is_nothing_to_read(traced,
                                                          monkeypatch):
    """A program without the kernels, the counters, the span's facts or
    the log (the parent of the PR that added them) leaves the metrics
    out and does not raise."""
    import ray_tpu.train

    host, trace, log = traced
    bare = {"busy_s": 1.0, "op_self_s": {"fusion.9": 1.0}, "mosaic_ops": []}
    for name in KERNEL_METRICS:
        assert _read(name, host, bare) is None
        assert _read(name, host, None) is None
    for entry in log:
        for span in entry["spans"]:
            for key in [k for k in span["attrs"] if k.startswith((
                    "attention_", "window_scores", "attn_gate", "moe_"))]:
                span["attrs"].pop(key)
    for name in (*KERNEL_METRICS[1:3], KERNEL_METRICS[4], *SPAN_METRICS):
        assert _read(name, host, trace) is None
    monkeypatch.setattr(ray_tpu.train, "call_log", lambda: [], raising=False)
    for name in (*KERNEL_METRICS[1:3], KERNEL_METRICS[4], *SPAN_METRICS):
        assert _read(name, host, trace) is None
