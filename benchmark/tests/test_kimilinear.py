"""The ``kimi_linear`` family, its cell and its per-layer metrics: found
by the manifest, the configuration's numbers against the catalog's, the
parameter count reckoned again from the built tree, the plain reference
against the program at the tiny preset, the counts behind
``flops_per_sample`` and the kernels' operations and bytes by hand, the
cell's CPU rehearsal end to end with the counter readers on ITS log and
the trace readers on a synthetic trace beside it, and what a program
from before the kda mixer gives them (nothing, without raising)."""

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

CELL, CONFIG = "kimilinear_ep32_seq8k", "kimilinear_48b_a3b_ep32"
KERNEL_METRICS = ("kda_time_share", "kda_fwd_roofline_share",
                  "kda_bwd_roofline_share",
                  "kimilinear_attention_time_share",
                  "kimilinear_attention_fwd_roofline_share",
                  "kimilinear_attention_bwd_roofline_share",
                  "kimilinear_expert_matmul_time_share",
                  "kimilinear_expert_matmul_roofline_share")
SPAN_METRICS = ("kimilinear_gate_open_share", "kda_decay_channel_spread",
                "kimilinear_expert_rows_filled_share",
                "kimilinear_expert_load_max_over_mean")
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}


def test_manifest_finds_cell_family_and_metrics():
    cell = manifest.cell(CELL)
    assert cell["chips"] == 1 and cell["model"]["family"] == "kimi_linear"
    mine = {*KERNEL_METRICS, *SPAN_METRICS}
    assert len(mine) == 12 and mine <= set(cell["readers"])
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
        and "thirty-two chips share each layer" in model["deployment"]
    cfg = cell["family"].model_cfg(model)
    assert cfg.kinds == (("kda", "dense"), ("kda", "experts"),
                         ("kda", "experts"), ("latent", "experts"),
                         ("kda", "experts"))
    latent = dict(cfg.by_kind)["latent"]
    assert (latent.n_heads, latent.rope_dim) == (32, 0)
    assert (cfg.delta_key_heads, cfg.delta_value_heads, cfg.delta_key_dim,
            cfg.delta_value_dim, cfg.conv_taps) == (32, 32, 128, 128, 4)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_dim,
            cfg.qk_rope_dim, cfg.v_head_dim) == (0, 512, 128, 64, 128)
    assert (cfg.n_experts, cfg.top_k, cfg.held, cfg.d_expert, cfg.d_shared,
            cfg.d_dense, cfg.routing, cfg.routed_scale, cfg.remat) == (
                256, 8, (0, 8), 1024, 1024, 9216, "sigmoid_bias", 2.446,
                "parts")
    workload = cell["workload"]
    assert workload["seq"] == 8192 and workload["steps_per_call"] == 8 \
        and workload["trace_steps"] == 8
    assert workload["batch"] == max(
        int(b) for b, gib in workload["aot_step_GiB"].items()
        if gib is not None and gib <= 13.5)


def test_configuration_keeps_the_catalogs_numbers():
    """Every key of the source's config under the same key with the same
    value — `linear_attn_config` whole, its lists as published — but the
    three `reduced`."""
    model = manifest.config_file(CONFIG)
    for key, value in PUBLISHED.items():
        if key in model["reduced"]:
            assert model["published"][key] == value and model[key] != value
        else:
            assert model[key] == value, key
    assert (model["num_hidden_layers"], model["num_experts"],
            model["vocab_size"]) == (5, 8, 163840 // 8)
    assert model["router_outputs"] == 256 and 256 // 8 == 32
    for word in ("low ranks", "bias", "decay", "kda mixer",
                 "latent attention", "carried and unused", "norm",
                 "routing weights", "selection bias", "shared expert",
                 "balancing loss", "initialisation", "optimizer",
                 "sequence length", "held share", "remat"):
        assert word in model["assumed"], word
    assert "head_dim 72" in model["assumed"]["carried and unused"]


def test_parameter_count_is_the_files():
    cell = manifest.cell(CELL)
    p = cell["family"].pieces(cell["model"], dict(cell["workload"], batch=1,
                                                  seq=64), 3)
    params, state = jax.eval_shape(p.model_init, jax.random.key(0))
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == 602_433_408
    assert f"{n:,}".replace(",", " ") in cell["model"]["parameters"]
    assert params["head"].shape == params["embed"].shape[::-1] \
        == (2304, 20480)
    assert set(state) == {"epoch_counters", "expert_bias"} \
        and state["expert_bias"].shape == (4, 256)


def test_reference_matches_program_loss_at_the_tiny_preset():
    from benchmark.families import kimi_linear, kimi_linear_reference
    from ray_tpu.models import decoder

    model = manifest.config_file("kimilinear_tiny")
    workload = {"batch": 2, "seq": 128}
    for seed in (1, 2 ** 31 + 11):
        p = kimi_linear.pieces(model, workload, seed)
        init = p.model_init(jax.random.key(key_seed(seed)))
        got = float(p.loss_fn(*init, p.batch)[0])       # bf16 compute
        want = kimi_linear_reference.loss(init, p.batch, model)
        assert abs(got - want) <= 2e-3 * abs(want)
        cfg = dataclasses.replace(kimi_linear.model_cfg(model),
                                  dtype=jnp.float32)
        exact = float(decoder.stateful_loss(*init, p.batch, cfg)[0])
        assert abs(exact - want) <= 3e-6 * abs(want)


def test_flops_and_bytes_are_the_issues_reckoning():
    cell = manifest.cell(CELL)
    family, model = cell["family"], cell["model"]
    assert family.moe_layers(model) == 4 and family.kda_layers(model) == 4
    rule = family.kda_rule_flops_per_token(model)
    # a head: M and P over the channels, three products with the state's
    # shape, two with the chunk's, ten of [64, 64] for the inverse
    assert rule["fwd"] == 32 * (2 * 2 * 64 * 128 + 3 * 2 * 128 * 128
                                + 2 * 2 * 64 * 128 + 10 * 2 * 64 * 64)
    assert rule["bwd"] == 32 * (6 * 2 * 128 * 128 + 4 * 2 * 64 * 128
                                + 4 * 2 * 64 * 128)
    token = family.forward_flops_per_token(model, 8192)
    assert token == {
        "kda_projections": 4 * (
            2 * 2304 * (12288 + 256 + 32) + 2 * 2 * 128 * 4096
            + 2 * 4096 * 2304 + 2 * 4 * 12288),
        "kda_rule": 4 * rule["fwd"],
        "latent_projections": 2 * (2304 * 6144 + 2304 * 576 + 512 * 8192
                                   + 4096 * 2304),
        "latent_attention": 2 * 320 * 32 * 8193 / 2,
        "dense_mlp": 2 * 3 * 2304 * 9216,
        "shared_experts": 4 * 2 * 3 * 2304 * 1024,
        "routed_experts": 4 * (0.25 * 2 * 3 * 2304 * 1024 + 2 * 2304 * 256),
        "vocabulary": 2 * 2304 * 20480}
    total = sum(token.values())
    # the issue's ~775 MFLOP a token forward: the four KDA mixers 44 %
    # (projections 40, the rule 4), the latent layer 18 (projections 7,
    # scores 11), the dense MLP 16, the vocabulary slice 12, the shared
    # experts 7, held experts and routers 2
    assert round(total / 1e6) == 787
    share = {k: round(100 * v / total) for k, v in token.items()}
    assert share == {"kda_projections": 40, "kda_rule": 4,
                     "latent_projections": 7, "latent_attention": 11,
                     "dense_mlp": 16, "shared_experts": 7,
                     "routed_experts": 2, "vocabulary": 12}
    sample = family.flops_per_sample(model, {"seq": 8192})
    assert sample == 3 * (8192 * (total - token["vocabulary"])
                          + 8191 * token["vocabulary"])
    workload = {"batch": 2, "seq": 8192}
    both = family.kda_flops_bytes(model, workload, 8)
    assert both == family.kda_flops_bytes(model, workload, 8,
                                          chunks=4 * 2 * 128)
    tokens = 4 * 2 * 8192 * 8
    state = 4 * 2 * 128 * 8 * 32 * 128 * 128 * 4
    acts, sums = 2 * 4 * 4096, 4 * 32 * 129
    assert both["fwd"] == (2.0 * tokens * rule["fwd"],
                           2.0 * (tokens * (acts + sums) + state))
    assert both["bwd"] == (1.0 * tokens * rule["bwd"],
                           tokens * (2 * acts + 2 * sums) + state)
    # the bytes bound both on paper: under 240 FLOP a byte
    assert both["fwd"][0] / both["fwd"][1] < 240 \
        and both["bwd"][0] / both["bwd"][1] < 240
    attention = family.latent_attention_flops_bytes(model, workload, 8)
    scores = 2 * 32 * 8192 * 8193 / 2
    assert attention["fwd"][0] == pytest.approx(2 * 8 * scores * 2 * 320)
    assert attention["bwd"][0] == pytest.approx(8 * scores * 2 * 832)
    rows = 2 * 32 * 8192 * 8
    assert attention["fwd"][1] == 2 * rows * (640 * 2 + 4)
    assert attention["bwd"][1] == rows * ((4 * 192 + 3 * 128) * 2 + 8)
    flops, nbytes = family.expert_matmul_flops_bytes(model, 1000.0, 32)
    assert flops == 4 * 2 * 1000 * 3 * 2304 * 1024
    assert nbytes == 4 * 2 * 1000 * (2 * 2304 + 3 * 1024) \
        + 10 * 8 * 3 * 2304 * 1024 * 32


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """The cell's CPU rehearsal, end to end through run.py, and the
    program's call log of it."""
    # one CPU device, as a run of the command by hand has
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    log = tmp_path_factory.mktemp("kimilinear") / "log.json"
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
    steps and chunks are set to the cell's own."""
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
    assert spans["train.dispatch"]["kda_layers"] == 4 \
        and spans["train.dispatch"]["kda_heads"] == 4
    spans["train.dispatch"].update(steps=8, kda_chunks=4 * 2 * 128)
    cell = manifest.cell(CELL)
    family, model, workload = cell["family"], cell["model"], cell["workload"]
    rule = family.kda_flops_bytes(model, workload, 8, chunks=4 * 2 * 128)
    attention = family.latent_attention_flops_bytes(model, workload, 8)
    sync = spans["train.sync"]
    flops, nbytes = family.expert_matmul_flops_bytes(
        model, sync["moe_assignments_held"], sync["moe_steps"] * 4)

    def least(pair):
        return max(pair[0] / 197e12, pair[1] / 819e9)

    # the rule's forward at 10 % of its roofline (two calls), its
    # backward at 5 %, the attention forward at 40 % and backward at
    # 50 % of the compute roof, the expert matmuls at a quarter
    ops = {"kda_fwd.1": 0.5 * least(rule["fwd"]) / 0.1,
           "kda_fwd.2": 0.5 * least(rule["fwd"]) / 0.1,
           "kda_bwd.3": least(rule["bwd"]) / 0.05,
           "flash_fwd.4": attention["fwd"][0] / 0.4 / 197e12,
           "flash_bwd_fused.5": attention["bwd"][0] / 0.5 / 197e12,
           "moe_gmm.6": max(flops / 197e12, nbytes / 819e9) / 0.25,
           "fusion.9": 1.0}
    trace = {"busy_s": sum(ops.values()), "op_self_s": ops,
             "mosaic_ops": [k for k in ops if k != "fusion.9"]}
    return host, trace, log


def test_every_new_reader_returns_a_number(traced):
    host, trace, log = traced
    ops = trace["op_self_s"]
    busy = trace["busy_s"]
    assert _read("kda_fwd_roofline_share", host, trace) \
        == pytest.approx(10.0)
    assert _read("kda_bwd_roofline_share", host, trace) \
        == pytest.approx(5.0)
    assert _read("kda_time_share", host, trace) == pytest.approx(
        100 * sum(v for k, v in ops.items() if "kda" in k) / busy)
    assert _read("kimilinear_attention_fwd_roofline_share", host, trace) \
        == pytest.approx(40.0)
    assert _read("kimilinear_attention_bwd_roofline_share", host, trace) \
        == pytest.approx(50.0)
    assert _read("kimilinear_attention_time_share", host, trace) \
        == pytest.approx(
            100 * sum(v for k, v in ops.items() if "flash" in k) / busy)
    assert _read("kimilinear_expert_matmul_time_share", host, trace) \
        == pytest.approx(100 * ops["moe_gmm.6"] / busy)
    assert _read("kimilinear_expert_matmul_roofline_share", host, trace) \
        == pytest.approx(25.0)
    # the counters, from the rehearsal's own spans
    assert _read(SPAN_METRICS[0], host, trace) == pytest.approx(50.0, abs=3.0)
    # the channels of a head forget at rates two decades apart
    assert _read(SPAN_METRICS[1], host, trace) > 1.0
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
                    "attention_", "kda_", "moe_", "rope_dim", "steps"))]:
                span["attrs"].pop(key)
    spans_only = (KERNEL_METRICS[1], KERNEL_METRICS[2], KERNEL_METRICS[4],
                  KERNEL_METRICS[5], KERNEL_METRICS[7], *SPAN_METRICS)
    for name in spans_only:
        assert _read(name, host, trace) is None, name
    monkeypatch.setattr(ray_tpu.train, "call_log", lambda: [], raising=False)
    for name in spans_only:
        assert _read(name, host, trace) is None, name


def test_group_norms_name_the_issues_groups():
    from benchmark.families import kimi_linear
    from ray_tpu.models import decoder

    cfg = kimi_linear.model_cfg(manifest.config_file("kimilinear_tiny"))
    params = decoder.init(jax.random.key(0), cfg)
    norms = kimi_linear.group_norms(params)
    assert set(norms) == {
        "kda_projections", "kda_W_f", "kda_W_g", "kda_A_log", "kda_dt_bias",
        "latent", "dense", "router", "experts", "shared", "norms", "embed",
        "head"}
    total = sum(float((x.astype(jnp.float32) ** 2).sum())
                for x in jax.tree.leaves(params))
    assert sum(v * v for v in norms.values()) == pytest.approx(total,
                                                               rel=1e-5)
