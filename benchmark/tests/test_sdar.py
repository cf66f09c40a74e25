"""The ``sdar`` family, its cell and its per-layer metrics: found by the
manifest, the configuration's numbers against the catalog's, the
parameter count reckoned again from the built tree, the plain reference
against the program at the tiny preset, the counts behind
``flops_per_sample`` and the attention kernels' operations and bytes by
hand, the readers on a synthetic trace and log, and what a program from
before the objective gives them (nothing, without raising)."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from benchmark import manifest
from benchmark.common import key_seed

CELL = "sdar_ep8_seq4k"
METRICS = ("diffusion_attention_time_share",
           "diffusion_attention_fwd_roofline_share",
           "diffusion_attention_bwd_roofline_share",
           "sdar_expert_matmul_time_share",
           "sdar_expert_matmul_roofline_share")
FACT = "diffusion_tiles_visited_share"
COUNTERS = ("diffusion_masked_share", "sdar_expert_rows_filled_share",
            "sdar_expert_load_max_over_mean")


def test_manifest_finds_cell_family_and_metrics():
    cell = manifest.cell(CELL)
    assert cell["chips"] == 1 and cell["model"]["family"] == "sdar"
    mine = {*METRICS, FACT, *COUNTERS}
    assert mine <= set(cell["readers"])
    for other in ("gpt2s_epoch", "gpt2l_fsdp4", "resnet50_epoch",
                  "smallthinker_ep4_seq8k", "lfm2_ep4_seq4k",
                  "joyai_ep16_seq8k", "nemotron3_ep16_seq8k"):
        assert not mine & set(manifest.cell(other)["readers"])
    model, entry = cell["model"], next(
        c for c in manifest.manifest()["configs"]
        if c["name"] == "sdar_30b_a3b_ep8")
    assert entry["reduced"] == model["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert model["published"] == {"num_hidden_layers": 48,
                                  "num_experts": 128, "vocab_size": 151936}
    assert entry["source"] == model["source"] \
        and "eight chips" in model["deployment"]
    cfg = cell["family"].model_cfg(model)
    assert cfg.kinds == (("full", "experts"),) * 6
    assert (cfg.n_experts, cfg.top_k, cfg.held) == (128, 8, (0, 16))
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.d_expert, cfg.vocab_size, cfg.rope_theta, cfg.rms_eps) == (
                2048, 32, 4, 128, 768, 18992, 1e6, 1e-6)
    assert cfg.rotary == cfg.qk_norm == ("full",) and not cfg.tied_head \
        and cfg.head_rows and cfg.routing == "softmax_topk" \
        and cfg.router_input == "mlp" and cfg.activation == "silu" \
        and cfg.gated and cfg.diffusion_block == 4 and not cfg.d_shared
    # every matrix, the embedding too, starts at normal(0.02)
    assert cfg.init_std == model["init_std"] == 0.02 \
        and "embed_init_std" not in model
    workload = cell["workload"]
    # the traffic the issue fixed: 8 steps a call, 8 traced
    assert workload["seq"] == 4096 and workload["steps_per_call"] == 8 \
        and workload["trace_steps"] == 8
    assert workload["batch"] == max(
        int(b) for b, gib in workload["aot_step_GiB"].items()
        if gib is not None and gib <= 13.5)


def test_configuration_keeps_the_catalogs_numbers():
    """Every number of the source's config under the same key, but the
    three `reduced`."""
    model = manifest.config_file("sdar_30b_a3b_ep8")
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 32768, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    for key, value in published.items():
        if key in model["reduced"]:
            assert model["published"][key] == value and model[key] != value
        else:
            assert model[key] == value, key
    assert (model["num_hidden_layers"], model["num_experts"],
            model["vocab_size"]) == (6, 16, 18992)
    assert model["vocab_size"] * 8 == published["vocab_size"]
    assert model["num_experts"] * 8 == model["router_outputs"] == 128
    for word in ("block length", "noise", "masking schedule", "loss",
                 "mask id", "input order", "positions", "q/k norm",
                 "held share", "optimizer", "initialisation"):
        assert word in model["assumed"], word


def test_parameter_count_is_the_files():
    cell = manifest.cell(CELL)
    p = cell["family"].pieces(cell["model"], dict(cell["workload"], batch=1,
                                                  seq=64), 3)
    params, state = jax.eval_shape(p.model_init, jax.random.key(0))
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == 645_623_296
    assert f"{n:,}".replace(",", " ") in cell["model"]["parameters"]
    layer = sum(x.size for x in jax.tree.leaves(params["layers"])) // 6
    assert layer == 94_638_336
    assert params["head"].shape == params["embed"].shape == (18992, 2048)
    assert set(state) == {"epoch_counters", "noise_seed", "noise_step"}


def test_reference_matches_program_loss_at_the_tiny_preset():
    from benchmark.families import sdar, sdar_reference
    from ray_tpu.models import decoder

    model = manifest.config_file("sdar_tiny")
    workload = {"batch": 2, "seq": 64}
    for seed in (1, 2 ** 31 + 11):
        p = sdar.pieces(model, workload, seed)
        assert int(p.batch.max()) < model["vocab_size"] - 1
        init = p.model_init(jax.random.key(key_seed(seed)))
        got = float(p.loss_fn(*init, p.batch)[0])       # bf16 compute
        want = sdar_reference.loss(init, p.batch, model)
        assert abs(got - want) <= 2e-3 * abs(want)
        cfg = dataclasses.replace(sdar.model_cfg(model), dtype=jnp.float32)
        exact = float(decoder.stateful_loss(*init, p.batch, cfg)[0])
        assert abs(exact - want) <= 3e-6 * abs(want)


def test_flops_and_bytes_are_the_issues_reckoning():
    cell = manifest.cell(CELL)
    family, model = cell["family"], cell["model"]
    row = family.forward_flops_per_row(model, 4096)
    assert {k: round(v / 1e6, 1) for k, v in row.items()} == {
        "projections": 226.5, "routers": 3.1, "attention": 201.5,
        "experts": 56.6, "vocabulary": 38.9}
    assert round(sum(row.values()) / 1e6) == 527          # the issue's 526
    # the mask holds L ** 2 + L b of the plane's 4 L ** 2 scores
    assert family.scores_in_mask(4096, 4) == 4096 * 4096 + 4096 * 4
    from ray_tpu.ops.attention import block_diffusion_mask
    assert family.scores_in_mask(64, 4) == int(
        block_diffusion_mask(128, 4).sum())
    sample = family.flops_per_sample(model, {"seq": 4096})
    assert sample == 3 * 8192 * sum(row.values())
    workload = {"batch": 1, "seq": 4096}
    both = family.diffusion_attention_flops_bytes(model, workload, 8)
    scores = 32 * family.scores_in_mask(4096, 4)
    assert both["fwd"][0] == 2 * 6 * 8 * scores * 4 * 128
    assert both["bwd"][0] == 6 * 8 * scores * 10 * 128
    assert both["fwd"][1] == 2 * 48 * 8192 * (72 * 128 * 2 + 4 * 32)
    assert both["bwd"][1] == 48 * 8192 * (112 * 128 * 2 + 8 * 32)
    flops, nbytes = family.expert_matmul_flops_bytes(model, 1000.0, 48)
    assert flops == 4 * 2 * 1000 * 3 * 2048 * 768
    assert nbytes == 4 * 2 * 1000 * (2048 + 1536 + 768 + 2048) \
        + 10 * 16 * 3 * 2048 * 768 * 48


@pytest.fixture
def traced(monkeypatch):
    """A host record, a reduced trace and a call log as one traced run
    of the cell would leave them, with times set so that the kernels sit
    at known parts of their rooflines."""
    import sys

    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", CELL])
    cell = manifest.cell(CELL)
    family, model, workload = cell["family"], cell["model"], cell["workload"]
    steps = 8
    assignments = steps * 6 * workload["batch"] * 8192 * 8
    sync = {"moe_assignments": float(assignments),
            "moe_assignments_held": assignments / 8,
            "moe_assignments_dropped": 0.0, "moe_steps": steps,
            "moe_rows_filled": assignments / 8,
            "moe_rows_static": assignments + 16 * 512.0 * 6 * steps,
            "moe_expert_tokens_max": 700, "moe_expert_tokens_mean": 512.0,
            "diffusion_masked": 16500.0, "diffusion_targets": 32768.0,
            "diffusion_weight_max": 412.0}

    def entry(t0, wall):
        return {"trace_id": str(t0), "spans": [
            {"name": "train.call", "start": t0, "end": t0 + wall,
             "span": "r", "parent": None, "attrs": {}},
            {"name": "train.dispatch", "start": t0, "end": t0 + 1,
             "span": "d", "parent": "r",
             "attrs": {"steps": steps, "diffusion_block": 4,
                       "diffusion_rows": workload["batch"] * 8192,
                       "attention_tiles_visited": 160,
                       "attention_tiles_plane": 512}},
            {"name": "train.sync", "start": t0 + 1, "end": t0 + 2,
             "span": "s", "parent": "r", "attrs": dict(sync)}]}

    log = [entry(10.0 * i, 5.0) for i in range(5)]
    import ray_tpu.train

    monkeypatch.setattr(ray_tpu.train, "call_log", lambda: list(log),
                        raising=False)
    host = {"calls": [{"wall_s": 5.0}, {"wall_s": 5.0}], "attempted": 5,
            "peaks": manifest.peaks("TPU v5 lite")}
    both = family.diffusion_attention_flops_bytes(model, workload, steps)
    experts = family.expert_matmul_flops_bytes(
        model, sync["moe_assignments_held"], steps * 6)
    least = max(experts[0] / 197e12, experts[1] / 819e9)
    # the forward at 40 % of the compute roof (two calls), the backward
    # at 50 %, the expert matmuls at 30 % of theirs
    ops = {"flash_fwd.1": 0.5 * both["fwd"][0] / 0.4 / 197e12,
           "flash_fwd.2": 0.5 * both["fwd"][0] / 0.4 / 197e12,
           "flash_bwd_fused.3": both["bwd"][0] / 0.5 / 197e12,
           "moe_gmm.4": 0.5 * least / 0.3, "moe_gmm_dx.5": 0.5 * least / 0.3,
           "fusion.9": 1.0}
    trace = {"busy_s": sum(ops.values()), "op_self_s": ops,
             "mosaic_ops": [k for k in ops if k != "fusion.9"]}
    return host, trace, log


def _read(name, host, trace):
    return manifest.module("layer_metrics", name).read(host, trace)


def test_readers_on_a_synthetic_trace(traced):
    host, trace, _ = traced
    busy, ops = trace["busy_s"], trace["op_self_s"]
    assert _read(METRICS[1], host, trace) == pytest.approx(40.0)
    assert _read(METRICS[2], host, trace) == pytest.approx(50.0)
    assert _read(METRICS[0], host, trace) == pytest.approx(
        100 * sum(v for k, v in ops.items() if "flash" in k) / busy)
    assert _read(METRICS[4], host, trace) == pytest.approx(30.0)
    assert _read(METRICS[3], host, trace) == pytest.approx(
        100 * (ops["moe_gmm.4"] + ops["moe_gmm_dx.5"]) / busy)
    assert _read(FACT, host, trace) == pytest.approx(31.25)
    assert _read(COUNTERS[0], host, trace) == pytest.approx(
        100 * 16500 / 32768)
    assert _read(COUNTERS[1], host, trace) == pytest.approx(
        100 / 8 / (1 + 16 * 512 / (
            manifest.cell(CELL)["workload"]["batch"] * 8192 * 8)))
    assert _read(COUNTERS[2], host, trace) == pytest.approx(700 / 512)


def test_readers_give_none_where_there_is_nothing_to_read(traced,
                                                          monkeypatch):
    """A program without the kernels, the counters, the span's
    `diffusion_block` or the log (the parent of the PR that added them)
    leaves the metrics out and does not raise."""
    host, trace, log = traced
    bare = {"busy_s": 1.0, "op_self_s": {"fusion.9": 1.0}, "mosaic_ops": []}
    for name in METRICS:
        assert _read(name, host, bare) is None
        assert _read(name, host, None) is None
    import ray_tpu.train

    for entry in log:
        for span in entry["spans"]:
            for key in ("diffusion_block", "attention_tiles_plane",
                        "diffusion_targets"):
                span["attrs"].pop(key, None)
    for name in (METRICS[1], METRICS[2], FACT, COUNTERS[0]):
        assert _read(name, host, trace) is None
    monkeypatch.setattr(ray_tpu.train, "call_log", lambda: [], raising=False)
    for name in (*METRICS[1:3], METRICS[4], FACT, *COUNTERS):
        assert _read(name, host, trace) is None
