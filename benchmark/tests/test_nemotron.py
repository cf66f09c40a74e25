"""The ``nemotron_h`` family, its cell and its four per-layer metrics:
found by the manifest, the configuration's widths and counts against the
catalog's numbers, the blocks read as layers, the plain reference
against the program at the tiny preset, the counts behind
``flops_per_sample`` and the scan kernels' operations and bytes by hand,
the readers on a synthetic trace and log, and the cell's CPU rehearsal
to its end."""

import json
import subprocess
import sys

import jax
import pytest

from benchmark import manifest
from benchmark.common import key_seed

CELL = "nemotron3_ep16_seq8k"
METRICS = ("ssd_time_share", "ssd_fwd_roofline_share",
           "ssd_bwd_roofline_share", "nemotron_expert_matmul_roofline_share")
# the accepted readers of the expert layer and of the attention kernels,
# under names of this cell's own: two read the trace, two the counters
TRACE_COPIES = ("nemotron_expert_matmul_time_share",
                "nemotron_attention_time_share")
COUNTER_COPIES = ("nemotron_expert_rows_filled_share",
                  "nemotron_expert_load_max_over_mean")
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def test_manifest_finds_cell_family_and_metrics():
    cell = manifest.cell(CELL)
    assert cell["chips"] == 1 and cell["model"]["family"] == "nemotron_h"
    assert {*METRICS, *TRACE_COPIES, *COUNTER_COPIES} <= set(cell["readers"])
    for other in ("gpt2s_epoch", "gpt2l_fsdp4", "resnet50_epoch",
                  "smallthinker_ep4_seq8k", "lfm2_ep4_seq4k",
                  "joyai_ep16_seq8k"):
        assert not {*METRICS, *TRACE_COPIES, *COUNTER_COPIES} & set(
            manifest.cell(other)["readers"])
    model, entry = cell["model"], next(
        c for c in manifest.manifest()["configs"]
        if c["name"] == "nemotron3_nano_ep16")
    assert entry["reduced"] == model["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert model["published"] == {"num_hidden_layers": 52,
                                  "n_routed_experts": 128,
                                  "vocab_size": 131072}
    assert entry["source"] == model["source"] and "sixteen" in \
        model["deployment"].lower()
    # the model's own first nine blocks, read as five layers
    family = cell["family"]
    assert family.blocks(model) == "MEMEM*EME"
    cfg = family.model_cfg(model)
    assert cfg.kinds == (("ssm", "experts"), ("ssm", "experts"),
                         ("ssm", "none"), ("full", "experts"),
                         ("ssm", "experts"))
    # every published width, the router's 128 outputs and 6 a token
    assert (cfg.n_experts, cfg.top_k, cfg.held) == (128, 6, (0, 8))
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state,
            cfg.ssm_chunk, cfg.conv_taps, cfg.d_expert, cfg.d_shared,
            cfg.vocab_size) == (2688, 32, 2, 128, 64, 64, 8, 128, 128, 4,
                                1856, 3712, 16384)
    assert (cfg.routed_scale, cfg.rms_eps, cfg.mtp, cfg.rotary) == (
        2.5, 1e-5, 0, ())
    assert not cfg.tied_head and not cfg.gated \
        and cfg.routing == "sigmoid_bias" and cfg.router_input == "mlp" \
        and cfg.activation == "relu2"
    assert cfg.moe_layers == family.moe_layers(model) == 4
    assert family.count(model, "ssm") == 4 and family.count(model, "full") == 1


def test_configuration_keeps_the_catalogs_numbers():
    """Every number of the source's config under the same key, but the
    three `reduced`; the block pattern whole, as published."""
    model = manifest.config_file("nemotron3_nano_ep16")
    published = {
        "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
        "hidden_size": 2688, "intermediate_size": 1856,
        "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
        "mamba_num_heads": 64, "max_position_embeddings": 262144,
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
        "n_groups": 8, "n_shared_experts": 1, "norm_eps": 1e-05,
        "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_key_value_heads": 2, "num_logits_to_keep": 1,
        "partial_rotary_factor": 1, "rope_theta": 10000,
        "routed_scaling_factor": 2.5, "ssm_state_size": 128,
        "time_step_floor": 0.0001, "time_step_max": 0.1,
        "time_step_min": 0.001, "topk_group": 1}
    assert {k: model[k] for k in published} == published
    assert model["hybrid_override_pattern"] == PATTERN and len(PATTERN) == 52
    assert (PATTERN.count("M"), PATTERN.count("E"), PATTERN.count("*")) \
        == (23, 23, 6)
    assert (model["num_hidden_layers"], model["n_routed_experts"],
            model["vocab_size"]) == (9, 8, 131072 // 8)
    for key in ("blocks", "mamba mixer", "attention", "routing weights",
                "experts", "shared expert", "selection bias",
                "initialisation", "held share", "sequence length"):
        assert key in model["assumed"], key


@pytest.mark.parametrize("pattern,layers", [
    ("MEMEM*EME", [("ssm", "experts"), ("ssm", "experts"), ("ssm", "none"),
                   ("full", "experts"), ("ssm", "experts")]),
    ("EMEM*", [("none", "experts"), ("ssm", "experts"), ("ssm", "none"),
               ("full", "none")]),
    ("M-*-EE", [("ssm", "dense"), ("full", "dense"), ("none", "experts"),
                ("none", "experts")])])
def test_blocks_read_as_layers(pattern, layers):
    from benchmark.families import nemotron_h, nemotron_h_reference

    model = {"hybrid_override_pattern": pattern,
             "num_hidden_layers": len(pattern)}
    assert nemotron_h.layer_kinds(model) == layers
    assert [(a, m) for a, m, _ in nemotron_h_reference.layers(model)] \
        == layers


def test_parameter_count_is_the_files():
    """666.96 M parameters (10.67 GB at 16 B), from the shapes."""
    from ray_tpu.models import decoder

    cell = manifest.cell(CELL)
    cfg = cell["family"].model_cfg(cell["model"])
    shapes = jax.eval_shape(lambda k: decoder.init(k, cfg),
                            jax.random.key(0))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert n == 666_962_944
    assert "666.96 M" in cell["model"]["parameters"]
    layers = shapes["layers"]
    assert layers["ssm_in"].shape == (4, 4096 + 6144 + 64, 2688)
    assert layers["w_up"].shape == (4, 8, 1856, 2688)
    assert layers["norm1"].shape == (5, 2688)      # four mixers, one attention
    assert layers["norm2"].shape == (4, 2688)      # (ssm, none) has none
    assert "w_gate" not in layers and "ws_gate" not in layers
    state = jax.eval_shape(lambda k: decoder.state_init(k, cfg),
                           jax.random.key(0))
    assert state["expert_bias"].shape == (4, 128)


def test_reference_matches_program_loss_at_the_tiny_preset():
    """bf16 program against the float32 reference, 2 x 63 targets:
    measured 2e-6 to 3e-5 over these four seeds, so 1e-4 (the tight
    comparison is tests/test_decoder_nemotron.py's, in float32)."""
    from benchmark.families import nemotron_h_reference

    cell = manifest.cell(CELL, rehearse=True)
    model = cell["model"]
    for seed in (2 ** 31 + 11, 5, 6, 7):
        p = cell["family"].pieces(model, cell["workload"], seed)
        init = p.model_init(jax.random.key(key_seed(seed)))
        program, state = p.loss_fn(init[0], init[1], p.batch)
        want = nemotron_h_reference.loss(init, p.batch, model)
        c = state["epoch_counters"]
        assert abs(float(program) - want) <= 1e-4 * want, (seed, program, want)
        low = nemotron_h_reference.loss(init, p.batch, model,
                                        dtype=jax.numpy.bfloat16)
        # the blocks in bf16 under a float32 loss land as near as the
        # program, whose products are bf16 too: the loss at seeded
        # weights tells no precision apart (PERF.md section 7, PR 39)
        assert 0 < abs(low - want) <= 1e-3 * want
        assert int(c["moe_steps"]) == 1 and float(c["ssm_log_decay_min"]) < 0
        assert (state["expert_bias"] != init[1]["expert_bias"]).any()


def test_flops_and_bytes_are_the_issues_reckoning():
    cell = manifest.cell(CELL)
    family, model, workload = cell["family"], cell["model"], cell["workload"]
    part = family.forward_flops_per_token(model, 8192)
    # MFLOP a token at 8192: the Mamba projections 310 and the scan 14
    # (45 %), shared experts 160, the vocabulary slice 88, attention
    # scores 67 and projections 47, held routed experts 30, routers 3:
    # 718
    assert part["ssm_projections"] == 4 * 2 * (2688 * 10304 + 4096 * 2688)
    q, h, p, g, n = 128, 64, 64, 8, 128
    chunk_fwd = h * (2 * q * q * p + 2 * q * n * p + 2 * q * p * n) \
        + g * 2 * q * q * n
    assert part["ssm_scan"] == 4 * chunk_fwd / q
    assert part["shared_experts"] == 4 * 2 * 2 * 2688 * 3712
    assert part["routed_experts"] == 4 * (6 * 8 / 128) * 2 * 2 * 2688 * 1856
    assert part["routers"] == 4 * 2 * 2688 * 128
    assert part["attention"] == 2 * 2 * 32 * 128 * 8193 / 2
    assert part["attention_projections"] == 2 * (2 * 2688 * 4096
                                                 + 2 * 2688 * 256)
    assert part["vocabulary"] == 2 * 2688 * 16384 and part["dense_mlp"] == 0
    assert round(sum(part.values()) / 1e6) == 718
    mamba = part["ssm_projections"] + part["ssm_scan"]
    assert round(100 * mamba / sum(part.values())) == 45
    seq = workload["seq"]
    assert family.flops_per_sample(model, workload) == pytest.approx(
        3 * seq * sum(family.forward_flops_per_token(model, seq).values()),
        rel=1e-3)
    # the kernels, by hand, one step of the cell's batch: 4 mixer blocks
    both = family.ssd_flops_bytes(model, workload, 1)
    chunks = 4 * workload["batch"] * seq // q
    assert both["fwd"][0] == 2 * chunks * chunk_fwd
    assert both["bwd"][0] == chunks * h * (
        2 * 2 * q * q * p + 2 * 2 * q * q * n + 4 * 2 * q * p * n)
    rows, states = 4 * h * q, 4 * h * p * n
    assert both["fwd"][1] == chunks * (
        2 * (2 * q * h * p + 2 * q * g * n) * 2 + 4 * rows + states)
    assert both["bwd"][1] == chunks * (
        (3 * q * h * p + 4 * q * g * n) * 2 + 4 * rows + states + 4 * h * p)
    for flops, nbytes in both.values():   # the bytes bound both
        assert nbytes / 819e9 > flops / 197e12
    assert family.ssd_flops_bytes(model, workload, 2, chunks=chunks)["fwd"] \
        == tuple(2 * x for x in both["fwd"])
    # the experts: two products an expert
    flops, nbytes = family.expert_matmul_flops_bytes(model, 1000.0, 4)
    assert flops == 4 * 2 * 1000 * 2 * 2688 * 1856
    assert nbytes == 4 * 1000 * 2 * (2688 + 1856) * 2 \
        + 4 * 8 * 2 * 2688 * 1856 * (3 * 2 + 4)


@pytest.fixture
def traced(monkeypatch):
    """A synthetic trace reduction and log of one traced call of the
    cell: 8 steps, a sixteenth of the assignments held."""
    cell = manifest.cell(CELL)
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", CELL])
    workload, steps = cell["workload"], 8
    family, model = cell["family"], cell["model"]
    assignments = workload["batch"] * workload["seq"] * 6 * 4 * steps
    sync = {"moe_assignments": float(assignments),
            "moe_assignments_held": assignments / 16,
            "moe_assignments_dropped": 0.0, "moe_steps": steps,
            "moe_rows_filled": assignments / 16,
            "moe_rows_static": assignments + 8 * 512.0 * 4 * steps,
            "ssm_log_decay_min": -21.5, "ssm_dt_max": 0.11,
            "moe_expert_tokens_max": 500, "moe_expert_tokens_mean": 384.0}
    chunks = 4 * workload["batch"] * workload["seq"] // 128

    def entry(t0, wall):
        return {"trace_id": str(t0), "spans": [
            {"name": "train.call", "start": t0, "end": t0 + wall,
             "span": "r", "parent": None, "attrs": {}},
            {"name": "train.dispatch", "start": t0, "end": t0 + 1,
             "span": "d", "parent": "r",
             "attrs": {"steps": steps, "ssm_layers": 4,
                       "ssm_chunks": chunks}},
            {"name": "train.sync", "start": t0 + 1, "end": t0 + 2,
             "span": "s", "parent": "r", "attrs": dict(sync)}]}

    log = [entry(10.0 * i, 5.0) for i in range(5)]
    import ray_tpu.train

    monkeypatch.setattr(ray_tpu.train, "call_log", lambda: list(log),
                        raising=False)
    host = {"calls": [{"wall_s": 5.0}, {"wall_s": 5.0}], "attempted": 5,
            "peaks": manifest.peaks("TPU v5 lite")}
    both = family.ssd_flops_bytes(model, workload, steps)
    experts = family.expert_matmul_flops_bytes(
        model, sync["moe_assignments_held"], steps * 4)
    least = max(experts[0] / 197e12, experts[1] / 819e9)
    # the forward at 40 % of the memory roof, the backward at 25 %, the
    # expert matmuls at 30 % of theirs
    ops = {"ssd_fwd.1": 0.5 * both["fwd"][1] / 0.4 / 819e9,
           "ssd_fwd.2": 0.5 * both["fwd"][1] / 0.4 / 819e9,
           "ssd_bwd.3": both["bwd"][1] / 0.25 / 819e9,
           "moe_gmm.4": 0.5 * least / 0.3, "moe_gmm_dw.5": 0.5 * least / 0.3,
           "flash_fwd.6": 0.011, "flash_bwd_fused.7": 0.009,
           "fusion.9": 1.0}
    trace = {"busy_s": sum(ops.values()), "op_self_s": ops,
             "mosaic_ops": [k for k in ops if k != "fusion.9"]}
    return host, trace, log


def _read(name, host, trace):
    return manifest.module("layer_metrics", name).read(host, trace)


def test_readers_on_a_synthetic_trace(traced):
    host, trace, _ = traced
    busy, ops = trace["busy_s"], trace["op_self_s"]
    assert _read("ssd_fwd_roofline_share", host, trace) == pytest.approx(40.0)
    assert _read("ssd_bwd_roofline_share", host, trace) == pytest.approx(25.0)
    assert _read("ssd_time_share", host, trace) == pytest.approx(
        100 * sum(v for k, v in ops.items() if "ssd" in k) / busy)
    assert _read("nemotron_expert_matmul_roofline_share", host, trace) \
        == pytest.approx(30.0)
    assert _read("nemotron_expert_matmul_time_share", host, trace) \
        == pytest.approx(100 * (ops["moe_gmm.4"] + ops["moe_gmm_dw.5"]) / busy)
    assert _read("nemotron_attention_time_share", host, trace) \
        == pytest.approx(100 * 0.020 / busy)
    # the counters of the window's calls: a sixteenth held, 8 tiles more
    assert _read("nemotron_expert_rows_filled_share", host, trace) \
        == pytest.approx(100 / 16 / (1 + 8 * 512 / (
            manifest.cell(CELL)["workload"]["batch"] * 8192 * 6)))
    assert _read("nemotron_expert_load_max_over_mean", host, trace) \
        == pytest.approx(500 / 384)


def test_readers_give_none_where_there_is_nothing_to_read(traced,
                                                          monkeypatch):
    """A program without the kernels, the counters, the span's
    `ssm_chunks` or the log (the parent of the PR that added them)
    leaves the metrics out and does not raise."""
    host, trace, log = traced
    bare = {"busy_s": 1.0, "op_self_s": {"fusion.9": 1.0}, "mosaic_ops": []}
    for name in METRICS + TRACE_COPIES:
        assert _read(name, host, bare) is None
        assert _read(name, host, None) is None
    import ray_tpu.train

    for span in log[-1]["spans"]:
        span["attrs"].pop("ssm_chunks", None)
    for name in METRICS[1:3]:
        assert _read(name, host, trace) is None
    monkeypatch.setattr(ray_tpu.train, "call_log", lambda: [], raising=False)
    for name in METRICS[1:] + COUNTER_COPIES:
        assert _read(name, host, trace) is None


def test_cell_rehearses_on_the_cpu_to_its_end():
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2 ** 31 + 5), "--seconds", "2", "--trace", "1",
         "--rehearse-cpu"], cwd=manifest.ROOT, capture_output=True,
        text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    checks = line["checks"]
    assert checks["losses_finite"] and checks["matches_reference"] \
        and checks["loss_fell"] and checks["no_call_failed"]
    assert line["rehearsal"] and not line["correct"] and not line["metrics"]
