"""The ``qwen3_next`` family, its cell and its per-layer metrics: found
by the manifest, the configuration's numbers against the catalog's, the
parameter count reckoned again from the built tree, the plain reference
against the program at the tiny preset, the counts behind
``flops_per_sample`` and the kernels' operations and bytes by hand, the
cell's CPU rehearsal end to end with the counter readers on ITS log and
the trace readers on a synthetic trace beside it, and what a program
from before the delta mixer gives them (nothing, without raising)."""

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

CELL, CONFIG = "qwen3next_ep16_seq8k", "qwen3next_80b_a3b_ep16"
KERNEL_METRICS = ("gdr_time_share", "gdr_fwd_roofline_share",
                  "gdr_bwd_roofline_share",
                  "qwen3next_attention_time_share",
                  "qwen3next_attention_fwd_roofline_share",
                  "qwen3next_attention_bwd_roofline_share",
                  "qwen3next_expert_matmul_time_share",
                  "qwen3next_expert_matmul_roofline_share")
SPAN_METRICS = ("gdr_write_strength_share", "qwen3next_gate_open_share",
                "qwen3next_expert_rows_filled_share",
                "qwen3next_expert_load_max_over_mean")
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


def test_manifest_finds_cell_family_and_metrics():
    cell = manifest.cell(CELL)
    assert cell["chips"] == 1 and cell["model"]["family"] == "qwen3_next"
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
        and "sixteen chips share each layer" in model["deployment"]
    cfg = cell["family"].model_cfg(model)
    assert cfg.kinds == (("delta", "experts"),) * 3 + (("full", "experts"),)
    full = dict(cfg.by_kind)["full"]
    assert (full.n_heads, full.rope_theta, full.rope_dim, full.yarn) \
        == (16, 1e7, 64, None)
    assert (cfg.delta_key_heads, cfg.delta_value_heads, cfg.delta_key_dim,
            cfg.delta_value_dim, cfg.conv_taps) == (16, 32, 128, 128, 4)
    assert cfg.attn_gate == "element" and cfg.norm_plus_one \
        and cfg.shared_gate and cfg.qk_norm == ("full",)
    assert (cfg.n_experts, cfg.top_k, cfg.held, cfg.d_expert, cfg.d_shared,
            cfg.routing) == (512, 10, (0, 32), 512, 512, "softmax_topk")
    workload = cell["workload"]
    assert workload["seq"] == 8192 and workload["steps_per_call"] == 8 \
        and workload["trace_steps"] == 8
    assert workload["batch"] == max(
        int(b) for b, gib in workload["aot_step_GiB"].items()
        if gib is not None and gib <= 13.5)


def test_configuration_keeps_the_catalogs_numbers():
    """Every number of the source's config under the same key, but the
    three `reduced`."""
    model = manifest.config_file(CONFIG)
    for key, value in PUBLISHED.items():
        if key in model["reduced"]:
            assert model["published"][key] == value and model[key] != value
        else:
            assert model[key] == value, key
    assert (model["num_hidden_layers"], model["num_experts"],
            model["vocab_size"]) == (4, 32, 151936 // 8)
    assert model["router_outputs"] == 512 and 512 // 32 == 16
    for word in ("norm", "delta mixer", "attention", "routing weights",
                 "shared expert", "left out", "initialisation", "optimizer",
                 "sequence length", "held share", "balancing"):
        assert word in model["assumed"], word
    assert "MTP" in model["assumed"]["left out"]


def test_parameter_count_is_the_files():
    cell = manifest.cell(CELL)
    p = cell["family"].pieces(cell["model"], dict(cell["workload"], batch=1,
                                                  seq=64), 3)
    params, state = jax.eval_shape(p.model_init, jax.random.key(0))
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == 625_667_136
    assert f"{n:,}".replace(",", " ") in cell["model"]["parameters"]
    assert params["head"].shape == params["embed"].shape[::-1] \
        == (2048, 18992)
    assert set(state) == {"epoch_counters"} \
        and len(state["epoch_counters"]) == 17


def test_reference_matches_program_loss_at_the_tiny_preset():
    from benchmark.families import qwen3_next, qwen3_next_reference
    from ray_tpu.models import decoder

    model = manifest.config_file("qwen3next_tiny")
    workload = {"batch": 2, "seq": 128}
    for seed in (1, 2 ** 31 + 11):
        p = qwen3_next.pieces(model, workload, seed)
        init = p.model_init(jax.random.key(key_seed(seed)))
        got = float(p.loss_fn(*init, p.batch)[0])       # bf16 compute
        want = qwen3_next_reference.loss(init, p.batch, model)
        assert abs(got - want) <= 2e-3 * abs(want)
        cfg = dataclasses.replace(qwen3_next.model_cfg(model),
                                  dtype=jnp.float32)
        exact = float(decoder.stateful_loss(*init, p.batch, cfg)[0])
        assert abs(exact - want) <= 3e-6 * abs(want)


def test_flops_and_bytes_are_the_issues_reckoning():
    cell = manifest.cell(CELL)
    family, model = cell["family"], cell["model"]
    assert family.moe_layers(model) == 4
    rule = family.delta_rule_flops_per_token(model)
    # a value head: three products with the state's shape, two with the
    # chunk's, ten of [64, 64] for the inverse; a key head: K K^T, Q K^T
    assert rule["fwd"] == 32 * (3 * 2 * 128 * 128 + 2 * 2 * 64 * 128
                                + 10 * 2 * 64 * 64) + 16 * 2 * 2 * 64 * 128
    assert rule["bwd"] == 32 * (6 * 2 * 128 * 128 + 4 * 2 * 64 * 128) \
        + 16 * 4 * 2 * 64 * 128
    token = family.forward_flops_per_token(model, 8192)
    assert token == {
        "delta_projections": 3 * (2 * 2048 * (12288 + 64) + 2 * 4096 * 2048
                                  + 2 * 4 * 8192),
        "delta_rule": 3 * rule["fwd"],
        "attention_projections": 2 * 2048 * (3 * 16 * 256 + 2 * 2 * 256),
        "attention_scores": 4 * 16 * 256 * 8193 / 2,
        "shared_experts": 4 * (2 * 3 * 2048 * 512 + 2 * 2048),
        "routed_experts": 4 * (0.625 * 2 * 3 * 2048 * 512
                               + 2 * 2048 * 512),
        "vocabulary": 2 * 2048 * 18992}
    total = sum(token.values())
    # the issue's 470 MFLOP a token forward: the delta layers 47 %
    # (projections 43, the rule 5), the attention layer 26 (projections
    # 12, scores 14), the experts 10, the vocabulary slice 16
    assert round(total / 1e6) == 473
    share = {k: round(100 * v / total) for k, v in token.items()}
    assert share == {"delta_projections": 43, "delta_rule": 5,
                     "attention_projections": 12, "attention_scores": 14,
                     "shared_experts": 5, "routed_experts": 5,
                     "vocabulary": 16}
    sample = family.flops_per_sample(model, {"seq": 8192})
    assert sample == 3 * (8192 * (total - token["vocabulary"])
                          + 8191 * token["vocabulary"])
    workload = {"batch": 1, "seq": 8192}
    both = family.gated_delta_flops_bytes(model, workload, 8)
    assert both == family.gated_delta_flops_bytes(model, workload, 8,
                                                  chunks=3 * 128)
    tokens = 3 * 8192 * 8
    state = 3 * 128 * 8 * 32 * 128 * 128 * 4
    acts, sums = 2 * (2 * 2048 + 2 * 4096), 3 * 4 * 32
    assert both["fwd"] == (2.0 * tokens * rule["fwd"],
                           2.0 * (tokens * (acts + sums) + state))
    assert both["bwd"] == (1.0 * tokens * rule["bwd"],
                           tokens * (2 * acts + 2 * sums) + state)
    # the bytes bound both on paper: under 240 FLOP a byte
    assert both["fwd"][0] / both["fwd"][1] < 240 \
        and both["bwd"][0] / both["bwd"][1] < 240
    attention = family.attention_flops_bytes(model, workload, 8)
    scores = 16 * 8192 * 8193 / 2
    assert attention["fwd"][0] == pytest.approx(2 * 8 * scores * 4 * 256)
    assert attention["bwd"][0] == pytest.approx(8 * scores * 10 * 256)
    rows = 8192 * 8
    assert attention["fwd"][1] == 2 * rows * ((2 * 16 + 4) * 256 * 2 + 64)
    assert attention["bwd"][1] == rows * ((3 * 16 + 8) * 256 * 2 + 128)
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
    log = tmp_path_factory.mktemp("qwen3next") / "log.json"
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
    spans["train.dispatch"].update(steps=8, delta_chunks=3 * 128)
    cell = manifest.cell(CELL)
    family, model, workload = cell["family"], cell["model"], cell["workload"]
    rule = family.gated_delta_flops_bytes(model, workload, 8,
                                          chunks=3 * 128)
    attention = family.attention_flops_bytes(model, workload, 8)
    sync = spans["train.sync"]
    flops, nbytes = family.expert_matmul_flops_bytes(
        model, sync["moe_assignments_held"], sync["moe_steps"] * 4)

    def least(pair):
        return max(pair[0] / 197e12, pair[1] / 819e9)

    # the rule's forward at 10 % of its roofline (two calls), its
    # backward at 5 %, the attention forward at 40 % and backward at
    # 50 % of the compute roof, the expert matmuls at a quarter
    ops = {"gdr_fwd.1": 0.5 * least(rule["fwd"]) / 0.1,
           "gdr_fwd.2": 0.5 * least(rule["fwd"]) / 0.1,
           "gdr_bwd.3": least(rule["bwd"]) / 0.05,
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
    assert _read("gdr_fwd_roofline_share", host, trace) \
        == pytest.approx(10.0)
    assert _read("gdr_bwd_roofline_share", host, trace) \
        == pytest.approx(5.0)
    assert _read("gdr_time_share", host, trace) == pytest.approx(
        100 * sum(v for k, v in ops.items() if "gdr" in k) / busy)
    assert _read("qwen3next_attention_fwd_roofline_share", host, trace) \
        == pytest.approx(40.0)
    assert _read("qwen3next_attention_bwd_roofline_share", host, trace) \
        == pytest.approx(50.0)
    assert _read("qwen3next_attention_time_share", host, trace) \
        == pytest.approx(
            100 * sum(v for k, v in ops.items() if "flash" in k) / busy)
    assert _read("qwen3next_expert_matmul_time_share", host, trace) \
        == pytest.approx(100 * ops["moe_gmm.6"] / busy)
    assert _read("qwen3next_expert_matmul_roofline_share", host, trace) \
        == pytest.approx(25.0)
    # the counters, from the rehearsal's own spans
    for name in SPAN_METRICS[:2]:
        assert _read(name, host, trace) == pytest.approx(50.0, abs=3.0)
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
                    "attention_", "delta_", "attn_gate", "shared_gate",
                    "moe_", "rope_dim"))]:
                span["attrs"].pop(key)
    spans_only = (KERNEL_METRICS[1], KERNEL_METRICS[2], KERNEL_METRICS[4],
                  KERNEL_METRICS[5], KERNEL_METRICS[7], *SPAN_METRICS)
    for name in spans_only:
        assert _read(name, host, trace) is None, name
    monkeypatch.setattr(ray_tpu.train, "call_log", lambda: [], raising=False)
    for name in spans_only:
        assert _read(name, host, trace) is None, name


def test_scope_share_reads_a_compiled_texts_scopes():
    """`tools/scope_share.py` on the text of a small compiled step: each
    instruction's `op_name` names the scope it was traced under, forward
    and on the way back, and the first scope asked for wins."""
    sys.path.insert(0, os.path.join(manifest.ROOT, "benchmark", "tools"))
    import scope_share

    def loss(x, w):
        with jax.named_scope("mixer_delta"):
            y = jnp.tanh(x @ w)
        with jax.named_scope("experts"):
            return (y @ w.T).sum()

    ones = jnp.ones((8, 8))
    module, names = scope_share.op_names(
        jax.jit(jax.grad(loss)).lower(ones, ones).compile().as_text())
    assert module == "jit_loss"
    found = {scope_share.scope_of(v, ("mixer_delta", "experts"))
             for v in names.values()}
    assert found == {"mixer_delta", "experts", "unnamed"}
    assert any("transpose(jvp(mixer_delta))" in v for v in names.values())
    line = ('  ROOT %fusion.234 = bf16[8]{0} fusion(bf16[8]{0} %p), kind='
            'kLoop, calls=%f, metadata={op_name="jit(fused)/checkpoint/'
            'mixer_delta/dot_general" source_file="x.py" source_line=3}')
    assert scope_share.op_names("HloModule jit_fused, x\n" + line) == (
        "jit_fused", {"fusion.234": "jit(fused)/checkpoint/mixer_delta/"
                                    "dot_general"})
