"""The ``lfm2`` family, its cell and its five per-layer metrics: found
by the manifest, the plain reference against the program at the tiny
preset, the counts behind ``flops_per_sample`` and the two kernels'
operations and bytes, the readers on a synthetic trace and log, and the
cell's CPU rehearsal to its end."""

import json
import subprocess
import sys

import jax
import pytest

from benchmark import manifest
from benchmark.common import key_seed

CELL = "lfm2_ep4_seq4k"
METRICS = ("short_conv_time_share", "short_conv_roofline_share",
           "lfm2_expert_matmul_time_share",
           "lfm2_expert_matmul_roofline_share",
           "lfm2_expert_load_max_over_mean")


def test_manifest_finds_cell_family_and_metrics():
    cell = manifest.cell(CELL)
    assert cell["chips"] == 1 and cell["model"]["family"] == "lfm2"
    assert set(METRICS) <= set(cell["readers"])
    for other in ("gpt2s_epoch", "gpt2l_fsdp4", "resnet50_epoch",
                  "smallthinker_ep4_seq8k"):
        assert not set(METRICS) & set(manifest.cell(other)["readers"])
    # the other expert cell's metrics list their cell and stay there
    assert "expert_matmul_time_share" not in cell["readers"]
    model, entry = cell["model"], next(
        c for c in manifest.manifest()["configs"]
        if c["name"] == "lfm2_8b_a1b_ep4")
    assert entry["reduced"] == model["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert model["published"] == {"num_hidden_layers": 24,
                                  "num_experts": 32, "vocab_size": 65536}
    assert len(model["layer_types"]) == 24
    # the router keeps its published width and its experts per token
    cfg = cell["family"].model_cfg(model)
    assert (cfg.n_experts, cfg.top_k, cfg.held) == (32, 4, (0, 8))
    assert cfg.kinds == (("conv", "dense"),) * 2 + (
        ("full", "experts"),) + (("conv", "experts"),) * 3
    assert (cfg.d_model, cfg.d_dense, cfg.d_expert, cfg.head_dim,
            cfg.n_heads, cfg.n_kv_heads, cfg.conv_taps) == (
                2048, 7168, 1792, 64, 32, 8, 3)
    assert cfg.tied_head and cfg.routing == "sigmoid_bias" \
        and cfg.router_input == "mlp" and cfg.activation == "silu"


def test_parameter_count_is_the_files():
    """568.6 M parameters (9.1 GB at 16 B), from the shapes."""
    from ray_tpu.models import decoder

    cell = manifest.cell(CELL)
    cfg = cell["family"].model_cfg(cell["model"])
    shapes = jax.eval_shape(lambda k: decoder.init(k, cfg),
                            jax.random.key(0))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert round(n / 1e5) == 5686
    assert "568.6 M" in cell["model"]["parameters"]


def test_reference_matches_program_loss_at_the_tiny_preset():
    """bf16 program against the float32 reference, 2 x 63 targets:
    measured 2.5e-6 to 1.9e-5 over these four seeds, so 5e-5 (the tight comparison
    is tests/test_decoder_lfm2.py's, in float32)."""
    from benchmark.families import lfm2_reference

    cell = manifest.cell(CELL, rehearse=True)
    model = cell["model"]
    for seed in (2 ** 31 + 11, 5, 6, 7):
        p = cell["family"].pieces(model, cell["workload"], seed)
        init = p.model_init(jax.random.key(key_seed(seed)))
        program, state = p.loss_fn(init[0], init[1], p.batch)
        want = lfm2_reference.loss(init, p.batch, model)
        assert abs(float(program) - want) <= 5e-5 * want, (seed, program, want)
        assert int(state["epoch_counters"]["moe_steps"]) == 1
        assert (state["expert_bias"] != init[1]["expert_bias"]).any()


def test_flops_and_bytes_are_the_issues_reckoning():
    cell = manifest.cell(CELL)
    family, model, workload = cell["family"], cell["model"], cell["workload"]
    part = family.forward_flops_per_token(model, 4096)
    # MFLOP a token: conv projections 168 (31 %), attention 21 + 17,
    # dense MLPs 176 (33 %), held experts 88 + router 0.5 (16 %),
    # vocabulary 67 (12 %): 537
    assert round(part["conv_projections"] / 1e6) == 168
    assert round(part["attention_projections"] / 1e6) == 21
    assert round(part["attention"] / 1e6) == 17
    assert round(part["dense_mlp"] / 1e6) == 176
    assert round(part["experts"] / 1e6) == 89
    assert round(part["vocabulary"] / 1e6) == 67
    assert round(sum(part.values()) / 1e6) == 537
    assert family.flops_per_sample(model, workload) == pytest.approx(
        3 * 4096 * 537.4e6, rel=1e-3)
    assert family.moe_layers(model) == 4
    flops, nbytes = family.expert_matmul_flops_bytes(model, 1000, 4)
    assert flops == 4 * 2 * 1000 * 3 * 2048 * 1792
    assert nbytes > 4 * 2 * 1000 * (2048 + 1792)
    # five conv layers, 15 streams of [tokens, 2048] bf16 a step
    flops, nbytes = family.short_conv_flops_bytes(model, workload, 2)
    tokens = workload["batch"] * workload["seq"] * 2
    assert nbytes == 15 * 2 * tokens * 5 * 2048
    assert flops / 197e12 < 0.05 * nbytes / 819e9      # the bytes bound it


@pytest.fixture
def traced(monkeypatch):
    """A synthetic trace reduction and log of one traced call of the
    cell: 8 steps, a quarter of the assignments held."""
    cell = manifest.cell(CELL)
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", CELL])
    workload, steps = cell["workload"], 8
    layers = cell["family"].moe_layers(cell["model"])
    held = 0.25 * workload["batch"] * workload["seq"] * 4 * layers * steps
    sync = {"moe_assignments": 4 * held, "moe_assignments_held": held,
            "moe_assignments_dropped": 0.0, "moe_steps": steps,
            "moe_assignments_bias_moved": 1234.0, "moe_bias_abs_max": 0.07,
            "moe_expert_tokens_max": 3300,
            "moe_expert_tokens_mean": 3072.0}

    def entry(t0, wall):
        return {"trace_id": str(t0), "spans": [
            {"name": "train.call", "start": t0, "end": t0 + wall,
             "span": "r", "parent": None, "attrs": {}},
            {"name": "train.dispatch", "start": t0, "end": t0 + 1,
             "span": "d", "parent": "r", "attrs": {"steps": steps}},
            {"name": "train.sync", "start": t0 + 1, "end": t0 + 2,
             "span": "s", "parent": "r", "attrs": dict(sync)}]}

    log = [entry(10.0 * i, 5.0) for i in range(5)]
    import ray_tpu.train

    monkeypatch.setattr(ray_tpu.train, "call_log", lambda: list(log),
                        raising=False)
    host = {"calls": [{"wall_s": 5.0}, {"wall_s": 5.0}], "attempted": 5,
            "peaks": manifest.peaks("TPU v5 lite")}
    family = cell["family"]
    gmm_flops, _ = family.expert_matmul_flops_bytes(
        cell["model"], held, layers * steps)
    _, conv_bytes = family.short_conv_flops_bytes(
        cell["model"], workload, steps)
    # the grouped matmuls at half the compute roof, the convolution at
    # 70 % of the memory roof
    ops = {"moe_gmm.1": 0.5 * gmm_flops / 197e12,
           "moe_gmm_dx.2": 0.75 * gmm_flops / 197e12,
           "moe_gmm_dw.3": 0.75 * gmm_flops / 197e12,
           "short_conv.4": 0.8 * conv_bytes / 0.7 / 819e9,
           "short_conv_bwd.5": 0.2 * conv_bytes / 0.7 / 819e9,
           "fusion.9": 1.0}
    trace = {"busy_s": sum(ops.values()), "op_self_s": ops,
             "mosaic_ops": [k for k in ops if k != "fusion.9"]}
    return host, trace


def _read(name, host, trace):
    return manifest.module("layer_metrics", name).read(host, trace)


def test_readers_on_a_synthetic_trace(traced):
    host, trace = traced
    busy, ops = trace["busy_s"], trace["op_self_s"]
    assert _read("lfm2_expert_matmul_roofline_share", host, trace) \
        == pytest.approx(50.0)
    assert _read("short_conv_roofline_share", host, trace) \
        == pytest.approx(70.0)
    assert _read("lfm2_expert_matmul_time_share", host, trace) \
        == pytest.approx(100 * sum(v for k, v in ops.items()
                                   if "moe_gmm" in k) / busy)
    assert _read("short_conv_time_share", host, trace) == pytest.approx(
        100 * (ops["short_conv.4"] + ops["short_conv_bwd.5"]) / busy)
    assert _read("lfm2_expert_load_max_over_mean", host, trace) \
        == pytest.approx(3300 / 3072)


def test_readers_give_none_where_there_is_nothing_to_read(traced,
                                                          monkeypatch):
    """A program without the kernels, the counters or the log (the
    parent of the PR that added them) leaves the metrics out."""
    host, trace = traced
    bare = {"busy_s": 1.0, "op_self_s": {"fusion.9": 1.0}, "mosaic_ops": []}
    for name in METRICS[:4]:
        assert _read(name, host, bare) is None
        assert _read(name, host, None) is None
    import ray_tpu.train

    monkeypatch.setattr(ray_tpu.train, "call_log", lambda: [], raising=False)
    for name in ("lfm2_expert_matmul_roofline_share",
                 "lfm2_expert_load_max_over_mean",
                 "short_conv_roofline_share"):
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
