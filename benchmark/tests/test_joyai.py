"""The ``joyai`` family, its cell and its five per-layer metrics: found
by the manifest, the configuration's widths and counts, the plain
reference against the program at the tiny preset, the counts behind
``flops_per_sample`` and the attention kernels' operations and bytes by
hand, the readers on a synthetic trace and log, and the cell's CPU
rehearsal to its end."""

import json
import subprocess
import sys

import jax
import pytest

from benchmark import manifest
from benchmark.common import key_seed

CELL = "joyai_ep16_seq8k"
METRICS = ("latent_attention_time_share",
           "latent_attention_fwd_roofline_share",
           "latent_attention_bwd_roofline_share",
           "expert_rows_filled_share", "joyai_expert_load_max_over_mean")


def test_manifest_finds_cell_family_and_metrics():
    cell = manifest.cell(CELL)
    assert cell["chips"] == 1 and cell["model"]["family"] == "joyai"
    assert set(METRICS) <= set(cell["readers"])
    for other in ("gpt2s_epoch", "gpt2l_fsdp4", "resnet50_epoch",
                  "smallthinker_ep4_seq8k", "lfm2_ep4_seq4k"):
        assert not set(METRICS) & set(manifest.cell(other)["readers"])
    assert "expert_load_max_over_mean" not in cell["readers"]
    model, entry = cell["model"], next(
        c for c in manifest.manifest()["configs"]
        if c["name"] == "joyai_flash_ep16")
    assert entry["reduced"] == model["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert model["published"] == {"num_hidden_layers": 40,
                                  "n_routed_experts": 256,
                                  "vocab_size": 129280}
    assert entry["source"] == model["source"] and "sixteen" in \
        model["deployment"]
    # every published width, the router's 256 outputs and 8 a token
    cfg = cell["family"].model_cfg(model)
    assert (cfg.n_experts, cfg.top_k, cfg.held) == (256, 8, (0, 16))
    assert cfg.kinds == (("latent", "dense"),) + (("latent", "experts"),) * 4
    assert (cfg.d_model, cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim, cfg.d_dense,
            cfg.d_expert, cfg.d_shared, cfg.vocab_size) == (
                2048, 32, 1536, 512, 128, 64, 128, 7168, 768, 768, 16160)
    assert (cfg.routed_scale, cfg.mtp, cfg.mtp_weight, cfg.rope_theta,
            cfg.rms_eps) == (2.5, 1, 0.3, 32e6, 1e-6)
    assert not cfg.tied_head and cfg.routing == "sigmoid_bias" \
        and cfg.router_input == "mlp" and cfg.activation == "silu"
    assert cfg.moe_layers == cell["family"].moe_layers(model) == 5


def test_configuration_keeps_the_catalogs_numbers():
    """Every number of the source's config under the same key, but the
    three `reduced`."""
    model = manifest.config_file("joyai_flash_ep16")
    published = {
        "first_k_dense_replace": 1, "head_dim": 64, "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "moe_intermediate_size": 768,
        "n_group": 1, "n_shared_experts": 1, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_key_value_heads": 32,
        "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
        "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 32000000,
        "routed_scaling_factor": 2.5, "topk_group": 1, "v_head_dim": 128,
        "moe_layer_freq": 1, "ep_size": 1}
    assert {k: model[k] for k in published} == published
    assert (model["num_hidden_layers"], model["n_routed_experts"],
            model["vocab_size"]) == (5, 16, 129280 // 8)
    for key in ("latent attention", "rotary", "routing weights",
                "shared expert", "selection bias", "multi-token prediction",
                "loss", "held share"):
        assert key in model["assumed"]


def test_parameter_count_is_the_files():
    """680.44 M parameters (10.89 GB at 16 B), from the shapes."""
    from ray_tpu.models import decoder

    cell = manifest.cell(CELL)
    cfg = cell["family"].model_cfg(cell["model"])
    shapes = jax.eval_shape(lambda k: decoder.init(k, cfg),
                            jax.random.key(0))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert round(n / 1e4) == 68044
    assert "680.44 M" in cell["model"]["parameters"]
    mtp = sum(x.size for x in jax.tree.leaves(shapes["mtp"]))
    assert round(mtp / 1e4) == 11549
    state = jax.eval_shape(lambda k: decoder.state_init(k, cfg),
                           jax.random.key(0))
    assert state["expert_bias"].shape == (5, 256)


def test_reference_matches_program_loss_at_the_tiny_preset():
    """bf16 program against the float32 reference, both terms, 2 x 63
    and 2 x 62 targets: measured 3.3e-6 to 4.1e-5 over these four
    seeds, so 1e-4 (the tight comparison is tests/test_decoder_joyai.py's,
    in float32)."""
    from benchmark.families import joyai_reference

    cell = manifest.cell(CELL, rehearse=True)
    model = cell["model"]
    for seed in (2 ** 31 + 11, 5, 6, 7):
        p = cell["family"].pieces(model, cell["workload"], seed)
        init = p.model_init(jax.random.key(key_seed(seed)))
        program, state = p.loss_fn(init[0], init[1], p.batch)
        want = joyai_reference.loss(init, p.batch, model)
        main, second, _ = joyai_reference.loss_terms(init, p.batch, model)
        c = state["epoch_counters"]
        assert abs(float(program) - want) <= 1e-4 * want, (seed, program, want)
        assert abs(float(c["loss_main"]) - main) <= 1e-4 * main
        assert abs(float(c["loss_mtp"]) - second) <= 1e-4 * second
        assert int(c["moe_steps"]) == 1
        assert (state["expert_bias"] != init[1]["expert_bias"]).any()


def test_flops_and_bytes_are_the_issues_reckoning():
    cell = manifest.cell(CELL)
    family, model, workload = cell["family"], cell["model"], cell["workload"]
    part = family.forward_flops_per_token(model, 8192)
    # MFLOP a token at 8192: the latent attention's products 503 (44 %),
    # its projections 316 (28 %; six blocks of 2 x 26.35 M less the
    # norms), the vocabulary slice twice 132 (12 %), the dense MLP 88,
    # shared experts 47, held routed experts 24 + routers 5, the MTP's
    # joining product 17: 1 133
    assert part["latent_attention"] == 6 * 2 * (192 + 128) * 32 * 8193 / 2
    assert part["latent_projections"] == 6 * 2 * (
        2048 * 1536 + 1536 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048)
    assert part["vocabulary"] == 2 * 2 * 2048 * 16160
    assert part["dense_mlp"] == 2 * 3 * 2048 * 7168
    assert part["shared_experts"] == 5 * 2 * 3 * 2048 * 768
    assert part["routed_experts"] == 5 * (0.5 * 2 * 3 * 2048 * 768
                                          + 2 * 2048 * 256)
    assert part["mtp_join"] == 2 * 4096 * 2048
    assert round(sum(part.values()) / 1e6) == 1133
    assert round(100 * part["latent_attention"] / sum(part.values())) == 44
    seq = workload["seq"]
    assert family.flops_per_sample(model, workload) == pytest.approx(
        3 * seq * sum(family.forward_flops_per_token(model, seq).values()),
        rel=1e-3)
    # the kernels, by hand, one step of the cell's batch: 6 blocks;
    # T (T + 1) / 2 scores a head and sequence inside the mask
    both = family.latent_attention_flops_bytes(model, workload, 1)
    b = workload["batch"]
    scores = b * 32 * seq * (seq + 1) / 2
    assert both["fwd"][0] == 2 * 6 * scores * 2 * (192 + 128)
    assert both["bwd"][0] == 6 * scores * 2 * (3 * 192 + 2 * 128)
    rows = b * 32 * seq
    assert both["fwd"][1] == 2 * 6 * rows * ((192 + 192 + 128 + 128) * 2 + 4)
    assert both["bwd"][1] == 6 * rows * ((4 * 192 + 3 * 128) * 2 + 8)
    for flops, nbytes in both.values():   # the products bound both
        assert flops / 197e12 > 5 * nbytes / 819e9
    two = family.latent_attention_flops_bytes(model, workload, 2)
    assert two["fwd"][0] == 2 * both["fwd"][0]


@pytest.fixture
def traced(monkeypatch):
    """A synthetic trace reduction and log of one traced call of the
    cell: 8 steps, a sixteenth of the assignments held."""
    cell = manifest.cell(CELL)
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", CELL])
    workload, steps = cell["workload"], 8
    layers = cell["family"].moe_layers(cell["model"])
    assignments = workload["batch"] * workload["seq"] * 8 * layers * steps
    sync = {"moe_assignments": float(assignments),
            "moe_assignments_held": assignments / 16,
            "moe_assignments_dropped": 0.0, "moe_steps": steps,
            "moe_rows_filled": assignments / 16,
            "moe_rows_static": assignments + 16 * 512.0 * layers * steps,
            "loss_main": 10.1, "loss_mtp": 10.2,
            "moe_expert_tokens_max": 300, "moe_expert_tokens_mean": 256.0}

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
    both = cell["family"].latent_attention_flops_bytes(
        cell["model"], workload, steps)
    # the forward at half the compute roof, the backward at 60 %
    ops = {"flash_fwd.1": 0.5 * both["fwd"][0] / 0.5 / 197e12,
           "flash_fwd.2": 0.5 * both["fwd"][0] / 0.5 / 197e12,
           "flash_bwd_fused.3": both["bwd"][0] / 0.6 / 197e12,
           "moe_gmm.4": 0.5, "fusion.9": 1.0}
    trace = {"busy_s": sum(ops.values()), "op_self_s": ops,
             "mosaic_ops": [k for k in ops if k != "fusion.9"]}
    return host, trace, sync


def _read(name, host, trace):
    return manifest.module("layer_metrics", name).read(host, trace)


def test_readers_on_a_synthetic_trace(traced):
    host, trace, sync = traced
    busy, ops = trace["busy_s"], trace["op_self_s"]
    assert _read("latent_attention_fwd_roofline_share", host, trace) \
        == pytest.approx(50.0)
    assert _read("latent_attention_bwd_roofline_share", host, trace) \
        == pytest.approx(60.0)
    assert _read("latent_attention_time_share", host, trace) \
        == pytest.approx(100 * sum(v for k, v in ops.items()
                                   if "flash" in k) / busy)
    assert _read("expert_rows_filled_share", host, trace) == pytest.approx(
        100 * sync["moe_rows_filled"] / sync["moe_rows_static"])
    assert 5.5 < _read("expert_rows_filled_share", host, trace) < 6.25
    assert _read("joyai_expert_load_max_over_mean", host, trace) \
        == pytest.approx(300 / 256)


def test_readers_give_none_where_there_is_nothing_to_read(traced,
                                                          monkeypatch):
    """A program without the kernels, the counters or the log (the
    parent of the PR that added them) leaves the metrics out and does
    not raise."""
    host, trace, _ = traced
    bare = {"busy_s": 1.0, "op_self_s": {"fusion.9": 1.0}, "mosaic_ops": []}
    for name in METRICS[:3]:
        assert _read(name, host, bare) is None
        assert _read(name, host, None) is None
    import ray_tpu.train

    monkeypatch.setattr(ray_tpu.train, "call_log", lambda: [], raising=False)
    for name in METRICS[1:]:
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
