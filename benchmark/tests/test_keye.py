"""The ``keye`` family, its cell and its per-layer metrics: found by the
manifest, the configuration's numbers against the catalog's, the
parameter count reckoned again from the built tree, the plain reference
against the program at the tiny preset, the counts behind
``flops_per_sample`` and the kernels' operations and bytes by hand, the
readers on a synthetic trace and log, what a program from before the
indexer gives them (nothing, without raising), and the cell's rehearsal
on the CPU."""

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

CELL = "keye_ep8_seq16k"
KERNELS = ("index_time_share", "index_scores_roofline_share",
           "keye_attention_time_share", "keye_attention_fwd_roofline_share",
           "keye_attention_bwd_roofline_share",
           "keye_expert_matmul_time_share",
           "keye_expert_matmul_roofline_share")
COUNTERS = ("index_selected_share", "index_beyond_window_share",
            "index_tiles_visited_share", "index_kl_nats",
            "keye_expert_rows_filled_share", "keye_expert_load_max_over_mean",
            "expert_rows_walked_share")


def test_manifest_finds_cell_family_and_metrics():
    cell = manifest.cell(CELL)
    assert cell["chips"] == 1 and cell["model"]["family"] == "keye"
    mine = {*KERNELS, *COUNTERS}
    assert mine <= set(cell["readers"])
    for other in (w["name"] for w in manifest.manifest()["workloads"]
                  if w["name"] != CELL):
        # (the rows walked: one reader, shared with the expert cells)
        assert not mine - {COUNTERS[6]} & set(
            manifest.cell(other)["readers"])
    model, entry = cell["model"], next(
        c for c in manifest.manifest()["configs"]
        if c["name"] == "keye_vl2_30b_a3b_ep8")
    assert entry["reduced"] == model["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert model["published"] == {"num_hidden_layers": 48,
                                  "num_experts": 128, "vocab_size": 151936}
    assert entry["source"] == model["source"] \
        and "eight chips" in model["deployment"]
    cfg = cell["family"].model_cfg(model)
    assert cfg.kinds == (("full", "experts"),) * 5
    assert (cfg.n_experts, cfg.top_k, cfg.held) == (128, 8, (0, 16))
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.d_expert, cfg.vocab_size, cfg.rope_theta, cfg.rms_eps) == (
                2048, 32, 4, 128, 768, 18992, 1e7, 1e-6)
    assert (cfg.index_topk, cfg.index_heads, cfg.index_dim,
            cfg.index_loss_weight, cfg.index_dtype) == (
                2048, 16, 64, 1.0, jnp.bfloat16)
    assert cfg.rotary == cfg.qk_norm == ("full",) and not cfg.tied_head \
        and cfg.head_rows and cfg.routing == "softmax_topk" \
        and cfg.router_input == "mlp" and cfg.activation == "silu" \
        and cfg.gated and not cfg.d_shared and not cfg.diffusion_block
    assert cfg.init_std == 0.02     # every matrix but the embedding
    assert model["embed_init_std"] == 1.0 \
        and model["optimizer"] == {"name": "adamw", "learning_rate": 3e-4,
                                   "warmup_steps": 2000}
    workload = cell["workload"]
    assert workload["seq"] == 16384 and workload["steps_per_call"] == 4 \
        and workload["trace_steps"] == 4
    assert workload["batch"] == max(
        int(b) for b, gib in workload["aot_step_GiB"].items()
        if gib is not None and gib <= 13.5)


def test_configuration_keeps_the_catalogs_numbers():
    """Every number of the source's config under the same key, but the
    three `reduced`; `sa_config` and `rope_scaling` whole."""
    model = manifest.config_file("keye_vl2_30b_a3b_ep8")
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 262144, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "KeyeVL2",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "num_local_experts": 128,
        "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24],
                         "rope_type": "default", "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    for key, value in published.items():
        if key in model["reduced"]:
            assert model[key] != value and model["published"][key] == value
        else:
            assert model[key] == value, key
    for word in ("vision tower", "indexer input", "indexer key norm",
                 "indexer positions", "indexer weights", "index scores",
                 "selection", "q_chunk_size, kv_chunk_size", "indexer loss",
                 "mrope", "q/k norm", "initialisation",
                 "optimizer", "held share"):
        assert word in model["assumed"], word


def test_parameter_count_is_the_files():
    cell = manifest.cell(CELL)
    p = cell["family"].pieces(cell["model"], dict(cell["workload"], batch=1,
                                                  seq=64), 3)
    params, state = jax.eval_shape(p.model_init, jax.random.key(0))
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == 562_290_560
    assert f"{n:,}".replace(",", " ") in cell["model"]["parameters"]
    layers = params["layers"]
    assert sum(x.size for x in jax.tree.leaves(layers)) // 5 == 96_899_456
    assert sum(layers[name].size for name in (
        "w_index_q", "w_index_k", "index_k_norm", "w_index_w")) // 5 \
        == 2_261_120
    assert params["head"].shape == params["embed"].shape == (18992, 2048)
    assert set(state) == {"epoch_counters"}


def test_the_recipe_scales_the_embedding_and_warms_the_rate_up():
    """`embed_init_std`: the embedding alone starts there, every other
    matrix at `init_std`, and the key still decides both.
    `warmup_steps`: step n runs at (n + 1) / warmup_steps of the rate."""
    from benchmark.families import keye

    model = manifest.config_file("keye_tiny")
    workload = {"batch": 1, "seq": 64}
    made = [keye.pieces(model, workload, seed).model_init(
        jax.random.key(key_seed(seed)))[0] for seed in (5, 2 ** 31 + 6)]
    assert float(made[0]["embed"].std()) == pytest.approx(1.0, rel=0.03)
    assert float(made[0]["head"].std()) == pytest.approx(0.02, rel=0.03)
    assert float(made[0]["layers"]["router"].std()) == pytest.approx(
        0.02, rel=0.1)
    assert not jnp.array_equal(made[0]["embed"], made[1]["embed"])
    from ray_tpu.models import decoder

    plain = decoder.init(jax.random.key(key_seed(5)), keye.model_cfg(model))
    assert jnp.allclose(plain["embed"] * 50.0, made[0]["embed"]) \
        and jnp.allclose(plain["head"], made[0]["head"])
    opt = keye.optimizer({"name": "adamw", "learning_rate": 3e-4,
                          "warmup_steps": 2000})
    p = {"w": jnp.zeros(2)}
    state, moved = opt.init(p), []
    for _ in range(3):      # Adam's first steps move a weight by the rate
        update, state = opt.update({"w": jnp.ones(2)}, state, p)
        moved.append(float(-update["w"][0]))
    assert moved == pytest.approx([1.5e-7, 3e-7, 4.5e-7], rel=1e-3)
    with pytest.raises(ValueError, match="AdamW alone"):
        keye.optimizer({"name": "sgd", "learning_rate": 0.1,
                        "warmup_steps": 10})


def test_reference_matches_program_loss_at_the_tiny_preset():
    from benchmark.families import keye, keye_reference
    from ray_tpu.models import decoder

    model = manifest.config_file("keye_tiny")
    workload = {"batch": 2, "seq": 128}
    for seed in (1, 2 ** 31 + 11):
        p = keye.pieces(model, workload, seed)
        init = p.model_init(jax.random.key(key_seed(seed)))
        got = float(p.loss_fn(*init, p.batch)[0])       # bf16 compute
        want = keye_reference.loss(init, p.batch, model)
        assert abs(got - want) <= 2e-3 * abs(want)
        cfg = dataclasses.replace(keye.model_cfg(model), dtype=jnp.float32,
                                  index_dtype=jnp.float32)
        exact = float(decoder.stateful_loss(*init, p.batch, cfg)[0])
        assert abs(exact - want) <= 3e-6 * abs(want)
        main, index = keye_reference.terms(init, p.batch, model)
        assert main + index == pytest.approx(want) and index > 0


def test_flops_and_bytes_are_the_issues_reckoning():
    cell = manifest.cell(CELL)
    family, model, workload = cell["family"], cell["model"], cell["workload"]
    causal, selected = family.pairs(16384, 2048)
    assert causal == 16384 * 16385 // 2 == 134_225_920
    assert selected == 2048 * 2049 // 2 + (16384 - 2048) * 2048 == 31_458_304
    assert 100 * selected / causal == pytest.approx(23.4, abs=0.1)
    parts = family.forward_flops_per_token(model, 16384)
    by_hand = {
        "projections": 5 * 2 * (2 * 2048 * 4096 + 2 * 2048 * 512),
        "index_projections": 5 * 2 * 2048 * (1024 + 64 + 16),
        "index_scores": 5 * 2 * 16 * 64 * causal / 16384,
        "routers": 5 * 2 * 2048 * 128,
        "attention": 5 * 4 * 32 * 128 * selected / 16384,
        "experts": 5 * 1.0 * 6 * 2048 * 768,
        "vocabulary": 2 * 2048 * 18992}
    assert parts == pytest.approx(by_hand)
    total = sum(parts.values())
    assert total == pytest.approx(580e6, rel=0.01)          # the issue's
    share = {k: round(100 * v / total, 1) for k, v in parts.items()}
    assert share["attention"] == pytest.approx(27, abs=0.5) \
        and share["index_scores"] == pytest.approx(14.5, abs=0.5) \
        and share["projections"] == pytest.approx(32, abs=1)
    assert family.flops_per_sample(model, workload) == pytest.approx(
        3 * 16384 * total)
    flops, nbytes = family.index_flops_bytes(model, workload, 4)
    calls = 2 * 5 * 4
    assert flops == calls * causal * 2 * 16 * 64
    assert nbytes == calls * (16384 * (17 * 64 * 2 + 64) + 4 * 16384 ** 2)
    both = family.attention_flops_bytes(model, workload, 4)
    assert both["fwd"][0] == 2 * 20 * 32 * selected * 4 * 128
    assert both["bwd"][0] == 20 * 32 * selected * 10 * 128
    rows = 16384
    assert both["fwd"][1] == 2 * 20 * (
        rows * (72 * 128 * 2 + 4 * 32) + rows * rows)
    assert both["bwd"][1] == 20 * (
        rows * ((96 + 16) * 128 * 2 + 8 * 32) + rows * rows)


@pytest.fixture
def traced(monkeypatch):
    """A host record, a reduced trace and a call log as one traced run
    of the cell would leave them, with times set so that the kernels sit
    at known parts of their rooflines."""
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", CELL])
    cell = manifest.cell(CELL)
    family, model, workload = cell["family"], cell["model"], cell["workload"]
    steps, layers = 4, 5
    assignments = steps * layers * 16384 * 8
    causal, selected = family.pairs(16384, 2048)
    sync = {"moe_assignments": float(assignments),
            "moe_assignments_held": assignments / 8,
            "moe_assignments_dropped": 0.0, "moe_steps": steps,
            "moe_rows_filled": assignments / 8,
            "moe_rows_static": assignments + 16 * 512.0 * layers * steps,
            "moe_rows_walked": (assignments + 16 * 512.0 * layers * steps) / 4,
            "moe_expert_tokens_max": 1400, "moe_expert_tokens_mean": 1024.0,
            "index_pairs_selected": float(steps * layers * selected),
            "index_pairs_causal": float(steps * layers * causal),
            "index_pairs_beyond_window": steps * layers * selected * 0.6,
            "index_tiles_visited": 1050.0 * steps * layers,
            "index_tiles_causal": 1056.0 * steps * layers,
            "index_kl_sum": 0.25 * steps * layers * 16384,
            "index_kl_count": float(steps * layers * 16384),
            "loss_main": 9.9, "loss_index": 0.25}

    def entry(t0, wall):
        return {"trace_id": str(t0), "spans": [
            {"name": "train.call", "start": t0, "end": t0 + wall,
             "span": "r", "parent": None, "attrs": {}},
            {"name": "train.dispatch", "start": t0, "end": t0 + 1,
             "span": "d", "parent": "r",
             "attrs": {"steps": steps, "index_topk": 2048,
                       "index_rows": layers * 16384,
                       "index_tile": "256x512"}},
            {"name": "train.sync", "start": t0 + 1, "end": t0 + 2,
             "span": "s", "parent": "r", "attrs": dict(sync)}]}

    log = [entry(10.0 * i, 5.0) for i in range(5)]
    import ray_tpu.train

    monkeypatch.setattr(ray_tpu.train, "call_log", lambda: list(log),
                        raising=False)
    host = {"calls": [{"wall_s": 5.0}, {"wall_s": 5.0}], "attempted": 5,
            "peaks": manifest.peaks("TPU v5 lite")}
    both = family.attention_flops_bytes(model, workload, steps)
    index = family.index_flops_bytes(model, workload, steps)
    experts = family.expert_matmul_flops_bytes(
        model, sync["moe_assignments_held"], steps * layers)

    def least(flops, nbytes):
        return max(flops / 197e12, nbytes / 819e9)

    # the forward at 10 % of its roof (two calls), the backward at 20 %,
    # the index scores at 60 % of theirs (the write's), the expert
    # matmuls at 30 %
    ops = {"flash_fwd.1": 0.5 * least(*both["fwd"]) / 0.1,
           "flash_fwd.2": 0.5 * least(*both["fwd"]) / 0.1,
           "flash_bwd_fused.3": least(*both["bwd"]) / 0.2,
           "index_scores.7": least(*index) / 0.6,
           "moe_gmm.4": 0.5 * least(*experts) / 0.3,
           "moe_gmm_dx.5": 0.5 * least(*experts) / 0.3,
           "fusion.9": 1.0}
    trace = {"busy_s": sum(ops.values()), "op_self_s": ops,
             "mosaic_ops": [k for k in ops if k != "fusion.9"]}
    return host, trace, log


def _read(name, host, trace):
    return manifest.module("layer_metrics", name).read(host, trace)


def test_readers_on_a_synthetic_trace(traced):
    host, trace, _ = traced
    busy, ops = trace["busy_s"], trace["op_self_s"]
    assert _read(KERNELS[0], host, trace) == pytest.approx(
        100 * ops["index_scores.7"] / busy)
    assert _read(KERNELS[1], host, trace) == pytest.approx(60.0)
    assert _read(KERNELS[2], host, trace) == pytest.approx(
        100 * sum(v for k, v in ops.items() if "flash" in k) / busy)
    assert _read(KERNELS[3], host, trace) == pytest.approx(10.0)
    assert _read(KERNELS[4], host, trace) == pytest.approx(20.0)
    assert _read(KERNELS[5], host, trace) == pytest.approx(
        100 * (ops["moe_gmm.4"] + ops["moe_gmm_dx.5"]) / busy)
    assert _read(KERNELS[6], host, trace) == pytest.approx(30.0)
    assert _read(COUNTERS[0], host, trace) == pytest.approx(23.437, abs=1e-2)
    assert _read(COUNTERS[1], host, trace) == pytest.approx(60.0)
    assert _read(COUNTERS[2], host, trace) == pytest.approx(
        100 * 1050 / 1056)
    assert _read(COUNTERS[3], host, trace) == pytest.approx(0.25)
    assert _read(COUNTERS[4], host, trace) == pytest.approx(
        100 / 8 / (1 + 16 * 512 / (16384 * 8)))
    assert _read(COUNTERS[5], host, trace) == pytest.approx(1400 / 1024)
    assert _read(COUNTERS[6], host, trace) == pytest.approx(25.0)


def test_readers_give_none_where_there_is_nothing_to_read(traced,
                                                          monkeypatch):
    """A program without the kernels, the counters, the span's
    `index_topk` or the log (the parent of the PR that added them)
    leaves the metrics out and does not raise."""
    host, trace, log = traced
    bare = {"busy_s": 1.0, "op_self_s": {"fusion.9": 1.0}, "mosaic_ops": []}
    for name in KERNELS:
        assert _read(name, host, bare) is None
        assert _read(name, host, None) is None
    import ray_tpu.train

    for entry in log:
        for span in entry["spans"]:
            for key in ("index_topk", "index_pairs_causal",
                        "index_pairs_selected", "index_tiles_causal",
                        "index_kl_count"):
                span["attrs"].pop(key, None)
    for name in (KERNELS[1], KERNELS[3], KERNELS[4], *COUNTERS[:4]):
        assert _read(name, host, trace) is None
    monkeypatch.setattr(ray_tpu.train, "call_log", lambda: [], raising=False)
    for name in (KERNELS[1], KERNELS[3], KERNELS[4], KERNELS[6], *COUNTERS):
        assert _read(name, host, trace) is None


def test_cell_rehearses_on_the_cpu_to_its_end():
    # one CPU device, as a run of the command by hand has: the test
    # tree's eight virtual ones are not the benchmark's to count
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "keye_ep8_seq16k",
         "--seed", str(2 ** 31 + 9), "--seconds", "2", "--trace", "1",
         "--rehearse-cpu"], cwd=manifest.ROOT, capture_output=True,
        text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    checks = line["checks"]
    assert checks["losses_finite"] and checks["matches_reference"] \
        and checks["no_call_failed"]
    assert line["rehearsal"] and not line["correct"] and not line["metrics"]
