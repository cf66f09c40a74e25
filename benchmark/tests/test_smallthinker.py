"""The ``smallthinker`` family, its cell and its five per-layer metrics:
found by the manifest, the plain reference against the program at the
tiny preset, the counts behind ``flops_per_sample``, the readers on a
synthetic trace and log, and the cell's CPU rehearsal to its end."""

import json
import subprocess
import sys

import jax
import pytest

from benchmark import manifest
from benchmark.common import key_seed

CELL = "smallthinker_ep4_seq8k"
METRICS = ("expert_matmul_time_share", "expert_matmul_roofline_share",
           "window_attention_time_share", "window_attention_roofline_share",
           "expert_load_max_over_mean")


def test_manifest_finds_cell_family_and_metrics():
    cell = manifest.cell(CELL)
    assert cell["chips"] == 1 and cell["model"]["family"] == "smallthinker"
    assert set(METRICS) <= set(cell["readers"])
    for other in ("gpt2s_epoch", "gpt2l_fsdp4", "resnet50_epoch"):
        assert not set(METRICS) & set(manifest.cell(other)["readers"])
    model, entry = cell["model"], next(
        c for c in manifest.manifest()["configs"]
        if c["name"] == "smallthinker_21b_ep4")
    assert entry["reduced"] == model["reduced"] == [
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size"]
    assert model["published"] == {"num_hidden_layers": 52,
                                  "moe_num_primary_experts": 64,
                                  "vocab_size": 151936}
    # the router keeps its published width and its experts per token
    cfg = cell["family"].model_cfg(model)
    assert (cfg.n_experts, cfg.top_k, cfg.held) == (64, 6, (0, 16))
    assert cfg.attention == ("full", "window", "window", "window")


def test_reference_matches_program_loss_at_the_tiny_preset():
    """bf16 program against the float32 reference, 2 x 63 targets:
    measured up to 1.3e-5 over four seeds (the routing of a token whose
    third and fourth logits lie within bf16's rounding of RMSNorm1's
    output may differ), so 5e-5; a wrong term moves the loss by 9e-6
    (rotary off one layer) to 1.6e-3 here, which is why the tight
    comparison is tests/test_decoder_moe.py's, in float32."""
    from benchmark.families import smallthinker_reference

    cell = manifest.cell(CELL, rehearse=True)
    model = cell["model"]
    for seed in (2 ** 31 + 11, 5, 6, 7):
        p = cell["family"].pieces(model, cell["workload"], seed)
        init = p.model_init(jax.random.key(key_seed(seed)))
        program, state = p.loss_fn(init[0], init[1], p.batch)
        want = smallthinker_reference.loss(init, p.batch, model)
        assert abs(float(program) - want) <= 5e-5 * want, (seed, program, want)
        assert int(state["epoch_counters"]["moe_steps"]) == 1


def test_flops_are_counted_inside_the_masks():
    cell = manifest.cell(CELL)
    family, model, workload = cell["family"], cell["model"], cell["workload"]
    part = family.forward_flops_per_token(model, 8192)
    # the issue's reckoning: projections 168, attention 191, held
    # experts 71, vocabulary 194 MFLOP a token, 15.3 T a sequence
    assert round(part["projections"] / 1e6) == 169      # with the router
    assert round(part["attention"] / 1e6) == 191
    assert round(part["experts"] / 1e6) == 71
    assert round(part["vocabulary"] / 1e6) == 194
    assert family.flops_per_sample(model, workload) == pytest.approx(
        15.36e12, rel=1e-3)
    assert family.mean_keys(8192, None) == 4096.5
    assert family.mean_keys(8192, 4096) == 3072.25
    assert family.mean_keys(64, 4096) == 32.5
    # rows really multiplied, four passes over two grouped products
    flops, nbytes = family.expert_matmul_flops_bytes(model, 1000, 4)
    assert flops == 4 * 2 * 1000 * 3 * 2560 * 768
    assert nbytes > 4 * 2 * 1000 * (2560 + 768)


@pytest.fixture
def traced(monkeypatch):
    """A synthetic trace reduction and log of one traced call of the
    cell: 8 steps, a quarter of the assignments held."""
    cell = manifest.cell(CELL)
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", CELL])
    layers, steps = cell["model"]["num_hidden_layers"], 8
    held = 0.25 * 3 * 8192 * 6 * layers * steps
    sync = {"moe_assignments": 4 * held, "moe_assignments_held": held,
            "moe_assignments_dropped": 0.0, "moe_steps": steps,
            "moe_expert_tokens_max": 2500,
            "moe_expert_tokens_mean": 2304.0}

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
    attn_flops, _ = family.window_attention_flops_bytes(
        cell["model"], cell["workload"], steps)
    # kernels running at half and at a tenth of the compute roof
    ops = {"moe_gmm.1": 0.5 * gmm_flops / 197e12,
           "moe_gmm_dx.2": 0.75 * gmm_flops / 197e12,
           "moe_gmm_dw.3": 0.75 * gmm_flops / 197e12,
           "flash_fwd.4": 10 * attn_flops / 197e12, "fusion.9": 1.0}
    trace = {"busy_s": sum(ops.values()), "op_self_s": ops,
             "mosaic_ops": [k for k in ops if k != "fusion.9"]}
    return host, trace


def _read(name, host, trace):
    return manifest.module("layer_metrics", name).read(host, trace)


def test_readers_on_a_synthetic_trace(traced):
    host, trace = traced
    busy = trace["busy_s"]
    assert _read("expert_matmul_roofline_share", host, trace) \
        == pytest.approx(50.0)
    assert _read("window_attention_roofline_share", host, trace) \
        == pytest.approx(10.0)
    assert _read("expert_matmul_time_share", host, trace) == pytest.approx(
        100 * sum(v for k, v in trace["op_self_s"].items()
                  if "moe_gmm" in k) / busy)
    assert _read("window_attention_time_share", host, trace) \
        == pytest.approx(100 * trace["op_self_s"]["flash_fwd.4"] / busy)
    assert _read("expert_load_max_over_mean", host, trace) \
        == pytest.approx(2500 / 2304)


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
    for name in ("expert_matmul_roofline_share", "expert_load_max_over_mean",
                 "window_attention_roofline_share"):
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
