"""The cell `qwen3next_ep16_seq8k` at its rehearsal sizes on the CPU,
through `Trainer.train()` on the normal path: the benchmark's own
command to its end, the new facts on `train.dispatch` and the new
counters on `train.sync`. A file of its own so that
`tests/test_decoder_qwen3next.py` and this one run on two workers."""

import json
import os
import subprocess
import sys

from benchmark import manifest


def test_cell_rehearses_on_the_cpu_to_its_end(tmp_path):
    # one CPU device, as a run of the command by hand has: the test
    # tree's eight virtual ones are not the benchmark's to count
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    log = tmp_path / "log.json"
    out = subprocess.run(
        [sys.executable, "benchmark/tools/run_with_log.py", str(log),
         "--workload", "qwen3next_ep16_seq8k", "--seed", str(2 ** 31 + 13),
         "--seconds", "2", "--trace", "1", "--rehearse-cpu"],
        cwd=manifest.ROOT, capture_output=True, text=True, timeout=600,
        env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    checks = line["checks"]
    assert checks["losses_finite"] and checks["matches_reference"] \
        and checks["no_call_failed"] and checks["loss_fell"]
    assert line["rehearsal"] and not line["correct"] and not line["metrics"]
    spans = {s["name"]: s["attrs"]
             for s in json.loads(log.read_text())[-1]["spans"]}
    facts, counted = spans["train.dispatch"], spans["train.sync"]
    assert (facts["delta_layers"], facts["delta_chunks"],
            facts["delta_heads"], facts["delta_heads_paired"],
            facts["attention_heads_full"],
            facts["rope_dim"]) == (3, 3 * 2 * (128 // 64), 4, 4, 4, 8)
    steps = counted["moe_steps"]
    assert counted["delta_beta_count"] == steps * 2 * 128 * 3 * 4
    assert counted["attn_gate_count_full"] == steps * 2 * 128 * 4 * 32
    assert counted["shared_gate_count"] == steps * 2 * 128 * 4
    for name in ("delta_beta", "shared_gate"):
        assert 0.4 < counted[name + "_sum"] / counted[name + "_count"] < 0.6
    assert 0.4 < counted["attn_gate_sum_full"] \
        / counted["attn_gate_count_full"] < 0.6
    assert counted["delta_log_decay_min"] < -1.0
    assert counted["moe_rows_filled"] == counted["moe_assignments_held"]
