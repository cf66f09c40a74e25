"""RL throughput benchmark: env-steps/s THROUGH the framework
(north-star metric #2, BASELINE.json "RLlib PPO Atari env-steps/s";
reference context: rllib claims ~30k transitions/s for IMPALA at 32
workers + GPU learner, doc/source/rllib-algorithms.rst:160, and the
release PPO regression logs, release/release_logs/1.2.0/
rllib_regression_tf.txt).

This box has CPU CartPole vector envs, so the absolute numbers measure a
different machine class than the reference's Atari+GPU rigs — the
artifact exists so every round records the framework's sampling+learning
pipeline rate under the SAME workload, with run metadata for cross-round
provenance. Results are written like MICROBENCH.json.

Usage: python -m ray_tpu.rlbench [--out <file>.json] [--seconds 20]
"""

from __future__ import annotations

import json
import time

from ray_tpu._private.bench_meta import run_metadata as _metadata


def bench_ppo(seconds: float) -> dict:
    """Synchronous PPO: sample (2 workers x 2 envs) -> SGD epochs.
    Every sampled step is trained, so one rate describes both."""
    from ray_tpu.rllib.agents.ppo import PPOTrainer

    trainer = PPOTrainer(config={
        "env": "CartPole-v1",
        "num_workers": 2,
        "num_envs_per_worker": 2,
        "rollout_fragment_length": 128,
        "train_batch_size": 1024,
        "sgd_minibatch_size": 256,
        "num_sgd_iter": 8,
        "seed": 0,
    })
    trainer.step()  # compile + warmup
    sampled = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        m = trainer.step()
        sampled += m.get("num_env_steps_trained", 0)
    wall = time.perf_counter() - t0
    trainer.cleanup()
    return {
        "name": "ppo_cartpole_env_steps",
        "env": "CartPole-v1",
        "per_second": round(sampled / wall, 1),
        "env_steps": sampled,
        "wall_s": round(wall, 2),
        "learner_utilization": 1.0,  # sync: every sampled step trains
    }


def bench_impala(seconds: float) -> dict:
    """Async IMPALA: actors sample while the LearnerThread consumes;
    utilization = trained/sampled (1.0 = learner keeps up; the reference
    reports the same two counters)."""
    from ray_tpu.rllib.agents.impala import ImpalaTrainer

    trainer = ImpalaTrainer(config={
        "env": "CartPole-v1",
        "num_workers": 2,
        "num_envs_per_worker": 2,
        "rollout_fragment_length": 80,
        "train_batch_size": 800,
        "seed": 0,
    })
    trainer.step()  # compile + warmup
    base_sampled = trainer._sampled
    base_trained = trainer._learner.num_steps_trained
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        trainer.step()
    wall = time.perf_counter() - t0
    sampled = trainer._sampled - base_sampled
    trained = trainer._learner.num_steps_trained - base_trained
    trainer.cleanup()
    return {
        "name": "impala_cartpole_env_steps",
        "env": "CartPole-v1",
        "per_second": round(sampled / wall, 1),
        "trained_per_second": round(trained / wall, 1),
        "env_steps": sampled,
        "wall_s": round(wall, 2),
        "learner_utilization": round(trained / max(sampled, 1), 3),
    }


def main(seconds: float = 20.0) -> dict:
    import ray_tpu

    # logical CPUs: the trainers place 2 rollout workers + a learner;
    # on a 1-core box autodetection would leave workers unschedulable
    # (they timeshare either way — this benchmark measures pipeline
    # rate, not core scaling)
    ray_tpu.init(num_cpus=8)
    try:
        results = [bench_ppo(seconds), bench_impala(seconds)]
    finally:
        ray_tpu.shutdown()
    doc = {
        "metadata": _metadata(),
        "reference_context": (
            "reference IMPALA ~30k env-steps/s at 32 workers + V100 "
            "learner on Atari (rllib-algorithms.rst:160); this artifact "
            "runs CPU CartPole on one shared box — compare across "
            "rounds, not across machine classes"),
        "results": results,
    }
    for r in results:
        print(f"{r['name']} per second {r['per_second']}")
    return doc


if __name__ == "__main__":
    import argparse

    parser = argparse.ArgumentParser()
    parser.add_argument("--out", default=None)
    parser.add_argument("--seconds", type=float, default=20.0)
    args = parser.parse_args()
    doc = main(args.seconds)
    print(json.dumps(doc))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=1)
