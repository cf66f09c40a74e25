"""HyperBand / PB2 / loggers / PG-backed trials (reference:
python/ray/tune/schedulers/hyperband.py, pb2.py, logger.py,
utils/placement_groups.py)."""

import json
import os

import pytest

from ray_tpu import tune
from ray_tpu.tune.schedulers import PB2, HyperBandScheduler


def _trainable(config):
    # Quality is the lr itself: higher lr -> higher score, so the culling
    # order is deterministic.
    for i in range(100):
        tune.report(score=config["lr"] * (i + 1), training_iteration=i + 1)


def test_hyperband_culls_bad_trials(ray_start_shared):
    scheduler = HyperBandScheduler(metric="score", mode="max", max_t=9,
                                   reduction_factor=3)
    analysis = tune.run(
        _trainable,
        config={"lr": tune.grid_search([1, 2, 3, 4, 5, 6])},
        metric="score",
        mode="max",
        scheduler=scheduler,
        max_concurrent_trials=3,
    )
    best = analysis.best_config["lr"]
    assert best == 6, f"hyperband kept the wrong trial: {best}"
    # at least one loser was culled before max_t
    iters = sorted(t.iteration for t in analysis.trials)
    assert iters[0] < 9, f"nothing was culled early: {iters}"


def test_pb2_perturbs_within_bounds(ray_start_shared, tmp_path):
    scheduler = PB2(metric="score", mode="max", perturbation_interval=2,
                    hyperparam_bounds={"lr": (1e-4, 1e-1)}, seed=0)

    def trainable(config):
        # PB2 exploits within a population that is alive TOGETHER: no
        # trial reports before all four have started (a trial restarted
        # by a perturbation finds the gate open), so a loaded box that
        # starts the actors one after another cannot run them in turn
        import time
        import uuid

        open(os.path.join(config["gate"], uuid.uuid4().hex), "w").close()
        deadline = time.monotonic() + 120
        while (len(os.listdir(config["gate"])) < 4
               and time.monotonic() < deadline):
            time.sleep(0.02)
        lr = config["lr"]
        for i in range(12):
            tune.report(score=lr * (i + 1), training_iteration=i + 1)

    analysis = tune.run(
        trainable,
        config={"lr": tune.loguniform(1e-4, 1e-1), "gate": str(tmp_path)},
        num_samples=4,
        metric="score",
        mode="max",
        scheduler=scheduler,
        max_concurrent_trials=4,
    )
    assert scheduler.perturbations >= 1, "PB2 never perturbed"
    for t in analysis.trials:
        assert 1e-4 - 1e-9 <= t.config["lr"] <= 1e-1 + 1e-9


def test_loggers_write_trial_files(ray_start_shared, tmp_path):
    def trainable(config):
        for i in range(3):
            tune.report(score=i, training_iteration=i + 1)

    analysis = tune.run(trainable, config={"x": 1}, num_samples=2,
                        metric="score", mode="max",
                        local_dir=str(tmp_path))
    for t in analysis.trials:
        tdir = tmp_path / t.trial_id
        assert (tdir / "progress.csv").exists()
        assert (tdir / "params.json").exists()
        lines = (tdir / "result.json").read_text().strip().splitlines()
        # 3 reports + the function-trainable's final done marker
        assert len(lines) >= 3
        last = json.loads(lines[-1])
        assert last["score"] == 2 and last["done"] is True


def test_pg_backed_trials(ray_start_shared):
    seen = []

    def trainable(config):
        tune.report(score=1, training_iteration=1)

    analysis = tune.run(
        trainable, config={}, num_samples=2, metric="score", mode="max",
        resources_per_trial=tune.PlacementGroupFactory(
            [{"CPU": 1}, {"CPU": 1}], strategy="PACK"),
        max_concurrent_trials=2)
    assert all(t.status == "TERMINATED" for t in analysis.trials)
    # groups are returned after the run: nothing left reserved (bundle
    # returns are async — poll until the resources settle)
    import time

    import ray_tpu

    total = ray_tpu.cluster_resources()
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        if ray_tpu.available_resources().get("CPU") == total.get("CPU"):
            break
        time.sleep(0.3)
    assert ray_tpu.available_resources().get("CPU") == total.get("CPU")


def test_cli_reporter_prints_table(ray_start_shared, capsys):
    import io

    buf = io.StringIO()
    reporter = tune.CLIReporter(metric_columns=["score"],
                                max_report_frequency=0.0, out=buf)

    def trainable(config):
        tune.report(score=42, training_iteration=1)

    tune.run(trainable, config={}, num_samples=1, metric="score",
             mode="max", progress_reporter=reporter)
    out = buf.getvalue()
    assert "tune status" in out and "TERMINATED" in out
