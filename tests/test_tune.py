"""Tune tests (reference idiom: python/ray/tune/tests/test_trial_runner*,
test_api.py — grid search correctness, early stopping, checkpointing,
function API, PBT perturbation)."""

import numpy as np
import pytest

import ray_tpu
from ray_tpu import tune
from ray_tpu.tune.schedulers import (
    ASHAScheduler,
    MedianStoppingRule,
    PopulationBasedTraining,
)
from ray_tpu.tune.search.basic_variant import generate_variants


def test_generate_variants_grid_and_sample():
    import random

    config = {
        "lr": tune.grid_search([0.1, 0.01]),
        "wd": tune.uniform(0, 1),
        "nested": {"units": tune.grid_search([32, 64])},
        "fixed": 7,
    }
    out = list(generate_variants(config, random.Random(0)))
    assert len(out) == 4
    assert {(v["lr"], v["nested"]["units"]) for v in out} == {
        (0.1, 32), (0.1, 64), (0.01, 32), (0.01, 64)}
    assert all(0 <= v["wd"] <= 1 and v["fixed"] == 7 for v in out)


class Quadratic(tune.Trainable):
    """score climbs toward -(x-3)^2; best config is x=3."""

    def setup(self, config):
        self.x = config["x"]
        self.score = -100.0

    def step(self):
        target = -((self.x - 3) ** 2)
        self.score = self.score + 0.5 * (target - self.score)
        return {"score": self.score}

    def save_checkpoint(self, d):
        return {"score": self.score}

    def load_checkpoint(self, state):
        self.score = state["score"]


def test_grid_search_finds_best(ray_start_shared):
    analysis = tune.run(
        Quadratic,
        config={"x": tune.grid_search([1, 3, 5])},
        stop={"training_iteration": 5},
        metric="score", mode="max")
    assert len(analysis.trials) == 3
    assert analysis.best_config["x"] == 3
    assert analysis.best_result["score"] == pytest.approx(-3.125)


def test_function_api_generator(ray_start_shared):
    def trainable(config):
        acc = 0.0
        for _ in range(5):
            acc += config["lr"]
            yield {"acc": acc}

    analysis = tune.run(
        trainable,
        config={"lr": tune.grid_search([0.1, 0.3])},
        metric="acc", mode="max")
    assert analysis.best_config["lr"] == 0.3
    assert analysis.best_result["acc"] == pytest.approx(1.5)


class GatedQuadratic(Quadratic):
    """Quadratic whose hopeless trials (x far from 3) take their first
    step only after every good one has begun its last (`horizon`): the
    runner asks a trial for step k+1 only once it has handled result k,
    so by then the scheduler holds all but the last result of each good
    trial — what a bad trial is judged against no longer depends on which
    actor the box happened to run first. `gate` is a directory the good
    trials leave a marker in; `good` says how many to wait for."""

    def setup(self, config):
        super().setup(config)
        self.gate = config["gate"]
        self.good = config["good"]
        self.horizon = config["horizon"]
        self.steps = 0

    def step(self):
        import os
        import time

        self.steps += 1
        if abs(self.x - 3) >= 1:
            deadline = time.monotonic() + 120
            while (self.steps == 1
                   and len(os.listdir(self.gate)) < self.good
                   and time.monotonic() < deadline):
                time.sleep(0.02)
        elif self.steps == self.horizon:
            open(os.path.join(self.gate, f"good-{self.x}"), "w").close()
        return super().step()


def test_asha_stops_bad_trials_early(ray_start_shared, tmp_path):
    analysis = tune.run(
        GatedQuadratic,
        config={"x": tune.grid_search([3, 30, 40, 50]),
                "gate": str(tmp_path), "good": 1, "horizon": 20},
        stop={"training_iteration": 20},
        scheduler=ASHAScheduler(metric="score", mode="max",
                                grace_period=2, reduction_factor=2,
                                max_t=20),
        metric="score", mode="max")
    assert analysis.best_config["x"] == 3
    iters = {t.config["x"]: t.iteration for t in analysis.trials}
    # the good trial met every rung (2, 4, 8, 16) first and ran to the
    # horizon; each hopeless one is cut at the first rung where the good
    # one's record is in the top half above it: rung 2, or rung 4 for
    # the one that arrives at rung 2 as the best of the rest
    assert iters[3] == 20
    assert sorted(iters[x] for x in (30, 40, 50))[:2] == [2, 2]
    assert max(iters[30], iters[40], iters[50]) <= 4


def test_median_stopping(ray_start_shared, tmp_path):
    grace, horizon = 3, 12
    analysis = tune.run(
        GatedQuadratic,
        config={"x": tune.grid_search([3, 3.1, 2.9, 50]),
                "gate": str(tmp_path), "good": 3, "horizon": horizon},
        stop={"training_iteration": horizon},
        scheduler=MedianStoppingRule(metric="score", mode="max",
                                     grace_period=grace),
        metric="score", mode="max")
    iters = {t.config["x"]: t.iteration for t in analysis.trials}
    # the bad trial is cut at the first result past its grace period,
    # when three peers' running means at that step exist to judge it by;
    # no peer is cut (each one's latest score beats the others' means)
    assert iters[50] == grace
    assert [iters[x] for x in (3, 3.1, 2.9)] == [horizon] * 3


def test_pbt_perturbs_and_improves(ray_start_shared, tmp_path):
    class Gated(tune.Trainable):
        """PBT needs a coexisting population, and gets it from two gates
        in `gate` (a directory) instead of from step times: nobody takes
        a second step before all four have taken their first, and the
        good trials take their tenth (the one after a checkpoint, so a
        donor is never asked to save while it waits) only once the
        hopeless one has been restored from a donor. Which actor the box
        runs first decides nothing."""

        def setup(self, config):
            self.level = 0.0
            self.steps = 0
            self.gate = config["gate"]
            self.hopeless = config["rate"] < 0.1

        def _wait_for(self, prefix, n):
            import os
            import time

            deadline = time.monotonic() + 120
            while (sum(f.startswith(prefix) for f in os.listdir(self.gate))
                   < n and time.monotonic() < deadline):
                time.sleep(0.02)

        def _mark(self, name):
            import os

            open(os.path.join(self.gate, name), "w").close()

        def step(self):
            self.steps += 1
            if self.steps == 1:
                self._mark(f"started-{self.config['rate']}")
            elif self.steps == 2:
                self._wait_for("started-", 4)
            elif self.steps == 10 and not self.hopeless:
                self._wait_for("restored", 1)
            self.level += self.config["rate"]
            return {"level": self.level}

        def save_checkpoint(self, d):
            return {"level": self.level}

        def load_checkpoint(self, state):
            self.level = state["level"]
            self._mark("restored")

        def reset_config(self, new_config):
            return True

    pbt = PopulationBasedTraining(
        metric="level", mode="max", perturbation_interval=3,
        hyperparam_mutations={"rate": tune.uniform(0.1, 1.0)}, seed=0)
    analysis = tune.run(
        Gated,
        config={"rate": tune.grid_search([0.01, 0.8, 0.9, 1.0]),
                "gate": str(tmp_path)},
        stop={"training_iteration": 12},
        scheduler=pbt, checkpoint_freq=3,
        metric="level", mode="max")
    assert pbt.perturbations >= 1
    # the loser adopted a winner's configuration, explored (x0.8 at the
    # least, or drawn anew from 0.1 up), and its level with it
    rates = sorted(t.config["rate"] for t in analysis.trials)
    assert rates[0] >= 0.1
    assert min(t.last_result["level"] for t in analysis.trials) > 0.8


def test_trial_failure_raises(ray_start_shared):
    class Exploder(tune.Trainable):
        def step(self):
            raise RuntimeError("boom")

    with pytest.raises(RuntimeError):
        tune.run(Exploder, config={}, metric="x", mode="max")

    analysis = tune.run(Exploder, config={}, metric="x", mode="max",
                        raise_on_failed_trial=False)
    assert analysis.trials[0].status == "ERROR"
    assert "boom" in analysis.trials[0].error


def test_checkpoint_roundtrip_pause_resume(ray_start_shared):
    from ray_tpu.tune.schedulers.scheduler import TrialScheduler

    class PauseOnce(TrialScheduler):
        def __init__(self):
            self.paused = set()

        def on_trial_result(self, runner, trial, result):
            if trial.iteration == 3 and trial.trial_id not in self.paused:
                self.paused.add(trial.trial_id)
                return self.PAUSE
            return self.CONTINUE

    analysis = tune.run(
        Quadratic,
        config={"x": 3},
        stop={"training_iteration": 6},
        scheduler=PauseOnce(),
        metric="score", mode="max")
    trial = analysis.trials[0]
    # score monotonicity across the pause proves state survived the restart
    scores = [r["score"] for r in trial.results]
    assert trial.iteration == 6
    assert scores == sorted(scores)
