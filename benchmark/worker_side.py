"""The benchmark's operator: what runs inside the chip-owning actor.

A ``TrainingOperator`` subclass built from data (the cell's family,
configuration and workload files), plus the counters only that process
can read. It overrides public methods only (``setup``, ``train_epoch``,
``validate``); the training loop is the program's own ``train_epoch``:
no sync is added inside an epoch.

Copied from ``chip_smoke._Measured`` and ``bench._operator_cls`` (PR 22),
without the smoke's step-by-step timing loop."""

from __future__ import annotations

import importlib

from benchmark.common import Repeat, key_seed


def _held(stats) -> int | None:
    """HBM one chip has held at most, as far as its runtime tells: the
    peak of live buffers, or what is live now plus the runtime's
    reservation for programs' temporaries, whichever is more. (On a TPU
    `peak_bytes_in_use` leaves the temporaries out — they sit under
    `bytes_reserved`, which stays at the largest program's need: my
    chip probe, PR 24.) None where the backend keeps no count."""
    if not stats or stats.get("peak_bytes_in_use") is None:
        return None
    return max(stats["peak_bytes_in_use"],
               stats.get("bytes_in_use", 0) + stats.get("bytes_reserved", 0))


def operator_cls():
    from ray_tpu.train import TrainingOperator

    class BenchOperator(TrainingOperator):
        def __init__(self, *args, **kwargs):
            import jax

            self.bench_programs = 0   # compiled, or loaded from a cache
            self.bench_cache = {"hits": 0, "misses": 0}

            def count(event, **_):
                if event == "/jax/compilation_cache/cache_hits":
                    self.bench_cache["hits"] += 1
                elif event == "/jax/compilation_cache/cache_misses":
                    self.bench_cache["misses"] += 1

            def count_program(event, duration, **_):
                # JAX times the compile-or-load of every new program
                if event == "/jax/core/compile/backend_compile_duration":
                    self.bench_programs += 1

            # before setup(): the first program is the model's init
            jax.monitoring.register_event_listener(count)
            jax.monitoring.register_event_duration_secs_listener(
                count_program)
            super().__init__(*args, **kwargs)

        def setup(self, config):
            model = config["model"]
            family = importlib.import_module(
                "benchmark.families." + model["family"])
            self.bench_pieces = family.pieces(
                model, config["workload"], config["seed"])
            p = self.bench_pieces
            self.register(model_init=p.model_init, loss_fn=p.loss_fn,
                          optimizer=p.optimizer, stateful=p.stateful,
                          seed=key_seed(config["seed"]))
            self.register_data(train_loader=Repeat(p.batch))

        def train_epoch(self, num_steps=None, profile_dir=None):
            import jax

            programs = self.bench_programs
            out = super().train_epoch(num_steps, profile_dir=profile_dir)
            out["programs_built"] = self.bench_programs - programs
            dev = jax.devices()[0]
            out["device"] = {
                "platform": dev.platform, "kind": dev.device_kind,
                "count": jax.device_count(),
                "memory_peak_bytes": max(
                    (_held(d.memory_stats()) for d in jax.local_devices()),
                    default=None)}
            out["jax_cache"] = dict(self.bench_cache)
            return out

        def validate(self, num_steps=None):
            """The plain reference's loss at the seeded initial
            parameters on the cell's batch. The parameters are made
            again from the seed (the training state has moved on)."""
            import jax

            p, model = self.bench_pieces, self.config["model"]
            reference = importlib.import_module(
                f"benchmark.families.{model['family']}_reference")
            init = p.model_init(
                jax.random.key(key_seed(self.config["seed"])))
            return {"reference_loss": reference.loss(init, p.batch, model),
                    "num_samples": p.rows}

    return BenchOperator
