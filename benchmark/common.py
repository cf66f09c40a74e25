"""What every family file needs and none should copy: the seed folded
to what ``jax.random.key`` takes, the optimizer named in a configuration
file, and the loader that repeats one device-resident batch."""

from __future__ import annotations

import dataclasses
from typing import Any, Callable


def key_seed(seed: int) -> int:
    """``--seed`` may be a little over 2**31; a PRNG key takes 32 signed
    bits. The same seed gives the same key."""
    return int(seed) % (2 ** 31 - 1)


def make_optimizer(spec: dict):
    import optax

    name = spec["name"]
    if name == "adamw":
        return optax.adamw(spec["learning_rate"])
    if name == "sgd":
        return optax.sgd(spec["learning_rate"],
                         momentum=spec.get("momentum"))
    raise ValueError(f"unknown optimizer {name!r} in the configuration")


@dataclasses.dataclass
class Pieces:
    """What a family hands the benchmark's operator."""
    model_init: Callable      # key -> params, or (params, state)
    loss_fn: Callable         # as TrainingOperator.register takes it
    optimizer: Any
    batch: Any                # one device-resident batch, from the seed
    stateful: bool
    rows: int                 # samples in the batch


class Repeat:
    """Synthetic loader: the same device-resident batch, for as many
    steps as the epoch asks (every cell passes ``num_steps``)."""

    def __init__(self, batch):
        self.batch = batch

    def __iter__(self):
        while True:
            yield self.batch
