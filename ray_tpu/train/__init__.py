"""ray_tpu.train — distributed SGD training (the RaySGD equivalent;
reference: python/ray/util/sgd/)."""

from ray_tpu.train.ingest import DatasetShard, IngestSpec, IngestStream
from ray_tpu.train.operator import TrainingOperator
from ray_tpu.train.torch_operator import TorchTrainingOperator
from ray_tpu.train.trainer import (Trainer, TrainWorker, call_log,
                                   start_log)

__all__ = ["DatasetShard", "IngestSpec", "IngestStream",
           "TorchTrainingOperator", "Trainer", "TrainWorker",
           "TrainingOperator", "call_log", "start_log"]
