"""Operator: the spans ``train.setup.init`` + ``train.setup.place`` of the
run's worker start (``benchmark/start_log.py``) — ``model_init`` until
the device has made the weights, then their placement, the optimizer's
state and the steps' construction until the device holds the whole
training state; seconds."""

from benchmark import start_log


def read(host, trace):
    return start_log.span_seconds(start_log.start_entry(host),
                                  "train.setup.init", "train.setup.place")
