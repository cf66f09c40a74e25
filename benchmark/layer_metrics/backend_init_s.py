"""Device: the span ``train.setup.backend`` of the run's worker start
(``benchmark/start_log.py``) — the worker's first ``jax.devices()``:
libtpu's initialisation; seconds."""

from benchmark import start_log


def read(host, trace):
    return start_log.span_seconds(start_log.start_entry(host),
                                  "train.setup.backend")
