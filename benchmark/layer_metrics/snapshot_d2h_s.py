"""Operator: device to host, leaf by leaf — the ``train.snapshot.d2h``
span of ``TrainingOperator.state_dict`` under the call's
``train.snapshot``; seconds, median over the window's calls
(``benchmark/span_log.py``)."""

from benchmark import span_log


def read(host, trace):
    return span_log.window_median(host, "d2h_s")
