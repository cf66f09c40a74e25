"""Control plane: what is left of ``train.call`` after the worker's epoch
(``train.dispatch`` start to ``train.sync`` end) and the snapshot's four
leaf spans — actor hops, reply waits, ``_reduce``; seconds, median
over the window's calls (``benchmark/span_log.py``)."""

from benchmark import span_log


def read(host, trace):
    return span_log.window_median(host, "hop_s")
