"""Driver API: the copy of the snapshot out of the object store's arena —
the ``train.snapshot.copy`` span of ``trainer._own``; seconds, median
over the window's calls (``benchmark/span_log.py``)."""

from benchmark import span_log


def read(host, trace):
    return span_log.window_median(host, "copy_s")
