"""Object plane: the worker's ``object.return_put`` spans under the call's
``train.snapshot`` — serialise, copy into the arena, seal; seconds, median
over the window's calls (``benchmark/span_log.py``)."""

from benchmark import span_log


def read(host, trace):
    return span_log.window_median(host, "put_s")
