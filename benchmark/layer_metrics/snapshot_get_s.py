"""Object plane: the driver's ``object.get`` spans under the call's
``train.snapshot`` — map and deserialise, after the reply has come; seconds, median
over the window's calls (``benchmark/span_log.py``)."""

from benchmark import span_log


def read(host, trace):
    return span_log.window_median(host, "get_s")
