"""Operator: the bytes one snapshot moves from the device to the host —
`bytes` on the call's ``train.snapshot.d2h`` span, in GiB, median over
the window's calls. With it each ``snapshot_*_s`` is a GB/s."""

from benchmark import span_log


def read(host, trace):
    size = span_log.window_median(host, "bytes")
    return None if size is None else size / 2**30
