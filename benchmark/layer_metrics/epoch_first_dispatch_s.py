"""Operator: ``first_dispatch_s`` on ``train.dispatch`` — the seconds of an
epoch's first step dispatch, loop entry to the first ``_dispatch_batch``
returned, where jax's deferred garbage of the pull before lands; median
over the window's calls (``benchmark/span_log.py``). A program whose
span does not carry it gives None."""

import statistics

from benchmark import span_log


def read(host, trace):
    entries = span_log.window_entries(host)
    if not entries:
        return None
    try:
        return statistics.median(
            s["attrs"]["first_dispatch_s"] for e in entries
            for s in e["spans"] if s["name"] == "train.dispatch")
    except (KeyError, statistics.StatisticsError):
        return None
