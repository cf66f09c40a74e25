"""Operator: the operator's own epoch rate (its samples over its clock,
which the epoch closes on ``float(loss)``), median over the window's
calls."""

import statistics


def read(host, trace):
    if not host["calls"]:
        return None
    return statistics.median(c["worker_samples_per_s"]
                             for c in host["calls"])
