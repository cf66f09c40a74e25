"""Operator: whether the later walks earn their FLOPs — the last walk's
summed cross-entropy over the first walk's (``loop_nll_T`` over
``loop_nll_1`` on a call's ``train.sync`` span, T the walks the span
counts), median over the window's calls: 1 where a further walk predicts
no better than the first, lower where it does. A program whose spans
carry no such counters gives None."""

from benchmark.layer_metrics.loop_exit_entropy_share import median_of, walks


def read(host, trace):
    return median_of(
        host, lambda a: a[f"loop_nll_{walks(a)}"] / a["loop_nll_1"])
