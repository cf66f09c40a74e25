"""Operator: that the delta rule's write strength is computed and has
not saturated — ``delta_beta_sum`` over ``delta_beta_count`` on a call's
``train.sync`` span (the mean of beta = sigmoid(b) over every token,
value head and delta layer of the call's steps), median over the
window's calls, in percent. 50 at seeded weights; a beta that saturates
reads toward 0 or 100, one left out of the program leaves no counter. A
program whose spans carry no such counters gives None."""

import statistics

from benchmark import span_log


def share_of(host, total: str, count: str):
    """The median over the window's calls of 100 x `total` / `count` on
    the calls' ``train.sync`` spans; None where no span has them."""
    shares = []
    for entry in span_log.window_entries(host) or []:
        for span in entry["spans"]:
            attrs = span["attrs"]
            if span["name"] == "train.sync" and attrs.get(count):
                shares.append(100.0 * attrs.get(total, 0) / attrs[count])
    return statistics.median(shares) if shares else None


def read(host, trace):
    return share_of(host, "delta_beta_sum", "delta_beta_count")
