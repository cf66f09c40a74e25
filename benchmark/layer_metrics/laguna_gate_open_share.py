"""Operator: that the attention's per-head output gate is computed and
has not shut — the gates' sum over their count, both kinds together
(``attn_gate_sum_full`` + ``attn_gate_sum_window`` over
``attn_gate_count_full`` + ``attn_gate_count_window`` on a call's
``train.sync`` span: the mean of sigmoid(x . w_g) over every token, head
and attention layer of the call's steps), median over the window's
calls, in percent. 50 at seeded weights; a gate that shuts reads toward
0, one left out of the program leaves no counter. A program whose spans
carry no such counters gives None."""

import statistics

from benchmark import span_log

KINDS = ("full", "window")


def read(host, trace):
    shares = []
    for entry in span_log.window_entries(host) or []:
        for span in entry["spans"]:
            attrs = span["attrs"]
            count = sum(attrs.get(f"attn_gate_count_{k}", 0) for k in KINDS)
            if span["name"] == "train.sync" and count:
                shares.append(100.0 * sum(
                    attrs.get(f"attn_gate_sum_{k}", 0) for k in KINDS) / count)
    return statistics.median(shares) if shares else None
