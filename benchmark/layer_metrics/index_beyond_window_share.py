"""Operator: what a sliding window could not have — of the pairs the
indexer selected, those whose key lies ``topk`` positions or more before
the query (``index_pairs_beyond_window`` over ``index_pairs_selected``
on a call's ``train.sync`` span), median over the window's calls, in
percent. A window of 2048 keys holds none of them; zero would mean the
cell exercises nothing a window cell lacks. A program whose spans carry
no such counters gives None."""

from benchmark.layer_metrics.index_selected_share import ratio


def read(host, trace):
    return ratio(host, "index_pairs_beyond_window", "index_pairs_selected")
