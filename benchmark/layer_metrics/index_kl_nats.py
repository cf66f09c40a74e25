"""Operator: the indexer's loss — the KL divergence of the main
attention's head-averaged probabilities over a query's selected keys
from the softmax of its index scores over them, in nats a query, mean
over rows, layers and the call's steps (``index_kl_sum`` over
``index_kl_count`` on a call's ``train.sync`` span), median over the
window's calls. It falls as the indexer learns the attention it steers.
A program whose spans carry no such counters gives None."""

from benchmark.layer_metrics.index_selected_share import ratio


def read(host, trace):
    return ratio(host, "index_kl_sum", "index_kl_count", scale=1.0)
