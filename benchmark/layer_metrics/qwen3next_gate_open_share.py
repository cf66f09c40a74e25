"""Operator: that the attention's ELEMENTWISE output gate is computed
and has not shut — ``attn_gate_sum_full`` over ``attn_gate_count_full``
on a call's ``train.sync`` span (the mean of sigmoid(gate) over every
token, head DIMENSION and attention layer of the call's steps: PR 55's
counters, which count elements here), median over the window's calls, in
percent. 50 at seeded weights. A program whose spans carry no such
counters gives None."""

from benchmark.layer_metrics.gdr_write_strength_share import share_of


def read(host, trace):
    return share_of(host, "attn_gate_sum_full", "attn_gate_count_full")
