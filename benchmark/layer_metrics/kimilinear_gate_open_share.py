"""Operator: that the KDA mixer's output gate is computed and has not
shut — ``kda_gate_sum`` over ``kda_gate_count`` on a call's
``train.sync`` span (the mean of sigmoid(gate) over every token, value
channel and KDA layer of the call's steps), median over the window's
calls, in percent. 50 at seeded weights. A program whose spans carry no
such counters gives None."""

from benchmark.layer_metrics.gdr_write_strength_share import share_of


def read(host, trace):
    return share_of(host, "kda_gate_sum", "kda_gate_count")
