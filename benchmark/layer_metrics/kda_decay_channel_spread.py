"""Operator: that the decay really differs over a head's channels —
``kda_decay_spread_sum`` over ``kda_decay_spread_count`` on a call's
``train.sync`` span (a chunk's sum of the log decay, its largest over a
head's 128 channels minus its least, averaged over chunks, heads, KDA
layers and the call's steps), median over the window's calls, in nats.
What a decay a head (``gdr_*``'s rule) cannot have: zero would mean the
cell exercises nothing the scalar rule lacks. A program whose spans
carry no such counters gives None."""

from benchmark.layer_metrics.gdr_write_strength_share import share_of


def read(host, trace):
    percent = share_of(host, "kda_decay_spread_sum", "kda_decay_spread_count")
    return None if percent is None else percent / 100.0
