"""Kernels: device time in the convolution in front of a recurrent rule
(the state-space, gated-delta and KDA mixers' taps, bias, SiLU and unit
norms as one operator) — the Mosaic operations whose ``XLA Ops`` event
name carries the kernels' ``name=`` (``mixer_conv.N``: the forward pass
and its rematerialised copy; ``mixer_conv_bwd.N``: the backward) — own
time over device busy time, in the traced steps. A program that leaves
the convolution to XLA names no such operation: nothing to read."""

from benchmark.layer_metrics import flash_fwd_time_share

KERNEL = "mixer_conv"        # in ``mixer_conv_bwd`` too


def read(host, trace):
    return flash_fwd_time_share.share(trace, KERNEL)
