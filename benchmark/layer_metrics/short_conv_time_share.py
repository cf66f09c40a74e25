"""Kernels: device time in the gated short convolution of the conv
mixers — the Mosaic operations whose ``XLA Ops`` event name carries the
kernels' ``name=`` (``short_conv.N``: the forward pass and its
rematerialised copy; ``short_conv_bwd.N``: the backward) — over device
busy time, in the traced steps. A program without the kernels names no
such operation: nothing to read."""

from benchmark.layer_metrics import flash_fwd_time_share

KERNEL = "short_conv"


def read(host, trace):
    return flash_fwd_time_share.share(trace, KERNEL)
