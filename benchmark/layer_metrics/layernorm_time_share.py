"""Kernels: device time in the layernorm forward kernel — the Mosaic
operations whose ``XLA Ops`` event name carries the kernel's ``name=``
(``layernorm.N``, ``jvp_layernorm_.N``) — over device busy time, in the
traced steps. With ``flash_fwd_time_share`` it adds up to
``mosaic_time_share``."""

from benchmark.layer_metrics.flash_fwd_time_share import share


def read(host, trace):
    return share(trace, "layernorm")
