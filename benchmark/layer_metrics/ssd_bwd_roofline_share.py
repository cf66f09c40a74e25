"""Kernels: how near the ``ssd_bwd`` operations run to the chip's
roofline — ``ssd_fwd_roofline_share``'s reckoning for the backward
kernel: FLOPs the two gradients of each of the forward's four products,
a head (what the kernel recomputes is not counted), bytes x, dy, dx, B,
C, dB, dC, the chunks' entering states, dt, dt A and their gradients and
D's partial sums once a call, from ``families/nemotron_h.py::
ssd_flops_bytes``."""

from benchmark.layer_metrics.ssd_fwd_roofline_share import share


def read(host, trace):
    return share(host, trace, "ssd_bwd", "bwd")
