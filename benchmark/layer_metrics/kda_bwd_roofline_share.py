"""Kernels: how near the ``kda_bwd`` operations run to the chip's
roofline — ``kda_fwd_roofline_share``'s reckoning for the backward
kernel: FLOPs the products the gradients need beyond the forward's own
values (six with the state's shape, four with the chunk's, four with the
pairs': what the kernel recomputes is not counted), bytes q, k, v, do,
dq, dk, dv, the sums, beta and their gradients and the chunks' entering
states once a call, from ``families/kimi_linear.py::kda_flops_bytes``."""

from benchmark.layer_metrics.kda_fwd_roofline_share import share


def read(host, trace):
    return share(host, trace, "kda_bwd", "bwd")
