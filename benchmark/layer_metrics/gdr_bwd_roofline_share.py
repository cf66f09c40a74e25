"""Kernels: how near the ``gdr_bwd`` operations run to the chip's
roofline — ``gdr_fwd_roofline_share``'s reckoning for the backward
kernel: FLOPs the ten products a value head and four a key head that
the gradients need beyond the forward's own values (what the kernel
recomputes — K S, Q S, the inverse, V' — is not counted), bytes q, k, v,
do, dq, dk, dv, the sums, beta and their gradients and the chunks'
entering states once a call, from ``families/qwen3_next.py::
gated_delta_flops_bytes``."""

from benchmark.layer_metrics.gdr_fwd_roofline_share import share


def read(host, trace):
    return share(host, trace, "gdr_bwd", "bwd")
