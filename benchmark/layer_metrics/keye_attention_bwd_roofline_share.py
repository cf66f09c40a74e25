"""Kernels: how near the ``flash_bwd_fused`` operations run to the
chip's roofline under a learned selection —
``keye_attention_fwd_roofline_share``'s reckoning for the backward
kernel: FLOPs over the selected pairs, 10 x 128 a pair and head (five
products), bytes q, do, dq (query heads), k, v, dk, dv (key/value
heads), lse, delta and the plane once a call, from ``families/keye.py::
attention_flops_bytes``."""

from benchmark.layer_metrics.keye_attention_fwd_roofline_share import share


def read(host, trace):
    return share(host, trace, "flash_bwd_fused", "bwd")
