"""Kernels: how near the ``flash_bwd_fused`` operations of the plain
causal path run to the chip's roofline at heads of 128 —
``ouro_attention_fwd_roofline_share``'s reckoning for the backward
kernel: FLOPs inside the causal mask, 10 x 128 a score (five products),
bytes q, do, dq, k, v, dk, dv, lse and delta once a call, from
``families/ouro.py::attention_flops_bytes``."""

from benchmark.layer_metrics.ouro_attention_fwd_roofline_share import share


def read(host, trace):
    return share(host, trace, "flash_bwd_fused", "bwd")
