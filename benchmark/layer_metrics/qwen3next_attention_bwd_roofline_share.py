"""Kernels: how near the ``flash_bwd_fused`` operations run to the
chip's roofline at heads of 256 —
``qwen3next_attention_fwd_roofline_share``'s reckoning for the backward
kernel: FLOPs inside the causal mask, 10 x 256 a score (five products),
bytes q, do, dq with the 16 query heads, k, v, dk, dv with the 2
key/value heads, lse and delta once a call, from
``families/qwen3_next.py::attention_flops_bytes``."""

from benchmark.layer_metrics.qwen3next_attention_fwd_roofline_share import share


def read(host, trace):
    return share(host, trace, "flash_bwd_fused", "bwd")
