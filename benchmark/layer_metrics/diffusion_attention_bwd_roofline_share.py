"""Kernels: how near the ``flash_bwd_fused`` operations run to the
chip's roofline under the block-diffusion mask —
``diffusion_attention_fwd_roofline_share``'s reckoning for the backward
kernel: FLOPs inside the mask, 10 x 128 a score (five products), bytes
q, do, dq (query heads), k, v, dk, dv (key/value heads), lse and delta
once a call, from ``families/sdar.py::diffusion_attention_flops_bytes``."""

from benchmark.layer_metrics.diffusion_attention_fwd_roofline_share import share


def read(host, trace):
    return share(host, trace, "flash_bwd_fused", "bwd")
