"""Kernels: how near the ``flash_bwd_fused`` operations of the latent
mixer run to the chip's roofline — ``latent_attention_fwd_roofline_
share``'s reckoning for the backward kernel: FLOPs inside the causal
mask, 2 x (3 x 192 + 2 x 128) a score (five products), bytes q, k, dq,
dk (192 wide), v, do, dv (128), lse and delta once a call, from
``families/joyai.py::latent_attention_flops_bytes``."""

from benchmark.layer_metrics.latent_attention_fwd_roofline_share import share


def read(host, trace):
    return share(host, trace, "flash_bwd_fused", "bwd")
