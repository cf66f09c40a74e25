"""Kernels: how near the ``flash_bwd_fused`` operations of the one
latent layer run to the chip's roofline —
``kimilinear_attention_fwd_roofline_share``'s reckoning for the backward
kernel: 2 x (3 x 192 + 2 x 128) a score inside the causal mask, bytes q,
k, dq, dk (192), v, do, dv (128), lse and delta once a call."""

from benchmark.layer_metrics.latent_attention_bwd_roofline_share import read  # noqa: F401
