"""Kernels: how near the ``flash_fwd`` operations of the one latent
layer run to the chip's roofline — ``latent_attention_fwd_roofline_
share``'s reckoning (FLOPs INSIDE the causal mask, 2 x (192 + 128) a
score; bytes q, k, v, o and the row log-sum-exp once a call) with the
counts of ``families/kimi_linear.py::latent_attention_flops_bytes`` (one
layer, not every block) for the steps the traced call really ran. A
program without the kernel or the span gives None."""

from benchmark.layer_metrics.latent_attention_fwd_roofline_share import read  # noqa: F401
