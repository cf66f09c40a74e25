"""Kernels: how near the ``flash_bwd_fused`` operations run to the
chip's roofline in the same cell —
``laguna_attention_fwd_roofline_share``'s reckoning for the backward
kernel: FLOPs inside each kind's mask, 10 x 128 a score (five
products), bytes q, do, dq with the kind's query heads, k, v, dk, dv
with the 8 key/value heads, lse and delta once a call, from
``families/laguna.py::attention_flops_bytes``. A window of 512 is one
512 x 512 backward tile: every key block is walked against two query
blocks."""

from benchmark.layer_metrics.laguna_attention_fwd_roofline_share import share


def read(host, trace):
    return share(host, trace, "flash_bwd_fused", "bwd")
