"""Kernels: device time in the attention kernels of the cell's ONE
attention block (32 query heads on 2 key/value heads of 128, causal, no
window, no positions) — the ``flash_fwd.N`` operations (the block's
forward pass and its rematerialised copy) and the ``flash_bwd_fused.N``
one (its backward, 16 query heads a key/value head summed in VMEM), own
time over device busy time, in the traced steps:
``latent_attention_time_share``'s reading of the same two kernel names,
under a name of its own because that metric's entry lists its cell."""

from benchmark.layer_metrics.latent_attention_time_share import read  # noqa: F401
