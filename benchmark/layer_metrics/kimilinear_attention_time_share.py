"""Kernels: device time in the attention kernels of a cell where ONE
layer in five is latent attention without positions (192-wide queries
and keys, 128-wide values, 32 heads) — the ``flash_fwd.N`` operations
(the layer's forward pass and its rematerialised copy) and the
``flash_bwd_fused.N`` ones (its backward), own time over device busy
time, in the traced steps. A program whose trace names neither gives
None."""

from benchmark.layer_metrics.latent_attention_time_share import read  # noqa: F401
