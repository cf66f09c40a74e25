"""Kernels: device time in the attention kernels of a cell whose
attention runs over a learned selection — the ``flash_fwd.N`` operations
(each block's forward pass and its rematerialised copy) and the
``flash_bwd_fused.N`` ones (its backward), own time over device busy
time, in the traced steps. The selection keeps the kernels' ``name=``s:
the plane is an input, not a name. A program whose trace names neither
gives None."""

from benchmark.layer_metrics.latent_attention_time_share import read  # noqa: F401
