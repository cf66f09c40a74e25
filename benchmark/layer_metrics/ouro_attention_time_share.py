"""Kernels: device time in the attention kernels of a looped cell whose
every mixer is plain causal attention at heads of 128 — the
``flash_fwd.N`` operations (each block's forward pass and its
rematerialised copy, every walk's) and the ``flash_bwd_fused.N`` ones
(its backward), own time over device busy time, in the traced steps. A
program whose trace names neither gives None."""

from benchmark.layer_metrics.latent_attention_time_share import read  # noqa: F401
