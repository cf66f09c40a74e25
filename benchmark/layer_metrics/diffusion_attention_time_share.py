"""Kernels: device time in the attention kernels of a cell trained by
block diffusion — the ``flash_fwd.N`` operations (each block's forward
pass over the ``[clean ; noised]`` rows and its rematerialised copy) and
the ``flash_bwd_fused.N`` ones (its backward), own time over device busy
time, in the traced steps. The block-diffusion mask keeps the kernels'
``name=``s: the mask is in the program, not in the name. A program whose
trace names neither gives None."""

from benchmark.layer_metrics.latent_attention_time_share import read  # noqa: F401
