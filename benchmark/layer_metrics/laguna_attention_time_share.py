"""Kernels: device time in the attention kernels of a cell whose
attention layers are of two kinds with head counts of their own (48
full, 64 under a window of 512, 8 key/value heads of 128) — the
``flash_fwd.N`` operations (each layer's forward pass and its
rematerialised copy) and the ``flash_bwd_fused.N`` ones (its backward),
both kinds' calls together, own time over device busy time, in the
traced steps. A program whose trace names neither gives None."""

from benchmark.layer_metrics.latent_attention_time_share import read  # noqa: F401
