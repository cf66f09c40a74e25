"""Kernels: device time in the attention kernels of a cell whose every
mixer is latent attention (192-wide queries and keys, 128-wide values) —
the ``flash_fwd.N`` operations (each block's forward pass and its
rematerialised copy) and the ``flash_bwd_fused.N`` ones (its backward),
own time over device busy time, in the traced steps. The two-width call
keeps the kernels' ``name=``s: the widths are in the shapes. A program
whose trace names neither gives None."""

from benchmark.layer_metrics import expert_matmul_time_share as time_share

KERNELS = ("flash_fwd", "flash_bwd_fused")


def read(host, trace):
    own = [time_share.seconds(trace, k) for k in KERNELS]
    if None in own:
        return None
    return 100.0 * sum(own) / trace["busy_s"]
