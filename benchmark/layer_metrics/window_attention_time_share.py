"""Kernels: device time in the flash-attention forward kernel in a cell
whose layers mix window and full attention — the ``flash_fwd.N``
operations (every layer's forward pass and its rematerialised copy; the
kernel skips the key blocks outside a layer's window) over device busy
time, in the traced steps. The same reading as ``flash_fwd_time_share``,
listed for the cells where a window decides it."""

from benchmark.layer_metrics import flash_fwd_time_share


def read(host, trace):
    return flash_fwd_time_share.share(trace, "flash_fwd")
