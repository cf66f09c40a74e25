"""Kernels: how near the ``mixer_conv`` / ``mixer_conv_bwd`` operations
run to the chip's roofline — max(operations over the bf16 peak, bytes
over the HBM peak of ``peaks.json``) over their traced time, in percent.
The bytes bound it: ``flops_bytes`` below counts every array a pass
reads or writes once — x in and the result out a forward, x and the
result's gradient in and dx out a backward; two forwards (the second
rematerialised) and one backward a recurrent mixer's layer — at the
widths of the cell's model file, for the steps the traced call really
ran (``steps`` on its ``train.dispatch`` span). The taps, the bias, the
partial sums of their gradients and a tile's 16-row halos (3 % of a tile
of 512 forward, 9 % backward) are left out: the share reads a little
low. A program without the kernels or the span gives None."""

import numpy as np

from benchmark.layer_metrics import expert_matmul_roofline_share as roofline
from benchmark.layer_metrics import expert_matmul_time_share as time_share
from benchmark.layer_metrics.mixer_conv_time_share import KERNEL
from benchmark.this_cell import this_cell, traced_call_attrs


def conv_channels(cfg) -> dict:
    """The channels each recurrent mixer kind's convolution runs over,
    from the decoder's configuration."""
    keys = cfg.delta_key_heads * cfg.delta_key_dim
    return {
        "ssm": cfg.ssm_heads * cfg.ssm_head_dim
        + 2 * cfg.ssm_groups * cfg.ssm_state,
        "delta": 2 * keys + cfg.delta_value_heads * cfg.delta_value_dim,
        "kda": 2 * keys + cfg.delta_key_heads * cfg.delta_value_dim}


def flops_bytes(cfg, workload: dict, steps: int) -> tuple[float, float]:
    """(operations, bytes) of the operator's calls over `steps` steps:
    2 + 2 + 3 arrays of `[tokens, C]` a layer in the compute dtype; K
    multiply-adds an element a forward, three times that a backward
    (the convolution again, dx and the taps' gradient) — SiLU and the
    norms are not counted, and far below the bytes' time all the same."""
    widths = conv_channels(cfg)
    channels = sum(widths.get(mixer, 0) for mixer, _ in cfg.kinds)
    elements = workload["batch"] * workload["seq"] * steps * channels
    return ((2 + 3) * 2 * cfg.conv_taps * elements,
            7.0 * np.dtype(cfg.dtype).itemsize * elements)


def read(host, trace):
    own = time_share.seconds(trace, KERNEL)
    cell = this_cell()
    steps = (traced_call_attrs("train.dispatch") or {}).get("steps")
    if own is None or cell is None or not steps:
        return None
    cfg = cell["family"].model_cfg(cell["model"])
    flops, nbytes = flops_bytes(cfg, cell["workload"], steps)
    return roofline.roofline_share(host, flops, nbytes, own)
