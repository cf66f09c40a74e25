"""Kernels: how near the ``short_conv`` / ``short_conv_bwd`` operations
run to the chip's roofline — max(operations over the bf16 peak, bytes
over the HBM peak of ``peaks.json``) over their traced time, in percent.
The bytes bound it: three streams of ``[tokens, D]`` in and one out, a
handful of operations an element. Both counts come from ``families/
lfm2.py::short_conv_flops_bytes`` (every array each pass reads or
writes, once; forward, rematerialised forward and backward, every conv
layer) for the steps the traced call really ran (``steps`` on its
``train.dispatch`` span). A program without the kernels or the span
gives None."""

from benchmark.layer_metrics import expert_matmul_roofline_share as roofline
from benchmark.layer_metrics import expert_matmul_time_share as time_share
from benchmark.layer_metrics.short_conv_time_share import KERNEL
from benchmark.this_cell import this_cell, traced_call_attrs


def read(host, trace):
    own = time_share.seconds(trace, KERNEL)
    cell = this_cell()
    steps = (traced_call_attrs("train.dispatch") or {}).get("steps")
    if own is None or cell is None or not steps:
        return None
    flops, nbytes = cell["family"].short_conv_flops_bytes(
        cell["model"], cell["workload"], steps)
    return roofline.roofline_share(host, flops, nbytes, own)
