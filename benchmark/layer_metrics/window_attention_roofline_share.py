"""Kernels: how near the ``flash_fwd`` operations run to the chip's
roofline — max(FLOPs over the bf16 peak, bytes over the HBM peak) over
their traced time, in percent. FLOPs are the score and value products
INSIDE causal AND window only (a kernel that visits masked blocks reads
low), bytes q, k, v and o once a call, both from ``families/
smallthinker.py::window_attention_flops_bytes`` for the steps the
traced call really ran (``steps`` on its ``train.dispatch`` span)."""

from benchmark.layer_metrics import expert_matmul_roofline_share as roofline
from benchmark.layer_metrics import expert_matmul_time_share as time_share
from benchmark.this_cell import this_cell, traced_call_attrs


def read(host, trace):
    own = time_share.seconds(trace, "flash_fwd")
    cell = this_cell()
    steps = (traced_call_attrs("train.dispatch") or {}).get("steps")
    if own is None or cell is None or not steps:
        return None
    flops, nbytes = cell["family"].window_attention_flops_bytes(
        cell["model"], cell["workload"], steps)
    return roofline.roofline_share(host, flops, nbytes, own)
