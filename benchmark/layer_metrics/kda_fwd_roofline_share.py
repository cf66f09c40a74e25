"""Kernels: how near the ``kda_fwd`` operations run to the chip's
roofline — max(FLOPs over the bf16 peak, bytes over the HBM peak of
``peaks.json``) over their traced time, in percent. FLOPs are the
products the RULE names, whatever unit forms them (the decayed scores M
and P as sums over the 128 channels of every pair of a chunk, K S, Q S,
the state's update, Tm R, tril(P) V', the inverse at what the doubling
multiplies), bytes every array a pass reads or writes once (q, k, v, o,
the running sums of g a channel, beta, and the chunks' entering states),
both from ``families/kimi_linear.py::kda_flops_bytes`` for the steps the
traced call really ran and the chunks a step walks (``steps`` and
``kda_chunks`` on its ``train.dispatch`` span). A program without the
kernel or the span's ``kda_chunks`` gives None."""

from benchmark.layer_metrics import expert_matmul_roofline_share as roofline
from benchmark.layer_metrics import expert_matmul_time_share as time_share
from benchmark.this_cell import this_cell, traced_call_attrs


def share(host, trace, kernel: str, which: str):
    own = time_share.seconds(trace, kernel)
    cell = this_cell()
    attrs = traced_call_attrs("train.dispatch") or {}
    if own is None or cell is None or not (
            attrs.get("steps") and attrs.get("kda_chunks")):
        return None
    flops, nbytes = cell["family"].kda_flops_bytes(
        cell["model"], cell["workload"], attrs["steps"],
        chunks=attrs["kda_chunks"])[which]
    return roofline.roofline_share(host, flops, nbytes, own)


def read(host, trace):
    return share(host, trace, "kda_fwd", "fwd")
