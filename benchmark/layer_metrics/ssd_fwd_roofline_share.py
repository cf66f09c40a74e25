"""Kernels: how near the ``ssd_fwd`` operations run to the chip's
roofline — max(FLOPs over the bf16 peak, bytes over the HBM peak of
``peaks.json``) over their traced time, in percent. FLOPs are the
products the chunked equations name ((C B^T o L)(dt o X), C S_in^T, the
state's update, a head; C B^T once a group), bytes every array a pass
reads or writes once (x, y, B, C, dt and dt A, and on the rematerialised
call the chunks' entering states), both from ``families/nemotron_h.py::
ssd_flops_bytes`` for the steps the traced call really ran and the
chunks a step walks (``steps`` and ``ssm_chunks`` on its
``train.dispatch`` span). The bytes bound it on this chip. A program
without the kernel or the span's ``ssm_chunks`` gives None."""

from benchmark.layer_metrics import expert_matmul_roofline_share as roofline
from benchmark.layer_metrics import expert_matmul_time_share as time_share
from benchmark.this_cell import this_cell, traced_call_attrs


def share(host, trace, kernel: str, which: str):
    own = time_share.seconds(trace, kernel)
    cell = this_cell()
    attrs = traced_call_attrs("train.dispatch") or {}
    if own is None or cell is None or not (
            attrs.get("steps") and attrs.get("ssm_chunks")):
        return None
    flops, nbytes = cell["family"].ssd_flops_bytes(
        cell["model"], cell["workload"], attrs["steps"],
        chunks=attrs["ssm_chunks"])[which]
    return roofline.roofline_share(host, flops, nbytes, own)


def read(host, trace):
    return share(host, trace, "ssd_fwd", "fwd")
