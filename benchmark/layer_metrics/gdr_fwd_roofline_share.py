"""Kernels: how near the ``gdr_fwd`` operations run to the chip's
roofline — max(FLOPs over the bf16 peak, bytes over the HBM peak of
``peaks.json``) over their traced time, in percent. FLOPs are the
products the chunked equations name as ``ops/gated_delta.py`` forms them
(K S, Q S, the state's update, Tm R, tril(Q K^T o D) V' a value head; K
K^T and Q K^T once a key head; the inverse at what the kernel's own
doubling multiplies, ten products of [64, 64] a chunk and value head),
bytes every array a pass reads or writes once (q, k, v, o, the running
sums twice and beta, and on the rematerialised call the chunks' entering
states), both from ``families/qwen3_next.py::gated_delta_flops_bytes``
for the steps the traced call really ran and the chunks a step walks
(``steps`` and ``delta_chunks`` on its ``train.dispatch`` span). A
program without the kernel or the span's ``delta_chunks`` gives None."""

from benchmark.layer_metrics import expert_matmul_roofline_share as roofline
from benchmark.layer_metrics import expert_matmul_time_share as time_share
from benchmark.this_cell import this_cell, traced_call_attrs


def share(host, trace, kernel: str, which: str):
    own = time_share.seconds(trace, kernel)
    cell = this_cell()
    attrs = traced_call_attrs("train.dispatch") or {}
    if own is None or cell is None or not (
            attrs.get("steps") and attrs.get("delta_chunks")):
        return None
    flops, nbytes = cell["family"].gated_delta_flops_bytes(
        cell["model"], cell["workload"], attrs["steps"],
        chunks=attrs["delta_chunks"])[which]
    return roofline.roofline_share(host, flops, nbytes, own)


def read(host, trace):
    return share(host, trace, "gdr_fwd", "fwd")
