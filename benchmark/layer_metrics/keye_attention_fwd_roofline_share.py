"""Kernels: how near the ``flash_fwd`` operations run to the chip's
roofline under a learned selection — max(FLOPs over the bf16 peak, bytes
over the HBM peak of ``peaks.json``) over their traced time, in percent.
FLOPs are the score and value products over the SELECTED pairs only
(min(t + 1, 2048) keys a query, 4 x 128 a pair and head): the count is
of the mathematics, so a kernel that walks every causal tile for the
23 % of its pairs that are selected reads under 23 % times its
efficiency, and can never pass 100 %; bytes q, k, v, o, the row
log-sum-exp and the int8 plane once a call; both from ``families/
keye.py::attention_flops_bytes`` for the steps the traced call really
ran (``steps`` on its ``train.dispatch`` span). A program without the
kernel, the span or the span's ``index_topk`` gives None."""

from benchmark.layer_metrics import expert_matmul_roofline_share as roofline
from benchmark.layer_metrics import expert_matmul_time_share as time_share
from benchmark.this_cell import this_cell, traced_call_attrs


def share(host, trace, kernel: str, which: str):
    own = time_share.seconds(trace, kernel)
    cell = this_cell()
    facts = traced_call_attrs("train.dispatch") or {}
    if own is None or cell is None or not facts.get("steps") \
            or not facts.get("index_topk"):
        return None
    flops, nbytes = cell["family"].attention_flops_bytes(
        cell["model"], cell["workload"], facts["steps"])[which]
    return roofline.roofline_share(host, flops, nbytes, own)


def read(host, trace):
    return share(host, trace, "flash_fwd", "fwd")
