"""Kernels: how near the ``index_scores`` operations run to the chip's
roofline — max(FLOPs over the bf16 peak, bytes over the HBM peak of
``peaks.json``) over their traced time, in percent. FLOPs are the 16
products of width 64 a CAUSAL pair (2 x 16 x 64; the ReLU and the
weighted sum are not counted), bytes q_I, k_I and w read and the float32
scores it writes over the whole plane (the tiles above the diagonal as
-inf), both from ``families/keye.py::index_flops_bytes`` for the steps
the traced call really ran (``steps`` on its ``train.dispatch`` span).
The write bounds it on this chip (1 GiB a call at 16 384 tokens against
0.27 TFLOP). A program without the kernel, the span or the span's
``index_topk`` gives None."""

from benchmark.layer_metrics import expert_matmul_roofline_share as roofline
from benchmark.layer_metrics import expert_matmul_time_share as time_share
from benchmark.this_cell import this_cell, traced_call_attrs


def read(host, trace):
    own = time_share.seconds(trace, "index_scores")
    cell = this_cell()
    facts = traced_call_attrs("train.dispatch") or {}
    if own is None or cell is None or not facts.get("steps") \
            or not facts.get("index_topk"):
        return None
    flops, nbytes = cell["family"].index_flops_bytes(
        cell["model"], cell["workload"], facts["steps"])
    return roofline.roofline_share(host, flops, nbytes, own)
