"""Kernels: how near the ``flash_fwd`` operations run to the chip's
roofline where two of five layers are full attention at 48 heads and
three walk a window of 512 — ONE forward key tile — at 64: max(FLOPs
over the bf16 peak, bytes over the HBM peak of ``peaks.json``) over
their traced time, both kinds' calls together, in percent. FLOPs are the
score and value products INSIDE each kind's mask (4 x 128 a score; a
kernel that walks tiles the mask empties, or half-filled ones, reads
low), bytes q, k, v, o and the row log-sum-exp once a call, both from
``families/laguna.py::attention_flops_bytes`` for the steps the traced
call really ran (``steps`` on its ``train.dispatch`` span). A program
without the kernel, the span or the span's ``attention_heads_window``
gives None."""

from benchmark.layer_metrics import expert_matmul_roofline_share as roofline
from benchmark.layer_metrics import expert_matmul_time_share as time_share
from benchmark.this_cell import this_cell, traced_call_attrs


def share(host, trace, kernel: str, which: str):
    own = time_share.seconds(trace, kernel)
    cell = this_cell()
    facts = traced_call_attrs("train.dispatch") or {}
    if own is None or cell is None or not facts.get("steps") \
            or not facts.get("attention_heads_window"):
        return None
    flops, nbytes = cell["family"].attention_flops_bytes(
        cell["model"], cell["workload"], facts["steps"])[which]
    return roofline.roofline_share(host, flops, nbytes, own)


def read(host, trace):
    return share(host, trace, "flash_fwd", "fwd")
