"""Kernels: how near the ``flash_fwd`` operations run to the chip's
roofline at heads of 256, the benchmark's widest — max(FLOPs over the
bf16 peak, bytes over the HBM peak of ``peaks.json``) over their traced
time, in percent. FLOPs are the score and value products INSIDE the
causal mask (4 x 256 a score; a kernel that walks masked tiles reads
low), bytes q, k, v, o and the row log-sum-exp once a call, both from
``families/qwen3_next.py::attention_flops_bytes`` for the steps the
traced call really ran (``steps`` on its ``train.dispatch`` span). A
program without the kernel, the span or the span's ``delta_layers``
gives None."""

from benchmark.layer_metrics import expert_matmul_roofline_share as roofline
from benchmark.layer_metrics import expert_matmul_time_share as time_share
from benchmark.this_cell import this_cell, traced_call_attrs


def share(host, trace, kernel: str, which: str):
    own = time_share.seconds(trace, kernel)
    cell = this_cell()
    facts = traced_call_attrs("train.dispatch") or {}
    if own is None or cell is None or not facts.get("steps") \
            or not facts.get("delta_layers"):
        return None
    flops, nbytes = cell["family"].attention_flops_bytes(
        cell["model"], cell["workload"], facts["steps"])[which]
    return roofline.roofline_share(host, flops, nbytes, own)


def read(host, trace):
    return share(host, trace, "flash_fwd", "fwd")
