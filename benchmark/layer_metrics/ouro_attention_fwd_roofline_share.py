"""Kernels: how near the ``flash_fwd`` operations of the PLAIN causal
path run to the chip's roofline at heads of 128 — max(FLOPs over the
bf16 peak, bytes over the HBM peak of ``peaks.json``) over their traced
time, in percent. FLOPs are the score and value products INSIDE the
causal mask (4 x 128 a score; a kernel that visits masked tiles reads
low), bytes q, k, v, o and the row log-sum-exp once a call, both from
``families/ouro.py::attention_flops_bytes`` for the steps and the layer
passes the traced call really ran (``steps`` and ``layer_passes`` on its
``train.dispatch`` span: four walks of eight layers). A program without
the kernel, the span or the span's ``layer_passes`` gives None."""

from benchmark.layer_metrics import expert_matmul_roofline_share as roofline
from benchmark.layer_metrics import expert_matmul_time_share as time_share
from benchmark.this_cell import this_cell, traced_call_attrs


def share(host, trace, kernel: str, which: str):
    own = time_share.seconds(trace, kernel)
    cell = this_cell()
    facts = traced_call_attrs("train.dispatch") or {}
    if own is None or cell is None or not facts.get("steps") \
            or not facts.get("layer_passes"):
        return None
    flops, nbytes = cell["family"].attention_flops_bytes(
        cell["model"], cell["workload"], facts["steps"],
        facts["layer_passes"])[which]
    return roofline.roofline_share(host, flops, nbytes, own)


def read(host, trace):
    return share(host, trace, "flash_fwd", "fwd")
