"""Kernels: how near the ``flash_fwd`` operations run to the chip's
roofline under the block-diffusion mask — max(FLOPs over the bf16 peak,
bytes over the HBM peak of ``peaks.json``) over their traced time, in
percent. FLOPs are the score and value products INSIDE the mask only
(L ** 2 + L x b scores a head and sequence of the 4 L ** 2 plane,
4 x 128 a score): the count is of the mathematics, so a kernel that
walks more of the plane than the mask holds reads lower; bytes q, k, v,
o and the row log-sum-exp once a call; both from ``families/sdar.py::
diffusion_attention_flops_bytes`` for the steps the traced call really
ran (``steps`` on its ``train.dispatch`` span). A program without the
kernel, the span or the span's ``diffusion_block`` gives None."""

from benchmark.layer_metrics import expert_matmul_roofline_share as roofline
from benchmark.layer_metrics import expert_matmul_time_share as time_share
from benchmark.this_cell import this_cell, traced_call_attrs


def share(host, trace, kernel: str, which: str):
    own = time_share.seconds(trace, kernel)
    cell = this_cell()
    facts = traced_call_attrs("train.dispatch") or {}
    if own is None or cell is None or not facts.get("steps") \
            or not facts.get("diffusion_block"):
        return None
    flops, nbytes = cell["family"].diffusion_attention_flops_bytes(
        cell["model"], cell["workload"], facts["steps"])[which]
    return roofline.roofline_share(host, flops, nbytes, own)


def read(host, trace):
    return share(host, trace, "flash_fwd", "fwd")
