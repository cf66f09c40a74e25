"""Experts: how near the grouped expert matmuls run to the chip's
roofline in this family's cell — ``expert_matmul_roofline_share``'s
reckoning, with the operations and bytes of ``families/lfm2.py::
expert_matmul_flops_bytes`` (width 2048 -> 2 x 1792 -> 2048, 8 held
experts) fed the TRACED call's own ``moe_assignments_held`` and its
``moe_steps`` times the layers that HAVE experts (the leading dense
layers have none) from the call's ``train.sync`` span. A program whose
spans carry no such counters, or whose trace names no such kernel,
gives None."""

from benchmark.layer_metrics import expert_matmul_roofline_share as roofline
from benchmark.layer_metrics import expert_matmul_time_share as time_share
from benchmark.this_cell import this_cell, traced_call_attrs


def read(host, trace):
    own = time_share.seconds(trace, time_share.KERNEL)
    counters, cell = traced_call_attrs("train.sync"), this_cell()
    if own is None or cell is None or not (
            counters and "moe_assignments_held" in counters):
        return None
    family, model = cell["family"], cell["model"]
    flops, nbytes = family.expert_matmul_flops_bytes(
        model, counters["moe_assignments_held"],
        counters["moe_steps"] * family.moe_layers(model))
    return roofline.roofline_share(host, flops, nbytes, own)
