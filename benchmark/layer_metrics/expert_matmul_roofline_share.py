"""Experts: how near the grouped expert matmuls run to the chip's
roofline — the least time the chip could take for them, max(FLOPs over
the bf16 peak, bytes over the HBM peak of ``peaks.json``), over their
traced time (``expert_matmul_time_share``'s operations), in percent.
FLOPs and bytes come from ``families/smallthinker.py::
expert_matmul_flops_bytes``, fed the TRACED call's own
``moe_assignments_held`` (the rows really multiplied; padding is not
counted) and its ``moe_steps`` from the call's ``train.sync`` span —
never the expectation. A program whose spans carry no such counters, or
whose trace names no such kernel, gives None."""

from benchmark.layer_metrics import expert_matmul_time_share as time_share
from benchmark.this_cell import this_cell, traced_call_attrs


def roofline_share(host, flops: float, nbytes: float, own_s) -> float | None:
    if own_s is None or not own_s or "peaks" not in host:
        return None
    least = max(flops / host["peaks"]["bf16_flops_per_s"],
                nbytes / host["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / own_s


def read(host, trace):
    own = time_share.seconds(trace, time_share.KERNEL)
    counters, cell = traced_call_attrs("train.sync"), this_cell()
    if own is None or cell is None or not (
            counters and "moe_assignments_held" in counters):
        return None
    family = cell["family"]
    layers = cell["model"]["num_hidden_layers"]
    flops, nbytes = family.expert_matmul_flops_bytes(
        cell["model"], counters["moe_assignments_held"],
        counters["moe_steps"] * layers)
    return roofline_share(host, flops, nbytes, own)
