"""Experts: how near the grouped expert matmuls run to the chip's
roofline in this family's cell — ``expert_matmul_roofline_share``'s
reckoning, with the operations and bytes of ``families/nemotron_h.py::
expert_matmul_flops_bytes`` (UNGATED experts: two products, 2688 -> 1856
-> 2688, 8 held; 1856 is no multiple of a lane tile, so the weight block
along it is the whole width) fed the TRACED call's own
``moe_assignments_held`` and its ``moe_steps`` times the layers that
HAVE experts from the call's ``train.sync`` span: the reading that says
what the odd width costs. A program whose spans carry no such counters,
or whose trace names no such kernel, gives None."""

from benchmark.layer_metrics.lfm2_expert_matmul_roofline_share import read  # noqa: F401
