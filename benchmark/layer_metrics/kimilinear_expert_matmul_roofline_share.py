"""Experts: how near the grouped expert matmuls run to the chip's
roofline in this family's cell — ``expert_matmul_roofline_share``'s
reckoning, with the operations and bytes of ``families/kimi_linear.py::
expert_matmul_flops_bytes`` (width 2304 -> 2 x 1024 -> 2304, 8 held
experts) fed the TRACED call's own ``moe_assignments_held`` and its
``moe_steps`` times the four layers that HAVE experts (the leading dense
layer has none) from the call's ``train.sync`` span. A program whose
spans carry no such counters, or whose trace names no such kernel,
gives None."""

from benchmark.layer_metrics.lfm2_expert_matmul_roofline_share import read  # noqa: F401
